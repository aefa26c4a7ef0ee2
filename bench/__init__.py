"""Chip benchmark of the serving engine, driven by data: see README.md."""
