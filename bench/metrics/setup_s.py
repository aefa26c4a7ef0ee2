"""Seconds from the process's start to the window's: imports, weights made
on the chip, engine, compilation or its load from the cache, warm-up."""


def read(rec):
    return rec["setup_s"]
