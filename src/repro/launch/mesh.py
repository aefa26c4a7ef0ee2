"""Production mesh definition, single pod and multi-pod.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state. Single pod: 16x16 = 256 chips (v5e pod slice), axes
(data, model). Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model); the
``pod`` axis carries pure data parallelism (slow inter-pod links see only
gradient all-reduce, overlapped with backward — DESIGN §8).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh"]


def _auto_mesh(shape, axes):
    # Auto axes: shardings propagate from the rules in distributed/sharding
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests on CPU)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return _auto_mesh((data, model), ("data", "model"))
