"""Sharding-constraint context: models stay mesh-agnostic.

Step builders install a {name: PartitionSpec} table; model code calls
``constrain(x, "act")`` at strategic points. Outside a mesh/step-builder
context it is a no-op, so smoke tests on one CPU device run unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import jax

_state = threading.local()


def _table() -> Optional[Dict]:
    return getattr(_state, "table", None)


@contextlib.contextmanager
def sharding_rules(table: Dict):
    prev = _table()
    _state.table = table
    try:
        yield
    finally:
        _state.table = prev


def constrain(x, name: str):
    table = _table()
    if not table or name not in table:
        return x
    spec = table[name]
    if spec is None:
        return x
    mesh = table.get("__mesh__")
    if mesh is not None:
        # divisibility guard: drop axes that don't divide the dim (lets one
        # rule table serve every shape incl. tiny smoke/decode shapes)
        import numpy as np
        from jax.sharding import PartitionSpec as P

        def size(ax):
            if ax is None:
                return 1
            if isinstance(ax, (tuple, list)):
                return int(np.prod([mesh.shape[a] for a in ax]))
            return mesh.shape[ax]

        parts = list(spec) + [None] * (x.ndim - len(spec))
        parts = [a if (d % size(a) == 0 and size(a) > 1) else None
                 for d, a in zip(x.shape, parts)]
        if all(a is None for a in parts):
            return x
        spec = P(*parts)
    return jax.lax.with_sharding_constraint(x, spec)
