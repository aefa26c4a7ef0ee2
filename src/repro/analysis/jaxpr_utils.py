"""Shared jaxpr walkers for the contract passes.

These are THE copies of the scan helpers that used to be triplicated across
``tests/test_kernel_dispatch.py`` / ``tests/test_attn_prefill.py`` /
``tests/test_engine_spec.py`` — same semantics (pallas_call bodies are not
descended into by default: their VMEM tiles are the point of the kernels),
plus eqn attribution so lint messages can name the offending equation.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = ["subjaxprs", "as_jaxpr", "iter_eqns", "eqn_label",
           "pallas_kernel_name", "float_shapes_outside_pallas",
           "find_pallas_eqns"]


def subjaxprs(val) -> Iterator[Jaxpr]:
    """Yield every Jaxpr reachable from one eqn-params value."""
    if isinstance(val, ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from subjaxprs(v)


def as_jaxpr(jaxpr) -> Jaxpr:
    return jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr


def iter_eqns(jaxpr, *, descend_pallas: bool = False):
    """Depth-first over every eqn of ``jaxpr`` and its sub-jaxprs.

    ``pallas_call`` eqns are always yielded; their kernel BODIES are only
    descended into with ``descend_pallas=True``.
    """
    stack = [as_jaxpr(jaxpr)]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call" and not descend_pallas:
                continue
            for val in eqn.params.values():
                stack.extend(subjaxprs(val))


def _aval_str(aval) -> str:
    if hasattr(aval, "dtype") and hasattr(aval, "shape"):
        return f"{jnp.dtype(aval.dtype).name}{list(aval.shape)}"
    return str(aval)


def pallas_kernel_name(eqn) -> str:
    """The kernel function's name for a pallas_call eqn: the explicit
    ``name=`` if one was given, else the traced kernel body's function."""
    if eqn.params.get("name"):
        return eqn.params["name"]
    src = getattr(eqn.params["jaxpr"].debug_info, "func_src_info", "") or ""
    return src.split(" at ")[0]


def eqn_label(eqn) -> str:
    """Short human label naming an equation: primitive -> result avals."""
    outs = ", ".join(_aval_str(v.aval) for v in eqn.outvars
                     if hasattr(v, "aval"))
    name = eqn.primitive.name
    if name == "pallas_call":
        kname = pallas_kernel_name(eqn)
        name = f"pallas_call[{kname}]" if kname else name
    return f"{name} -> {outs}" if outs else name


def float_shapes_outside_pallas(jaxpr) -> Tuple[Dict[tuple, str], bool]:
    """All float-dtype result shapes in the graph, NOT descending into
    pallas_call bodies (their VMEM tiles are the point of the kernel).

    Returns ``({shape: label of the first eqn producing it}, saw_pallas)``
    — the keys are exactly the set the old test-local scanners returned,
    the labels are what lint messages attribute violations to.
    """
    shapes: Dict[tuple, str] = {}
    saw = False
    for eqn in iter_eqns(jaxpr, descend_pallas=False):
        if eqn.primitive.name == "pallas_call":
            saw = True
            continue
        for v in eqn.outvars:
            aval = v.aval
            if (hasattr(aval, "dtype")
                    and jnp.issubdtype(aval.dtype, jnp.floating)):
                shapes.setdefault(tuple(aval.shape), eqn_label(eqn))
    return shapes, saw


def find_pallas_eqns(jaxpr) -> List:
    """Every pallas_call eqn in the graph (not nested inside another)."""
    return [eqn for eqn in iter_eqns(jaxpr, descend_pallas=False)
            if eqn.primitive.name == "pallas_call"]
