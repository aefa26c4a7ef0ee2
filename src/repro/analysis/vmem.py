"""Per-kernel VMEM footprint estimation from pallas_call BlockSpecs/grid.

The paper's on-chip-memory contract, stated in bytes: a TPU core has
~16 MiB of VMEM, and a Pallas kernel's working set — every block-mapped
input/output tile (double-buffered by the pipeline: the compiler prefetches
block i+1 while block i computes) plus scratch allocations — must fit in
it, or the kernel either fails to compile on hardware or silently spills.

The estimate is read off the traced ``pallas_call`` eqn alone, no
execution: the kernel jaxpr's invars ARE the per-block refs (block shapes
with squeezed dims removed, real dtypes, memory spaces), partitioned by the
grid mapping into [scalar-prefetch][inputs][outputs][scratch].
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax.numpy as jnp

from repro.analysis.jaxpr_utils import pallas_kernel_name

__all__ = ["DEFAULT_VMEM_BUDGET", "pallas_vmem_estimate"]

# one TPU core's VMEM (~16 MiB): the hard on-chip ceiling the double-
# buffered working set must stay under
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


def _ref_bytes(aval) -> int:
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * jnp.dtype(aval.dtype).itemsize


def _is_smem(aval) -> bool:
    return "smem" in str(getattr(aval, "memory_space", "")).lower()


def pallas_vmem_estimate(eqn) -> Dict[str, Any]:
    """Estimate one pallas_call eqn's on-chip footprint.

    Returns ``{name, grid, vmem_bytes, smem_bytes, refs}`` where ``refs``
    itemizes every kernel ref as ``(kind, shape, dtype, bytes)`` with
    kind in {prefetch, in, out, scratch}. Inputs/outputs count x2
    (pipeline double buffering), scratch and scalar-prefetch count once.
    """
    gm = eqn.params["grid_mapping"]
    kernel_jaxpr = eqn.params["jaxpr"]
    n_idx = gm.num_index_operands
    n_in, n_out = gm.num_inputs, gm.num_outputs
    n_scratch = gm.num_scratch_operands
    invars = kernel_jaxpr.invars
    kinds = (["prefetch"] * n_idx + ["in"] * n_in + ["out"] * n_out
             + ["scratch"] * n_scratch)
    vmem = smem = 0
    refs: List[tuple] = []
    for kind, v in zip(kinds, invars):
        aval = v.aval
        b = _ref_bytes(aval)
        mult = 2 if kind in ("in", "out") else 1
        if kind == "prefetch" or _is_smem(aval):
            smem += b
        else:
            vmem += b * mult
        refs.append((kind, tuple(aval.shape), jnp.dtype(aval.dtype).name, b))
    return {"name": pallas_kernel_name(eqn) or "pallas_call",
            "grid": tuple(gm.grid), "vmem_bytes": int(vmem),
            "smem_bytes": int(smem), "refs": refs}
