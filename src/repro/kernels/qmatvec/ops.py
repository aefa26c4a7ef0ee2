"""Jit'd wrapper for the packed-container matmul kernel.

Handles leading batch dims and interpret-mode fallback on CPU. Used by the
``quant_dense.serve_apply`` kernel dispatch for the ``qp`` weight form (both
batched decode ``(B<=slots, K)`` and bucketed prefill
``(slots*bucket_len, K)`` shapes) and by the legacy MLP ``packed_apply``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.qmatmul.ops import on_tpu
from repro.kernels.qmatvec.kernel import qmatvec_pallas

__all__ = ["qmatvec"]


@functools.partial(jax.jit, static_argnames=("k", "interpret", "out_dtype"))
def qmatvec(x: jnp.ndarray, w_packed: jnp.ndarray, delta: jnp.ndarray, *,
            k: int, bias: jnp.ndarray | None = None,
            interpret: bool | None = None, out_dtype=None) -> jnp.ndarray:
    """(..., K) against container-packed (KP, N) weights -> (..., N).

    ``bias`` (N,) is fused into the kernel epilogue (applied after the
    per-channel delta rescale, in fp32); ``out_dtype`` overrides the output
    dtype (one cast from the fp32 accumulator)."""
    if interpret is None:
        interpret = not on_tpu()
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    out = qmatvec_pallas(x2, w_packed, delta, bias, out_dtype=out_dtype,
                         interpret=interpret)
    return out.reshape(*lead, w_packed.shape[-1])
