"""Serving-facing entry point for the blocked prefill/verify attention
kernel: plain (B, T, H, D) queries against the stacked (L, B, S, KV*D)
cache layout, GQA grouping / 1-sqrt(D) pre-scaling /
visibility-bound plumbing handled here, interpret mode auto-selected off
TPU (same convention as ``attn_decode`` and ``qmatmul``).

Callers express masking as per-query [lo, hi) bounds:

  * bucketed prefill — ``hi = min(t + 1, lengths[row])``: causal within the
    prompt AND the padded tail masked per row (``attn_prefill`` builds this
    from ``lengths``; pass ``window`` to also raise ``lo`` for SWA layers);
  * speculative verify — ``hi = valid`` (B, T), the per-row causal frontier
    over the live cache, built by ``verify_attention``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.attn_prefill.kernel import attn_prefill_pallas
from repro.kernels.qmatmul.ops import on_tpu

__all__ = ["attn_prefill"]


def attn_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 hi: jnp.ndarray, lo: jnp.ndarray | None = None,
                 k_scale: jnp.ndarray | None = None,
                 v_scale: jnp.ndarray | None = None, *, layer=0,
                 bt: int = 128, bs: int = 128,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Blocked online-softmax attention: q (B, T, H, D) against layer
    ``layer`` of stacked k/v (L, B, S, KV*D) (fp or int8 + per-token
    (L, B, S) scales), query ``t`` of row ``b`` seeing key positions
    ``lo[b, t] <= p < hi[b, t]`` (``lo`` defaults to 0). Returns
    (B, T, H, D) in q's dtype."""
    if interpret is None:
        interpret = not on_tpu()
    b, t, h, d = q.shape
    kv = k.shape[-1] // d
    g = h // kv
    qg = (q * (d ** -0.5)).reshape(b, t, kv, g, d)
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.int32), (b, t))
    if lo is None:
        lo = jnp.zeros((b, t), jnp.int32)
    else:
        lo = jnp.broadcast_to(jnp.asarray(lo, jnp.int32), (b, t))
    out = attn_prefill_pallas(qg, k, v, lo, hi, layer, k_scale, v_scale,
                              bt=bt, bs=bs, interpret=interpret)
    return out.reshape(b, t, h, d)
