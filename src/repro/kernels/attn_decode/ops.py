"""Jit'd wrapper for the fused decode-attention kernel.

Takes the model-side decode shapes (q (B, 1, H, D) against one layer of the
stacked (L, B, S, KV*D) cache, scalar or per-row ``cache_len``, optional
(L, B, S) int8-cache scales),
handles the GQA reshape + 1/sqrt(D) pre-scale, and falls back to interpret
mode off-TPU (slow; for tests). Used by the ``models.attention``
``decode_attention(..., mode="kernel")`` dispatch — the decode-side
counterpart of ``quant_dense.serve_apply``'s qmatvec/qmatmul routing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.attn_decode.kernel import attn_decode_pallas

__all__ = ["attn_decode"]


@functools.partial(jax.jit, static_argnames=("bm", "bs", "interpret"))
def attn_decode(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                cache_len, k_scale: jnp.ndarray | None = None,
                v_scale: jnp.ndarray | None = None, *, layer=0, bm: int = 8,
                bs: int = 128,
                interpret: bool | None = None) -> jnp.ndarray:
    """Fused one-token GQA attention: q (B, 1, H, D) against layer
    ``layer`` of the stacked cache (L, B, S, KV*D) -> (B, 1, H, D).
    ``cache_len`` scalar or (B,); pass per-token ``k_scale``/``v_scale``
    (L, B, S) to read an int8 cache directly. The KV head count is read off
    the cache's last dim (KV*D) and q's head size.

    ``bm`` batch rows ride per program (M-blocking over the engine's slot
    dimension); ``bs`` is the cache block — the score tile never exceeds
    (bm, G, bs) and never leaves VMEM.
    """
    if interpret is None:
        from repro.kernels.qmatmul.ops import on_tpu
        interpret = not on_tpu()
    b, _, h, d = q.shape
    kv = k_cache.shape[-1] // d
    g = h // kv
    scale = 1.0 / (d ** 0.5)
    q4 = (q * scale).reshape(b, kv, g, d)
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    out = attn_decode_pallas(q4, k_cache, v_cache, lens, layer, k_scale,
                             v_scale, bm=bm, bs=bs, interpret=interpret)
    return out.reshape(b, 1, h, d)
