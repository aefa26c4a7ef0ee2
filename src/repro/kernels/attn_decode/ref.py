"""Pure-jnp oracle for the fused decode-attention kernel.

Same contract and numerics class as ``kernel.attn_decode_pallas``: fp32
scores/softmax statistics, per-token int8 scales factored exactly where the
kernel applies them (k_scale after QK^T, v_scale into the probabilities
before PV), probabilities cast to the compute dtype for the PV contraction,
one cast back to the query dtype. Rows with ``cache_len == 0`` return zeros
(the kernel's guard; a plain softmax would return the uniform average).

In exact arithmetic this equals ``models.attention.decode_attention``
whenever every row has ``cache_len >= 1`` — which ``decode_step`` always
guarantees — so the kernel is cross-checked against both
(``tests/test_attn_decode.py``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.attn_decode.kernel import NEG_INF

__all__ = ["attn_decode_ref"]


def attn_decode_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, cache_len,
                    k_scale: jnp.ndarray | None = None,
                    v_scale: jnp.ndarray | None = None) -> jnp.ndarray:
    """q (B, 1, H, D); k/v cache (B, S, KV, D); cache_len scalar or (B,);
    optional (B, S) per-token scales for an int8 cache -> (B, 1, H, D)."""
    b, _, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / (d ** 0.5)
    qr = (q * scale).reshape(b, 1, kvh, g, d)
    kc = k_cache if k_scale is None else k_cache.astype(q.dtype)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qr, kc,
                    preferred_element_type=jnp.float32)
    if k_scale is not None:
        sc = sc * k_scale[:, None, None, None, :].astype(jnp.float32)
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.broadcast_to(
        jnp.asarray(cache_len)[..., None], (b, s))
    sc = jnp.where(valid[:, None, None, None], sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    # masked exp with the kernel's empty-row guard: all-invalid rows get
    # p == 0 everywhere (not the uniform average a raw softmax would give)
    p = jnp.where(m > NEG_INF / 2, jnp.exp(sc - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    if v_scale is not None:
        p = (p * v_scale[:, None, None, None, :].astype(jnp.float32)
             ).astype(q.dtype)
        vc = v_cache.astype(q.dtype)
    else:
        p = p.astype(v_cache.dtype)
        vc = v_cache
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, vc,
                     preferred_element_type=jnp.float32)
    out = out / l.transpose(0, 3, 1, 2, 4).astype(jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)
