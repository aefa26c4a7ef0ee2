"""Attention: GQA with chunked online-softmax (memory O(seq·chunk), never
materializes the full score matrix) + sliding-window path + decode step.

Shapes: q (B, Lq, H, D); k/v (B, Lkv, KV, D) for prompt attention; the
decode and verify entries read the stacked cache as stored, (L, B, S, KV*D)
with a layer index; GQA groups G = H // KV.
The chunked scan keeps running (max, sum, acc) per q position — the standard
flash-attention recurrence expressed in pure JAX (``jax.lax.scan`` over KV
chunks). XLA fuses each chunk's QK^T+softmax+PV; on TPU the same structure is
what a Pallas flash kernel would tile, so the dry-run HLO reflects realistic
memory behaviour at 32k/500k sequence lengths.

Three entry points are kernel-dispatched on ``mode`` ("auto" | "kernel" |
"ref", mirroring ``quant_dense.serve_apply``; 'auto' picks the Pallas
kernel on TPU, the einsum/chunked paths elsewhere):

  * ``decode_attention`` -> ``repro.kernels.attn_decode`` (one q row per
    step, QK^T -> online softmax -> PV in VMEM, per-row cache_len block
    skipping, int8-cache dequant epilogue);
  * ``prefill_attention`` -> ``repro.kernels.attn_prefill`` (blocked
    online-softmax over (q block, key block) tiles; per-row rule: query t
    sees key j iff j <= t AND j < lengths[row], i.e. causal within the
    prompt and the padded tail masked per row; SWA raises the lower bound);
  * ``verify_attention`` -> the same attn_prefill kernel with T = spec_k+1
    query rows and hi = the per-row ``valid`` counts over the live cache.

In every case the ref path is the plain einsum/chunked formulation below,
which the kernel packages' ``ref.py`` oracles match term for term. Masked
softmax rows that are entirely invalid (a zero-valid-length row from engine
padding) produce zeros in both paths — never NaN or the uniform v average.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["chunked_attention", "decode_attention", "prefill_attention",
           "sliding_window_attention", "verify_attention",
           "resolve_attn_mode", "ATTN_MODES"]

NEG_INF = -1e30

ATTN_MODES = ("auto", "kernel", "ref")


def resolve_attn_mode(mode: str) -> str:
    """'auto' -> fused Pallas decode kernel on TPU, einsum path elsewhere."""
    if mode == "auto":
        from repro.kernels.qmatmul.ops import on_tpu
        return "kernel" if on_tpu() else "ref"
    if mode not in ("kernel", "ref"):
        raise ValueError(f"attn mode must be one of {ATTN_MODES}, "
                         f"got {mode!r}")
    return mode


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q (B,Lq,KV,G,D) x k (B,Lc,KV,D) -> (B,KV,G,Lq,Lc), fp32."""
    return jnp.einsum("bqkgd,bckd->bkgqc", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """p (B,KV,G,Lq,Lc) x v (B,Lc,KV,D) -> (B,Lq,KV,G,D)."""
    return jnp.einsum("bkgqc,bckd->bqkgd", p, v)


def _guarded_softmax(sc: jnp.ndarray) -> jnp.ndarray:
    """Softmax over the last axis of NEG_INF-masked fp32 scores with the
    empty-row guard: a row whose every slot is masked would softmax to the
    uniform average over v (exp(NEG_INF - NEG_INF) = 1 per slot — or NaN
    with a true -inf fill); guarded rows produce exact zeros instead,
    matching the attn_decode / attn_prefill kernels."""
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.where(m > NEG_INF / 2, jnp.exp(sc - m), 0.0)
    return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, chunk: int = 1024,
                      q_offset: int = 0) -> jnp.ndarray:
    """Online-softmax attention over KV chunks.

    ``q_offset``: absolute position of q[0] relative to k[0] (prefill: 0;
    chunked decode batches: cache length).
    """
    b, lq, h, d = q.shape
    _, lkv, kv, _ = k.shape
    g = h // kv
    chunk = min(chunk, lkv)
    nchunks = -(-lkv // chunk)
    pad = nchunks * chunk - lkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / (d ** 0.5)
    qr = (q * scale).reshape(b, lq, kv, g, d)
    kc = k.reshape(b, nchunks, chunk, kv, d)
    vc = v.reshape(b, nchunks, chunk, kv, d)
    q_pos = q_offset + jnp.arange(lq)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, c0 = xs                                    # chunk kv + start idx
        s = _gqa_scores(qr, kb)                            # (B,KV,G,Lq,C)
        kv_pos = c0 + jnp.arange(chunk)
        mask = jnp.broadcast_to(kv_pos[None, :] < lkv, (lq, chunk))  # pad guard
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # empty-row guard: rows with no valid position yet (all-false mask,
        # e.g. a negative q_offset) keep p = 0 instead of exp(0) = 1
        alive = m_new > NEG_INF / 2
        p = jnp.where(alive[..., None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.where(alive, jnp.exp(m - m_new), 1.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p.astype(v.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, lq), jnp.float32)
    a0 = jnp.zeros((b, kv, g, lq, d), jnp.float32)
    starts = jnp.arange(nchunks) * chunk
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), starts))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, lq, h, d).astype(q.dtype)


def sliding_window_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                             window: int, chunk: int = 1024) -> jnp.ndarray:
    """Causal SWA: each q sees at most ``window`` previous kv. O(L*window).

    Processes q in chunks; per q-chunk slices kv[start-window : start+chunk]
    (static size window+chunk) and runs plain masked attention on the slice.
    """
    b, l, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    chunk = min(chunk, l)
    nq = -(-l // chunk)
    pad = nq * chunk - l
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    span = window + chunk
    # left-pad kv by `window` so every slice is in range
    kp = jnp.pad(k, ((0, 0), (window, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, pad), (0, 0), (0, 0)))
    scale = 1.0 / (d ** 0.5)

    def q_block(i):
        s0 = i * chunk                                      # q block start
        qb = jax.lax.dynamic_slice_in_dim(q, s0, chunk, 1) * scale
        kb = jax.lax.dynamic_slice_in_dim(kp, s0, span, 1)  # abs pos s0-window..
        vb = jax.lax.dynamic_slice_in_dim(vp, s0, span, 1)
        qr = qb.reshape(b, chunk, kv, g, d)
        sc = _gqa_scores(qr, kb)                            # (B,KV,G,chunk,span)
        qpos = s0 + jnp.arange(chunk)
        kpos = s0 - window + jnp.arange(span)
        mask = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - window) \
            & (kpos[None, :] >= 0) & (kpos[None, :] < l)
        sc = jnp.where(mask[None, None, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return _gqa_out(p, vb).reshape(b, chunk, h, d)

    _, out = jax.lax.scan(lambda c, i: (c, q_block(i)), None,
                          jnp.arange(nq))  # (nq,B,chunk,H,D)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nq * chunk, h, d)
    return out[:, :l].astype(q.dtype)


def prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      lengths=None, window: int = 0, mode: str = "auto",
                      chunk: int = 1024,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Prompt self-attention for prefill/admission. q (B, T, H, D) against
    k/v (B, T, KV, D); ``lengths`` (B,) optional per-row valid prompt
    lengths (bucketed admission pads rows up to the bucket); ``window`` > 0
    selects sliding-window masking.

    'kernel' routes to ``repro.kernels.attn_prefill``: blocked online
    softmax — the fp32 score tile never leaves VMEM, no (B, ..., T, T)
    tensor in HBM — with the bucketed-prefill rule applied per row (query t
    sees key j iff j <= t AND j < lengths[row]; SWA additionally requires
    j > t - window) and DMA-level skipping of key blocks past each q
    block's causal frontier. 'ref' is the chunked/SWA scan below; it masks
    causally only — identical at every real query position (j <= t <
    lengths already implies j < lengths), while padded-query rows (t >=
    lengths[row]) may differ; their cache entries are masked downstream by
    per-row lengths and overwritten as the row advances, so decoded tokens
    agree. 'auto' picks the kernel on TPU."""
    if resolve_attn_mode(mode) == "kernel":
        from repro.kernels.attn_prefill.ops import attn_prefill
        b, t = q.shape[0], q.shape[1]
        pos = jnp.arange(t, dtype=jnp.int32)
        hi = jnp.broadcast_to(pos[None, :] + 1, (b, t))
        if lengths is not None:
            lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
            hi = jnp.minimum(hi, lens[:, None])
        lo = None
        if window:
            lo = jnp.broadcast_to(jnp.maximum(pos - (window - 1), 0)[None],
                                  (b, t))
        # the prompt's own K/V as a one-layer cache in the stored layout
        return attn_prefill(q, k.reshape(b, t, -1)[None],
                            v.reshape(b, t, -1)[None], hi, lo=lo,
                            interpret=interpret)
    if window:
        return sliding_window_attention(q, k, v, window=window, chunk=chunk)
    return chunked_attention(q, k, v, causal=True, chunk=chunk)


def verify_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, valid: jnp.ndarray,
                     k_scale=None, v_scale=None, *, layer=0,
                     mode: str = "auto",
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Multi-token decode attention for speculative verify. q: (B, T, H, D)
    against layer ``layer`` of the stacked (L, B, S, KV*D) cache; ``valid``
    (B, T) is the number of visible cache entries per query (its own
    just-written position included), so the T draft positions are causally
    masked against each other AND against the live prefix — the
    bucketed-prefill masking rule applied to the decode cache. Term-for-term the T>1 generalization of :func:`decode_attention`'s
    reference path (same contractions, same int8 per-token scale factoring),
    which keeps verify logits aligned with the sequential decode logits.

    'kernel' routes to ``repro.kernels.attn_prefill`` as its T-row
    specialization (T = spec_k+1, hi = ``valid``): no (B, ..., T, S) score
    tensor in HBM and per-row DMA skipping of cache blocks past the causal
    frontier — S is the full decode cache, so this bounds the verify
    latency that caps speculative throughput. 'ref' is the einsum below
    with the guarded softmax (zero-valid rows produce zeros, not NaN);
    'auto' picks the kernel on TPU."""
    b, t, h, d = q.shape
    if resolve_attn_mode(mode) == "kernel":
        from repro.kernels.attn_prefill.ops import attn_prefill
        return attn_prefill(q, k_cache, v_cache, valid, k_scale=k_scale,
                            v_scale=v_scale, layer=layer,
                            interpret=interpret)
    k_cache, v_cache, k_scale, v_scale = _layer_of(
        k_cache, v_cache, k_scale, v_scale, layer, d)
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / (d ** 0.5)
    qr = (q * scale).reshape(b, t, kvh, g, d)
    kc = k_cache if k_scale is None else k_cache.astype(q.dtype)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qr, kc,
                    preferred_element_type=jnp.float32)
    if k_scale is not None:
        sc = sc * k_scale[:, None, None, None, :]
    pos = jnp.arange(s)
    mask = pos[None, None, :] < valid[:, :, None]           # (B, T, S)
    sc = jnp.where(mask[:, None, None], sc, NEG_INF)
    p = _guarded_softmax(sc)
    if v_scale is not None:
        p = (p * v_scale[:, None, None, None, :]).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(q.dtype))
    else:
        p = p.astype(v_cache.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache)
    return out.reshape(b, t, h, d).astype(q.dtype)


def _layer_of(k_cache, v_cache, k_scale, v_scale, layer, d):
    """The reference paths' view of layer ``layer`` of a stacked
    (L, B, S, KV*D) cache: (B, S, KV, D) K/V and (B, S) scales. A slice
    and a relayout, so it stays off the kernel path."""
    _, b, s, kvd = k_cache.shape
    k_cache = k_cache[layer].reshape(b, s, kvd // d, d)
    v_cache = v_cache[layer].reshape(b, s, kvd // d, d)
    if k_scale is not None:
        k_scale, v_scale = k_scale[layer], v_scale[layer]
    return k_cache, v_cache, k_scale, v_scale


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_len: jnp.ndarray, k_scale=None, v_scale=None, *,
                     layer=0, mode: str = "auto",
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """One-token attention against layer ``layer`` (int32, may be traced)
    of the stacked (L, B, S, KV*D) cache. q: (B, 1, H, D).

    ``cache_len``: scalar or (B,) number of valid cache entries. O(S) compute,
    bound by cache bandwidth — the paper's memory-bound regime on TPU.

    int8 cache support: pass per-token ``k_scale``/``v_scale`` (L, B, S); the
    scales factor exactly through the score and value contractions, so the
    einsums read the int8 arrays directly (half the bf16 cache traffic).

    ``mode`` selects the implementation: 'kernel' runs the fused Pallas
    kernel (``repro.kernels.attn_decode``: blocked online softmax in VMEM —
    no (..., S) score tensor in HBM — per-row valid-length block skipping,
    int8 dequant fused into the epilogue, the layer read in place; interpret
    mode off-TPU, for tests), 'ref' the einsum path below, 'auto' (default)
    kernel on TPU.
    """
    if resolve_attn_mode(mode) == "kernel":
        from repro.kernels.attn_decode.ops import attn_decode
        return attn_decode(q, k_cache, v_cache, cache_len, k_scale, v_scale,
                           layer=layer, interpret=interpret)
    b, _, h, d = q.shape
    k_cache, v_cache, k_scale, v_scale = _layer_of(
        k_cache, v_cache, k_scale, v_scale, layer, d)
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / (d ** 0.5)
    qr = (q * scale).reshape(b, 1, kvh, g, d)
    kc = k_cache if k_scale is None else k_cache.astype(q.dtype)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qr, kc,
                    preferred_element_type=jnp.float32)
    if k_scale is not None:
        sc = sc * k_scale[:, None, None, None, :]
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.broadcast_to(jnp.asarray(cache_len)[..., None], (b, s))
    sc = jnp.where(valid[:, None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    if v_scale is not None:
        p = (p * v_scale[:, None, None, None, :]).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(q.dtype))
    else:
        p = p.astype(v_cache.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache)
    return out.reshape(b, 1, h, d).astype(q.dtype)
