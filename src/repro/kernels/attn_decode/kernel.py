"""Pallas TPU kernel: fused single-token GQA decode attention over one
layer of the stacked (L, B, S, KV*D) cache — bf16/f32 or int8 with
per-token scales.

Decode attention is the paper's memory-bound regime applied to the KV cache:
per generated token the whole valid cache is read once and O(S*D) FLOPs are
spent on it (~1 FLOP/byte), so decode speed is cache bandwidth. The plain
``decode_attention`` einsum path (models/attention.py) pays that bill three
times over: it materializes a full fp32 (B, KV, G, 1, S) score tensor in
HBM between QK^T, softmax and PV, and it streams all S ring slots no matter
how short each row's valid prefix is. This kernel is the decode-side analog
of the paper's on-chip dataflow (weights/scores never leave the chip):

  * QK^T -> online softmax -> PV fused in VMEM: the (..., S) score tensor
    exists only one (bm, G, bs) tile at a time; the running (m, l, acc)
    flash-attention carry lives in VMEM scratch across the S grid.
  * S-blocked grid with per-row ``cache_len`` masking; blocks that are
    fully past every row's valid length are SKIPPED — the scalar-prefetched
    per-block max length clamps the K/V index map, so Pallas's pipeline
    re-targets the previous block (same index => no new HBM->VMEM copy)
    and ``pl.when`` skips the compute.
  * Fused dequant epilogue: an int8 cache is read directly; per-token
    scales factor through the contractions exactly as in
    ``decode_attention`` (scores * k_scale after QK^T, p * v_scale before
    PV), halving cache bytes vs bf16 — the engine's ``kv_bits=8`` mode.
  * M-blocking over the batch: ``bm`` slot rows ride per program, so the
    engine's batched-slots decode shape (B = slots) runs as one batched
    dot_general per (M-block, kv-head, S-block).
  * In place: the cache is the model's stacked cache as stored, the layer
    a scalar-prefetched index in the K/V (and scale) index maps. The
    decode tick carries that cache through its layer loop and writes only
    the new rows into it, so no tick slices, reshapes or copies it.

Grid: (B/bm, KV, S/bs), S innermost ("arbitrary" — sequential accumulation
into the scratch carry); B and KV are parallel. One q block is (bm, G, D)
for a single kv head (GQA group G = H // KV), K/V blocks are (bm, bs, D):
kv head j is lane block j of the stored (L, B, S, KV*D) layout, so the
block's trailing dims are TPU tiles (``D`` must be a multiple of 128 on the
chip; interpret mode takes any ``D``).

Numerics match ``attn_decode_ref`` (ref.py): fp32 scores and softmax
statistics, probabilities cast to the compute dtype for PV, fp32
accumulator, one cast to the query dtype at the end. Rows whose
``cache_len`` is 0 produce zeros (the ref does the same; ``decode_step``
always has cache_len >= 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["attn_decode_pallas", "NEG_INF"]

NEG_INF = -1e30


def _kernel(lmax_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            len_ref, o_ref, acc_ref, m_ref, l_ref, *, bs: int,
            quantized: bool):
    """One (bm, G) q tile against one (bm, bs) cache block.

    Refs: q (bm, 1, G, D); k/v (bm, bs, D) — one kv head's lanes of one
    layer of the (L, B, S, KV*D) cache; ks/vs (bm, bs) fp32 scales (None
    when not quantized); len (bm, 1, 1) int32; out (bm, 1, G, D).
    Scratch: acc (bm, G, D) fp32; m/l (bm, G, 1) fp32 — the online-softmax
    carry, valid across the innermost S grid dimension.
    """
    i = pl.program_id(0)
    s_blk = pl.program_id(2)
    start = s_blk * bs

    @pl.when(s_blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip blocks past every row's valid length (their K/V DMA was already
    # elided by the clamped index map — see attn_decode_pallas)
    @pl.when(start < lmax_ref[i])
    def _compute():
        q = q_ref[:, 0]                                 # (bm, G, D)
        k = k_ref[...]                                  # (bm, bs, D)
        sc = jax.lax.dot_general(                       # (bm, G, bs) fp32
            q, k.astype(q.dtype),
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if quantized:
            sc = sc * ks_ref[...][:, None, :]           # (bm, 1, bs) bcast
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (sc.shape[0], 1, bs), 2)         # (bm, 1, bs)
        sc = jnp.where(pos < len_ref[...], sc, NEG_INF)  # len (bm, 1, 1)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        # `alive` guards rows with no valid position yet: m_new == NEG_INF
        # there, and exp(sc - m_new) would be exp(0) = 1 for masked slots
        alive = m_new > NEG_INF / 2                     # (bm, G, 1)
        p = jnp.where(alive, jnp.exp(sc - m_new), 0.0)  # (bm, G, bs)
        corr = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...]                                  # (bm, bs, D)
        if quantized:
            p = (p * vs_ref[...][:, None, :]).astype(q.dtype)
            v = v.astype(q.dtype)
        else:
            p = p.astype(v.dtype)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s_blk == pl.num_programs(2) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)              # (bm, G, 1)
        o_ref[:, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def divisor_block(n: int, want: int, align: int) -> int:
    """The largest divisor of ``n`` that is at most ``want`` and a multiple
    of ``align``, else ``n`` itself (one block over the whole dim). The
    attention kernels block the stacked cache with it: a block that divides
    the cache needs no padded copy of it."""
    for blk in range(min(want, n), 0, -1):
        if n % blk == 0 and blk % align == 0:
            return blk
    return n


@functools.partial(jax.jit,
                   static_argnames=("bm", "bs", "interpret"))
def attn_decode_pallas(q: jnp.ndarray, k_cache: jnp.ndarray,
                       v_cache: jnp.ndarray, cache_len: jnp.ndarray,
                       layer=0, k_scale: jnp.ndarray | None = None,
                       v_scale: jnp.ndarray | None = None, *,
                       bm: int = 8, bs: int = 128,
                       interpret: bool = False) -> jnp.ndarray:
    """q (B, KV, G, D) PRE-SCALED by 1/sqrt(D); k/v cache (L, B, S, KV*D),
    the stacked cache as stored, kv head j in lane block j; cache_len (B,)
    int32; ``layer`` the int32 index of the layer to read; optional
    per-token scales (L, B, S) fp32 for an int8 cache. Returns
    (B, KV, G, D) in q's dtype.

    The cache is read in place: the layer is selected in the index maps
    (scalar-prefetched), and the blocks divide the cache (``divisor_block``:
    ``bm`` rows and ``bs`` positions at most, tile-aligned on the chip), so
    no slice, view or padded copy of the cache is made.
    """
    b, kv, g, d = q.shape
    s = k_cache.shape[2]
    quantized = k_scale is not None
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))

    # on the chip the scale blocks (bm, bs) sit on the (B, S) tile dims
    bm = divisor_block(b, bm, 1 if interpret else 8)
    bs = divisor_block(s, bs, 1 if interpret else 128)
    nb, ns = b // bm, s // bs
    # per-M-block max valid length, scalar-prefetched: the index maps clamp
    # the S block index with it, so fully-invalid blocks re-target the last
    # valid block — same index as the previous grid step => the pipeline
    # skips the HBM->VMEM copy (the "don't stream the whole ring" part)
    lmax = jnp.max(lens.reshape(nb, bm), axis=1)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    len3 = lens[:, None, None]

    def _sblk(i, s_blk, lmax_ref):
        nblk = jnp.maximum((lmax_ref[i] + bs - 1) // bs, 1)
        return jnp.minimum(s_blk, nblk - 1)

    def kv_idx(i, j, s_blk, lmax_ref, layer_ref):
        return (layer_ref[0], i, _sblk(i, s_blk, lmax_ref), j)

    def sc_idx(i, j, s_blk, lmax_ref, layer_ref):
        return (layer_ref[0], i, _sblk(i, s_blk, lmax_ref))

    def row_idx(i, j, s_blk, lmax_ref, layer_ref):
        return (i, j, 0, 0)

    in_specs = [
        pl.BlockSpec((bm, 1, g, d), row_idx),
        pl.BlockSpec((None, bm, bs, d), kv_idx),
        pl.BlockSpec((None, bm, bs, d), kv_idx),
    ]
    args = [q, k_cache, v_cache]
    if quantized:
        in_specs += [pl.BlockSpec((None, bm, bs), sc_idx),
                     pl.BlockSpec((None, bm, bs), sc_idx)]
        args += [k_scale, v_scale]
    in_specs.append(pl.BlockSpec((bm, 1, 1),
                                 lambda i, j, s_blk, lmax, layer: (i, 0, 0)))
    args.append(len3)

    if quantized:
        kernel = functools.partial(_kernel, bs=bs, quantized=True)
    else:                  # no scale operands: splice None refs back in
        def kernel(lmax_ref, layer_ref, q_ref, k_ref, v_ref, len_ref, o_ref,
                   acc_ref, m_ref, l_ref):
            return _kernel(lmax_ref, layer_ref, q_ref, k_ref, v_ref, None,
                           None, len_ref, o_ref, acc_ref, m_ref, l_ref,
                           bs=bs, quantized=False)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, kv, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, 1, g, d), row_idx),
        scratch_shapes=[
            pltpu.VMEM((bm, g, d), jnp.float32),        # acc
            pltpu.VMEM((bm, g, 1), jnp.float32),        # running max
            pltpu.VMEM((bm, g, 1), jnp.float32),        # running sum
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        interpret=interpret,
    )(lmax, layer, *args)
