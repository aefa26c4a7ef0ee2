"""Pallas TPU kernel: blocked online-softmax prefill/verify attention —
(B, T, KV, G, D) queries against one layer of (L, B, S, KV*D) keys/values,
bf16/f32 or int8 with per-token scales, per-(row, query) visibility bounds.

This is the flash-attention analogue of the paper's on-chip dataflow applied
to the two multi-token serving paths: bucketed-prefill admission (T = the
admission bucket, S = T, self-attention over the prompt) and speculative
verify (T = spec_k+1 draft rows, S = the live cache). The plain einsum
paths materialize a full fp32 (B, KV, G, T, S) score tensor in HBM per
layer — quadratic in the prompt for admission, and the per-tick latency
floor of speculative verify. Here the (bt, G, bs) score tile is the ONLY
score storage and it never leaves VMEM:

  * QK^T -> online softmax -> PV fused per tile; the running (m, l, acc)
    flash carry lives in VMEM scratch across the S grid dimension.
  * Per-(row, query) masking: query ``t`` of row ``b`` sees key positions
    ``lo[b, t] <= p < hi[b, t]``. Bucketed prefill sets
    ``hi = min(t+1, lengths[b])`` (causal AND padded tail masked per row —
    the bucketed-prefill rule), verify passes its ``valid`` counts, and a
    sliding window raises ``lo`` to ``t - window + 1``.
  * DMA-level block skipping: the scalar-prefetched per-(row, q-block)
    bounds clamp the K/V index maps, so S blocks entirely past ``hi`` (the
    causal upper triangle + padded tails) or before ``lo`` (outside the
    window) re-target an adjacent block — same index as the previous grid
    step, so the pipeline elides the HBM->VMEM copy — and ``pl.when``
    skips their compute.
  * Fused dequant epilogue: an int8 K/V source is read directly; per-token
    scales factor through the contractions exactly as in the einsum paths
    (scores * k_scale after QK^T, p * v_scale into the probabilities
    before PV) — the engine's ``kv_bits=8`` cache needs no dequant pass.

Grid: (B, T/bt, KV, S/bs), S innermost ("arbitrary" — sequential
accumulation into the scratch carry). One q block is (bt, G, D) for a
single kv head; K/V blocks are (bs, D), kv head j read as lane block j of
the stored (L, B, S, KV*D) layout, the layer a scalar-prefetched index, so
verify reads the decode cache in place (``D`` must be a multiple of 128 on
the chip; interpret mode takes any ``D``).

Numerics match ``attn_prefill_ref`` (ref.py): fp32 scores and softmax
statistics, probabilities cast to the compute dtype for PV, fp32
accumulator, one cast to the query dtype at the end. Rows whose visible
range is empty (``hi <= lo``) produce zeros — the same empty-row guard as
``attn_decode`` (a raw softmax over pure NEG_INF would emit the uniform
average, or NaN with a true -inf fill).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.attn_decode.kernel import divisor_block

__all__ = ["attn_prefill_pallas", "NEG_INF"]

NEG_INF = -1e30


def _kernel(hmax_ref, lmin_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref,
            vs_ref, lo_ref, hi_ref, o_ref, acc_ref, m_ref, l_ref, *, bs: int,
            quantized: bool):
    """One (bt, G) q tile of one batch row against one (bs,) K/V block.

    Refs: q (1, bt, 1, G, D); k/v (bs, D) — one kv head's lanes of one row
    of one layer of the (L, B, S, KV*D) cache; ks/vs (B, bs) fp32 scales of
    every row (None when not quantized: a one-row block is no TPU tile, so
    the kernel picks row ``i``); lo/hi (1, bt, 1, 1) int32; out
    (1, bt, 1, G, D).
    Scratch: acc (bt, G, D) fp32; m/l (bt, G, 1) fp32 — the online-softmax
    carry, valid across the innermost S grid dimension.
    """
    i = pl.program_id(0)
    t = pl.program_id(1)
    s_blk = pl.program_id(3)
    start = s_blk * bs

    @pl.when(s_blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip blocks with no visible position for ANY query of this tile
    # (their K/V DMA was already elided by the clamped index maps)
    @pl.when((start < hmax_ref[i, t]) & (start + bs > lmin_ref[i, t]))
    def _compute():
        q = q_ref[0, :, 0]                              # (bt, G, D)
        k = k_ref[...]                                  # (bs, D)
        sc = jax.lax.dot_general(                       # (bt, G, bs) fp32
            q, k.astype(q.dtype),
            dimension_numbers=(((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quantized:
            sc = sc * ks_ref[pl.ds(i, 1), :]            # (1, bs) bcast
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (sc.shape[0], 1, bs), 2)         # (bt, 1, bs)
        valid = (pos < hi_ref[0]) & (pos >= lo_ref[0])  # lo/hi (bt, 1, 1)
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        # `alive` guards rows with no valid position yet: m_new == NEG_INF
        # there, and exp(sc - m_new) would be exp(0) = 1 for masked slots
        alive = m_new > NEG_INF / 2                     # (bt, G, 1)
        p = jnp.where(alive, jnp.exp(sc - m_new), 0.0)  # (bt, G, bs)
        corr = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...]                                  # (bs, D)
        if quantized:
            p = (p * vs_ref[pl.ds(i, 1), :]).astype(q.dtype)
            v = v.astype(q.dtype)
        else:
            p = p.astype(v.dtype)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s_blk == pl.num_programs(3) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)              # (bt, G, 1)
        o_ref[0, :, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bt", "bs", "interpret"))
def attn_prefill_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        lo: jnp.ndarray, hi: jnp.ndarray, layer=0,
                        k_scale: jnp.ndarray | None = None,
                        v_scale: jnp.ndarray | None = None, *,
                        bt: int = 128, bs: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """q (B, T, KV, G, D) PRE-SCALED by 1/sqrt(D); k/v (L, B, S, KV*D), the
    stacked cache as stored (kv head j in lane block j), read at layer
    ``layer`` (int32; a prompt's own K/V is one layer, ``k[None]``);
    lo/hi (B, T) int32 per-query visibility bounds (query t of row b sees
    positions lo <= p < hi); optional per-token scales (L, B, S) fp32 for
    an int8 K/V source. Returns (B, T, KV, G, D) in q's dtype.

    ``bt`` query rows x ``bs`` key positions per program. Queries are
    zero-padded to a multiple of ``bt``, padded rows masked via hi = 0 (the
    empty-row guard zeroes their output); ``bs`` is a divisor of S
    (``divisor_block``), so K/V are read in place, never copied.
    """
    b, t, kv, g, d = q.shape
    s = k.shape[2]
    quantized = k_scale is not None
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.int32), (b, t))
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.int32), (b, t))

    bt = min(bt, t)
    bs = divisor_block(s, bs, 1 if interpret else 128)
    tp = -(-t // bt) * bt
    if tp != t:
        q = jnp.pad(q, ((0, 0), (0, tp - t)) + ((0, 0),) * 3)
        lo = jnp.pad(lo, ((0, 0), (0, tp - t)))
        hi = jnp.pad(hi, ((0, 0), (0, tp - t)))         # pad queries: hi 0
    nt, ns = tp // bt, s // bs
    # per-(row, q-block) visibility bounds, scalar-prefetched: the index
    # maps clamp the S block index into [first needed, last needed], so
    # blocks past the causal frontier / padded tail (or before the sliding
    # window) re-target an adjacent block — same index as the previous grid
    # step => the pipeline skips the HBM->VMEM copy
    hmax = jnp.max(hi.reshape(b, nt, bt), axis=-1)
    lmin = jnp.min(lo.reshape(b, nt, bt), axis=-1)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def _sblk(i, tt, s_blk, hmax_ref, lmin_ref):
        nhi = jnp.maximum((hmax_ref[i, tt] + bs - 1) // bs, 1)
        return jnp.minimum(jnp.maximum(s_blk, lmin_ref[i, tt] // bs),
                           nhi - 1)

    def kv_idx(i, tt, j, s_blk, hmax_ref, lmin_ref, layer_ref):
        return (layer_ref[0], i, _sblk(i, tt, s_blk, hmax_ref, lmin_ref), j)

    def sc_idx(i, tt, j, s_blk, hmax_ref, lmin_ref, layer_ref):
        return (layer_ref[0], 0, _sblk(i, tt, s_blk, hmax_ref, lmin_ref))

    def q_idx(i, tt, j, s_blk, hmax_ref, lmin_ref, layer_ref):
        return (i, tt, j, 0, 0)

    def b_idx(i, tt, j, s_blk, hmax_ref, lmin_ref, layer_ref):
        return (i, tt, 0, 0)

    # bounds get unit trailing dims so each block spans the full array dim
    in_specs = [
        pl.BlockSpec((1, bt, 1, g, d), q_idx),
        pl.BlockSpec((None, None, bs, d), kv_idx),
        pl.BlockSpec((None, None, bs, d), kv_idx),
    ]
    args = [q, k, v]
    if quantized:
        in_specs += [pl.BlockSpec((None, b, bs), sc_idx),
                     pl.BlockSpec((None, b, bs), sc_idx)]
        args += [k_scale, v_scale]
    in_specs += [pl.BlockSpec((1, bt, 1, 1), b_idx),
                 pl.BlockSpec((1, bt, 1, 1), b_idx)]
    args += [lo[..., None, None], hi[..., None, None]]

    if quantized:
        kernel = functools.partial(_kernel, bs=bs, quantized=True)
    else:                  # no scale operands: splice None refs back in
        def kernel(hmax_ref, lmin_ref, layer_ref, q_ref, k_ref, v_ref,
                   lo_ref, hi_ref, o_ref, acc_ref, m_ref, l_ref):
            return _kernel(hmax_ref, lmin_ref, layer_ref, q_ref, k_ref,
                           v_ref, None, None, lo_ref, hi_ref, o_ref,
                           acc_ref, m_ref, l_ref, bs=bs, quantized=False)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nt, kv, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bt, 1, g, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((bt, g, d), jnp.float32),        # acc
            pltpu.VMEM((bt, g, 1), jnp.float32),        # running max
            pltpu.VMEM((bt, g, 1), jnp.float32),        # running sum
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tp, kv, g, d), q.dtype),
        interpret=interpret,
    )(hmax, lmin, layer, *args)
    return out[:, :t]
