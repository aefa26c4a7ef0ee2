"""Pallas TPU kernel: decode/prefill matmul streaming 3.2-bit packed weights.

THE paper's regime on TPU (DESIGN §3): decode GEMMs have arithmetic intensity
~1 FLOP/byte, entirely HBM-bandwidth-bound. This kernel streams the weight
matrix in the *container* format — 10 3-bit fields per int32 word, exactly the
paper's BRAM image — so HBM traffic is 3.2 bits/weight instead of 16 (bf16).

Layout: words (KP, N) int32 where field i of word j in column n holds weight
k = 10j+i (packed along K, see core.packing.pack_matrix). Field-major order:
a dot product does not care about the order of K, so

    x @ unpack(w) = sum_i x[:, i::10] @ field_i(w).

Each grid step extracts the ten 3-bit planes of its (bkp, bn) word tile by
two shifts (which also sign-extend), converts them to the activation dtype
and feeds each straight to its own MXU dot against the matching activation
plane. Nothing in the weight tile is stacked or reordered back into K order:
that re-interleave costs about four times the unpack itself in vector
relayouts. The activation is split into its planes instead, once per M
block, in VMEM (``_split_fields``): XLA would reorder it through a
lane-padded (M, KP, 10) copy in HBM, four per layer. fp32 accumulator in
VMEM scratch across the KP grid; the epilogue applies the per-channel delta
and the (optional, fused) bias.

The grid covers M too: the same kernel serves batched decode (M = active
slots) and bucketed prefill (M = slots x bucket_len) — weight words stream
once per M-tile regardless of how many rows ride in it, which is the paper's
batch-amortization argument verbatim. ``qmatvec_blocks`` picks the blocks
from the shape; the weight words are never padded or copied per call: a
partial last K block meets a zero activation tail, and whatever levels its
unread rows hold contribute exactly 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["qmatvec_pallas", "qmatvec_blocks", "FIELDS"]

FIELDS = 10  # 3-bit fields per int32 container word
_BITS = 3
_LANES = 128
# a prefill M block holds 256 rows of x three times (two buffers and its
# planes) and a K block's transpose: 20 MB at K 8960, over the 16 MiB a
# kernel gets by default
_VMEM_LIMIT = 32 << 20
_BM = 256        # rows per M block in prefill
_BN = 256        # columns per N block
_BKP_ONE = 256   # a KP up to this is one K block
_BKP = 128       # otherwise a K block divides KP in multiples of this


def _lanes(n: int) -> int:
    return -(-n // _LANES) * _LANES


def qmatvec_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(bm, bn, bkp) for x (m, k) against packed words (ceil(k/10), n).

    bm = m up to 256 rows (decode), else 256; bn = 256, or n below that;
    bkp = the whole of KP rounded up to 8 sublanes when
    KP is small (K 1536: one 160-word block, not two of 128), else the
    largest multiple of 128 up to KP that divides it (K 8960: 896)."""
    kp = -(-k // FIELDS)
    bm = m if m <= _BM else _BM
    bn = min(n, _BN)
    if kp <= _BKP_ONE:
        return bm, bn, -(-kp // 8) * 8
    divs = [b for b in range(_BKP, kp + 1, _BKP) if kp % b == 0]
    return bm, bn, divs[-1] if divs else _BKP


def _split_fields(x_ref, xt_ref, xf_ref):
    """x block (bm, W), K = 10j+i along lanes -> xf (nkb, 10, bm, bkp) with
    xf[j // bkp, i, :, j % bkp] = x[:, 10j+i]. A stride-10 gather along
    lanes has no vector form, so each K block goes through its transpose:
    K on sublanes, where a strided read takes every tenth row."""
    nkb, _, bm, bkp = xf_ref.shape
    width = xt_ref.shape[0]
    for r in range(0, bm, _LANES):
        rows = min(_LANES, bm - r)
        for kb in range(nkb):
            start = kb * FIELDS * bkp
            xt_ref[:, :rows] = x_ref[r:r + rows, start:start + width].astype(
                jnp.float32).T

            def field(i, carry):
                p = xt_ref[pl.ds(i, bkp, stride=FIELDS), :]
                xf_ref[kb, i, r:r + rows] = p.T[:rows].astype(xf_ref.dtype)
                return carry
            jax.lax.fori_loop(0, FIELDS, field, 0, unroll=True)


def _kernel(x_ref, w_ref, d_ref, b_ref, o_ref, acc_ref, xt_ref, xf_ref):
    j, kk = pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _fields():      # once per M block: the N axis runs in order
        _split_fields(x_ref, xt_ref, xf_ref)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]

    # fori_loop(unroll=True), here and in _split_fields: the straight code
    # of a Python loop, traced once (set-up traces every kernel shape)
    def field(i, acc):
        # field i sits in bits 3i..3i+2: lift its top bit to bit 31, then
        # an arithmetic shift sign-extends it to a level in [-4, 3]
        lv = (w << (32 - _BITS * (i + 1))) >> (32 - _BITS)
        return acc + jnp.dot(xf_ref[kk, i], lv.astype(xf_ref.dtype),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = jax.lax.fori_loop(0, FIELDS, field, acc_ref[...],
                                     unroll=True)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * d_ref[...].astype(jnp.float32)
                      + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def qmatvec_pallas(x: jnp.ndarray, w_packed: jnp.ndarray, delta: jnp.ndarray,
                   bias: jnp.ndarray | None = None, *, out_dtype=None,
                   interpret: bool = False) -> jnp.ndarray:
    """x (M, K), w_packed (KP, N) int32, delta (N,), bias (N,)|None -> (M, N).

    K must satisfy KP = ceil(K/10); x is zero-padded to whole blocks."""
    m, k = x.shape
    kp, n = w_packed.shape
    assert kp * FIELDS >= k, (x.shape, w_packed.shape)
    out_dtype = out_dtype or x.dtype
    # (1, N) rows: Mosaic tiles a 1-D operand differently from XLA
    delta = jnp.broadcast_to(jnp.asarray(delta, jnp.float32).reshape(-1),
                             (n,)).reshape(1, n)
    bias = (jnp.zeros((1, n), jnp.float32) if bias is None
            else jnp.broadcast_to(jnp.asarray(bias, jnp.float32).reshape(-1),
                                  (n,)).reshape(1, n))

    bm, bn, bkp = qmatvec_blocks(m, k, n)
    mpad = -(-m // bm) * bm
    nkb = -(-kp // bkp)
    xw = _lanes(FIELDS * nkb * bkp)
    x = jnp.pad(x, ((0, mpad - m), (0, xw - k)))

    grid = (mpad // bm, pl.cdiv(n, bn), nkb)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, xw), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bkp, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mpad, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((_lanes(FIELDS * bkp), _LANES),
                                   jnp.float32),
                        pltpu.VMEM((nkb, FIELDS, bm, bkp), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, w_packed, delta, bias)
    return out[:m]
