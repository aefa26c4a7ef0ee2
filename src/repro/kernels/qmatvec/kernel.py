"""Pallas TPU kernel: decode/prefill matmul streaming 3.2-bit packed weights.

THE paper's regime on TPU (DESIGN §3): decode GEMMs have arithmetic intensity
~1 FLOP/byte, entirely HBM-bandwidth-bound. This kernel streams the weight
matrix in the *container* format — 10 3-bit fields per int32 word, exactly the
paper's BRAM image — so HBM traffic is 3.2 bits/weight instead of 16 (bf16):
a 5x cut of the dominant roofline term. The unpack (shift/mask/sign-extend on
the VPU) is free: the kernel is still bandwidth-bound after a 5x traffic cut.

Layout: words (KP, N) int32 where word j of column n holds weights
k = 10j..10j+9 (packed along K, see core.packing.pack_matrix). The kernel
unpacks a (bkp, bn) word tile to a (10*bkp, bn) level tile in VMEM, converts
to the activation dtype, and MXU-accumulates against the (bm, 10*bkp)
activation slice. fp32 accumulator in VMEM scratch across the KP grid; the
epilogue applies the per-channel delta and the (optional, fused) bias.

The grid covers M too: the same kernel serves batched decode (M = active
slots) and bucketed prefill (M = slots x bucket_len) — weight words stream
once per M-tile regardless of how many rows ride in it, which is the paper's
batch-amortization argument verbatim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["qmatvec_pallas", "FIELDS"]

FIELDS = 10  # 3-bit fields per int32 container word
_BITS = 3
_MASK = (1 << _BITS) - 1
_SIGN = 1 << (_BITS - 1)


def _unpack_tile(words: jnp.ndarray) -> jnp.ndarray:
    """(bkp, bn) int32 -> (bkp*10, bn) int32 signed levels."""
    bkp, bn = words.shape
    fields = []
    for i in range(FIELDS):
        f = (words >> (i * _BITS)) & _MASK
        fields.append(f - ((f & _SIGN) << 1))      # sign-extend 3-bit
    lv = jnp.stack(fields, axis=1)                 # (bkp, 10, bn)
    return lv.reshape(bkp * FIELDS, bn)


def _kernel(x_ref, w_ref, d_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    lv = _unpack_tile(w_ref[...]).astype(x.dtype)
    acc_ref[...] += jnp.dot(x, lv, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * d_ref[...].astype(jnp.float32)
                      + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkp", "interpret",
                                             "out_dtype"))
def qmatvec_pallas(x: jnp.ndarray, w_packed: jnp.ndarray, delta: jnp.ndarray,
                   bias: jnp.ndarray | None = None, *, bm: int = 256,
                   bn: int = 256, bkp: int = 128, out_dtype=None,
                   interpret: bool = False) -> jnp.ndarray:
    """x (M, K), w_packed (KP, N) int32, delta (N,), bias (N,)|None -> (M, N).

    K must satisfy KP = ceil(K/10); x is zero-padded to 10*KP internally.
    """
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

    bm = min(bm, m)
    bn = min(bn, n)
    bkp = min(bkp, kp)
    mpad = -(-m // bm) * bm
    npad = -(-n // bn) * bn
    kppad = -(-kp // bkp) * bkp
    if npad != n:
        w_packed = jnp.pad(w_packed, ((0, 0), (0, npad - n)))
        delta = jnp.pad(delta, ((0, 0), (0, npad - n)))
        bias = jnp.pad(bias, ((0, 0), (0, npad - n)))
    if kppad != kp:
        w_packed = jnp.pad(w_packed, ((0, kppad - kp), (0, 0)))
    xk = kppad * FIELDS
    x = jnp.pad(x, ((0, mpad - m), (0, xk - k)))

    grid = (mpad // bm, npad // bn, kppad // bkp)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkp * FIELDS), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkp, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mpad, npad), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed, delta, bias)
    return out[:m, :n]
