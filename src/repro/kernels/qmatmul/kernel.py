"""Pallas TPU kernel: X @ dequant(W3) with on-chip (VMEM) dequantization.

The paper's insight mapped to the MXU (DESIGN §2): the weight matrix is
streamed HBM→VMEM as int8 *levels* (the paper's {-3..3} codes — half the
bytes of bf16), converted to bf16 inside VMEM (VPU convert, hidden behind the
MXU pipeline), matmul'd on the MXU with fp32 accumulation across the K grid,
and rescaled by the per-channel step size delta in the epilogue — exactly the
paper's PU accumulate-then-Delta-rescale dataflow (Fig. 4), retargeted.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics — sequential),
fp32 accumulator lives in a VMEM scratch tile, initialized at k==0 and
flushed (delta-scaled) at the last k step.

Block defaults (bm=256, bk=512, bn=512) keep the working set
256KB(x) + 256KB(w) + 512KB(acc) + 512KB(out) << 16MB v5e VMEM, and every
MXU dim is a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["qmatmul_pallas"]


def _kernel(x_ref, w_ref, d_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)          # int8 levels -> compute dtype, in VMEM
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * d_ref[...].astype(jnp.float32)
                      + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def qmatmul_pallas(x: jnp.ndarray, w_q: jnp.ndarray, delta: jnp.ndarray,
                   bias: jnp.ndarray | None = None, *,
                   bm: int = 256, bn: int = 512, bk: int = 512,
                   out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """x (M, K) x w_q (K, N) int8 levels x delta (N,) [+ bias (N,)] -> (M, N)."""
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2, (x.shape, w_q.shape)
    # (1, N) rows: Mosaic tiles a 1-D operand differently from XLA
    delta = jnp.broadcast_to(jnp.asarray(delta, jnp.float32).reshape(-1),
                             (n,)).reshape(1, n)
    bias = (jnp.zeros((1, n), jnp.float32) if bias is None
            else jnp.broadcast_to(jnp.asarray(bias, jnp.float32).reshape(-1),
                                  (n,)).reshape(1, n))
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    # pad to block multiples (zeros contribute nothing to the accumulation)
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    if np_ != n:
        delta = jnp.pad(delta, ((0, 0), (0, np_ - n)))
        bias = jnp.pad(bias, ((0, 0), (0, np_ - n)))

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_q, delta, bias)
    return out[:m, :n]
