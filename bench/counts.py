"""Bytes and operations of the served model, computed from its shapes.

The formulas of the paper's weight stream (0.4 B per 3-bit weight in the
``qp`` container, ten fields per int32 word) and of the KV cache, kept with
the benchmark so that every PR counts them the same way.
"""
from __future__ import annotations

from bench.weights import FIELDS, Shapes

ACT_BYTES = 2                 # bfloat16 activations and cache entries


def qp_words_bytes(s: Shapes) -> int:
    """HBM bytes of every layer's packed 3-bit matrices."""
    return s.layers * sum(-(-k // FIELDS) * n * 4
                          for _, k, n, _ in s.matrices())


def table_bytes(s: Shapes) -> int:
    """int8 embedding table, and the untied head if there is one."""
    return s.vocab * s.d * (1 if s.tied else 2)


def kv_bytes_per_token(s: Shapes) -> int:
    """Keys and values of one token over all layers, bfloat16."""
    return 2 * s.layers * s.kv_heads * s.head_dim * ACT_BYTES


def matmul_weights(s: Shapes) -> int:
    """Weights one token multiplies: every layer's matrices and the
    readout."""
    return s.layers * sum(k * n for _, k, n, _ in s.matrices()) \
        + s.vocab * s.d


def decode_flops(s: Shapes, tokens: int, context: int) -> int:
    """Model operations of ``tokens`` decoded tokens that attend over
    ``context`` positions in all: 2 per weight per token, and QK^T plus PV
    over each position."""
    return 2 * matmul_weights(s) * tokens \
        + 4 * s.layers * s.heads * s.head_dim * context


def qmatvec_call(m: int, k: int, n: int) -> tuple:
    """(operations, bytes) of one packed matmul (m, k) x (k, n): the
    ``qp`` words plus the bfloat16 activations in and out."""
    return 2 * m * k * n, -(-k // FIELDS) * n * 4 + (m * k + m * n) * ACT_BYTES


def qmatvec_roofline_s(s: Shapes, m: int, peaks: dict) -> float:
    """Least time the chip needs for one layer's seven packed matmuls at m
    rows: each the larger of its operations over the bf16 peak and its bytes
    over the HBM bandwidth."""
    total = 0.0
    for _, k, n, _ in s.matrices():
        ops, nbytes = qmatvec_call(m, k, n)
        total += max(ops / peaks["bf16_flops"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total


def prompt_flops(s: Shapes, n: int) -> int:
    """Model operations of prefilling one prompt of ``n`` tokens: 2 per
    layer weight per token, causal QK^T and PV over each token and those
    before it, and the readout at its last token."""
    layer_weights = s.layers * sum(k * n_ for _, k, n_, _ in s.matrices())
    return 2 * layer_weights * n \
        + 2 * s.layers * s.heads * s.head_dim * n * (n + 1) \
        + 2 * s.vocab * s.d
