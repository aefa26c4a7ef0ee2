"""Post-SPMD HLO analysis: collective-bytes extraction + cost decomposition.

The compiled-artifact backend of ``repro.analysis``. The jaxpr
passes in ``repro.analysis.passes`` see graphs BEFORE compilation; this
module reads what XLA actually produced.

``collective_bytes``: per the roofline spec, sums *operand* sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute in
the optimized (partitioned) HLO — shapes there are per-partition, so totals
are per-chip wire-byte proxies.

XLA's HloCostAnalysis visits a while-loop body ONCE regardless of trip count
(verified empirically — see EXPERIMENTS.md §Dry-run methodology), so totals
for scanned-layer models are reconstructed by the L0/L1 lowering
decomposition in launch.dryrun, not by trip-count guessing here. The flat
per-text counts this module returns are exactly "body counted once".
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

__all__ = ["collective_bytes", "DTYPE_BYTES", "cost_summary",
           "memory_summary", "_shape_bytes"]

# bytes per element. The packed serve forms put sub-byte and 8-bit codes on
# the wire: s4/u4 are bit-packed two-per-byte by XLA (0.5), and the f8
# variants are all one byte regardless of exponent/mantissa split.
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 0.5, "u4": 0.5,
    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e3m4": 1,
}

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\])")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\([^)]*\)|[a-z0-9]+\[[\d,]*\][^=]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(([^)]*)\)")

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return int(total)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """{kind: operand bytes (flat, body-once)} + 'total' + 'count'."""
    # pass 1: result shapes of every definition
    shapes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)
    out: Dict[str, float] = defaultdict(float)
    count = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        _, kind, operands = m.group(1), m.group(2), m.group(3)
        if "-done" in line.split("=")[1][:40]:
            continue
        b = 0
        for op in operands.split(","):
            op = op.strip().lstrip("%")
            # operands may carry inline shapes (newer HLO) or be refs
            ms = _SHAPE_RE.match(op)
            if ms:
                b += _shape_bytes(op.split(" ")[0])
            elif op in shapes:
                b += _shape_bytes(shapes[op])
        out[kind] += b
        count += 1
    out["total"] = sum(out[k] for k in KINDS if k in out)
    out["count"] = count
    return dict(out)


def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def memory_summary(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {
        "argument_bytes": float(ma.argument_size_in_bytes),
        "output_bytes": float(ma.output_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "alias_bytes": float(ma.alias_size_in_bytes),
        # peak live estimate: args + temps + outputs - aliased(donated)
        "peak_bytes_est": float(ma.argument_size_in_bytes
                                + ma.temp_size_in_bytes
                                + ma.output_size_in_bytes
                                - ma.alias_size_in_bytes),
    }
