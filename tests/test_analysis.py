"""HLO collective parser + roofline reconstruction math.

The parser lives in ``repro.analysis.hlo`` (the static-analysis
subsystem's compiled-artifact backend)."""
import numpy as np

from benchmarks import roofline as rl
from repro.analysis.hlo import collective_bytes, _shape_bytes

HLO = """
HloModule test

%fused (a: f32[128,256]) -> f32[128,256] {
  %a = f32[128,256] parameter(0)
}

ENTRY %main (p0: f32[128,256], p1: bf16[64]) -> f32[128,256] {
  %p0 = f32[128,256]{1,0} parameter(0)
  %p1 = bf16[64]{0} parameter(1)
  %ar = f32[128,256]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}
  %ag = bf16[256]{0} all-gather(%p1), dimensions={0}
  %rs = f32[32,256]{1,0} reduce-scatter(%ar), dimensions={0}
  %cp = f32[128,256]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
  ROOT %out = f32[128,256]{1,0} add(%ar, %cp)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[64]") == 128
    assert _shape_bytes("s8[10,10]") == 100
    assert _shape_bytes("pred[8]") == 8


def test_shape_bytes_packed_dtypes():
    """The packed serve forms put sub-byte and 8-bit codes on the wire:
    s4/u4 are bit-packed two per byte, every f8 variant is one byte."""
    assert _shape_bytes("s4[128,256]") == 128 * 256 // 2
    assert _shape_bytes("u4[16]") == 8
    assert _shape_bytes("u8[100]") == 100
    assert _shape_bytes("f8e4m3fn[32,32]") == 32 * 32
    assert _shape_bytes("f8e5m2[64]") == 64


def test_collective_parser_counts_operands():
    out = collective_bytes(HLO)
    assert out["count"] == 4
    assert out["all-reduce"] == 128 * 256 * 4          # operand p0
    assert out["all-gather"] == 64 * 2                 # operand p1 (bf16[64])
    assert out["reduce-scatter"] == 128 * 256 * 4      # operand = ar's shape
    assert out["collective-permute"] == 128 * 256 * 4
    assert out["total"] == sum(out[k] for k in
                               ("all-reduce", "all-gather", "reduce-scatter",
                                "collective-permute"))


def test_depth_combine_linear():
    rec = {"num_layers": 10, "attn_every": 0,
           "L0": {"cost": {"flops": 5.0, "bytes": 7.0},
                  "collectives": {"total": 1.0}},
           "L1": {"cost": {"flops": 8.0, "bytes": 10.0},
                  "collectives": {"total": 1.5}}}
    out = rl._depth_combine(rec)
    assert out["flops"] == 5.0 + 10 * 3.0
    assert out["bytes"] == 7.0 + 10 * 3.0
    assert out["coll"] == 1.0 + 10 * 0.5


def test_hybrid_combine_solves_attention_and_mamba():
    # synthetic: base 2, mamba layer m=3, attn block a=5, A=4, L=10 (G=2,T=2)
    base, m, a, A, L = 2.0, 3.0, 5.0, 4, 10
    rec = {"num_layers": L, "attn_every": A,
           "L0": {"cost": {"flops": base, "bytes": 0}, "collectives": {}},
           "G1": {"cost": {"flops": base + A * m + a, "bytes": 0},
                  "collectives": {}},
           "A1": {"cost": {"flops": base + m + a, "bytes": 0},
                  "collectives": {}}}
    out = rl._depth_combine(rec)
    g, tail = L // A, L % A
    expect = base + g * (A * m + a) + tail * m
    np.testing.assert_allclose(out["flops"], expect)


def test_quad_extrapolation_exact_for_quadratics():
    f = lambda s: 3.0 + 0.5 * s + 0.002 * s * s
    xs = [2048, 4096, 8192]
    got = rl._quad_extrapolate(xs, [f(x) for x in xs], 32768)
    np.testing.assert_allclose(got, f(32768), rtol=1e-12)


def test_model_flops_decode_vs_train():
    rec = {"arch": "qwen2-1.5b", "kind": "decode", "global_batch": 128,
           "seq_len": 32768, "params": 1.5e9, "active_params": 1.5e9}
    d = rl.model_flops_per_step(rec)
    rec2 = dict(rec, kind="train", global_batch=256, seq_len=4096)
    t = rl.model_flops_per_step(rec2)
    assert t / d > 1e4            # train moves vastly more flops per step
