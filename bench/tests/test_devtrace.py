"""The trace reduction on a small synthetic trace whose answers are worked
out by hand (times in ns)."""
import pytest

from bench import devtrace

MODULES = [["jit__tick(1)", 1000, 4000], ["jit__prefill(2)", 5000, 7000],
           ["jit__admit_many(3)", 7000, 7500], ["jit__tick(1)", 8000, 10000],
           ["jit__tick(1)", 10500, 12000]]
# (HLO event name, t0, t1, scope): the scope as load reads it from the op
# name in the .trace.json.gz
RAW_OPS = [
    ("%while.4 = (s32[]) while((s32[]) %t), body=%b", 1000, 4000,
     "unscoped"),
    ("%qmatvec_pallas.60 = bf16[16,8960] custom-call(bf16[16,2560] %p)",
     1000, 2000, "model.mlp"),
    ("%fusion.16 = f32[16] fusion(f32[16] %x)", 2000, 3500, "tick.sample"),
    ("%qmatvec_pallas.61 = bf16[4096,8960] custom-call(%p)", 5000, 6500,
     "model.mlp"),
    ("%attn_prefill_pallas.3 = bf16[16] custom-call(%p)", 6500, 7000,
     "model.attention"),
    ("%copy.19 = s32[16] copy(%a)", 7000, 7200, "unscoped"),
    ("%qmatvec_pallas.60 = bf16[16,8960] custom-call(%p)", 8000, 9000,
     "model.attn_qkv"),
    ("%attn_decode_pallas.8 = bf16[16] custom-call(%p)", 9000, 9500,
     "model.attention"),
    ("%qmatvec_pallas.60 = bf16[16,8960] custom-call(%p)", 10500, 11500,
     "model.mlp"),
    ("%reduce.3 = f32[] reduce(%p)", 12500, 13000, "unscoped"),
]
HOST = [["engine.step", 900, 1200], ["engine.drain", 3600, 4900],
        ["engine.step", 7400, 8100], ["bench.wait_arrival", 9400, 10400]]


@pytest.fixture
def plain():
    # modules out of time order, as a trace may list them
    return {"window": [1000, 11000], "host": HOST, "modules": MODULES,
            "ops": devtrace.attribute(sorted(MODULES), RAW_OPS)}


def test_op_names_and_programs(plain):
    assert devtrace.op_base(RAW_OPS[1][0]) == "qmatvec_pallas"
    assert devtrace.op_base("%fusion = f32[] fusion()") == "fusion"
    ops = plain["ops"]
    assert [o[0] for o in ops][:3] == ["qmatvec_pallas", "fusion",
                                       "qmatvec_pallas"]
    assert not any(o[0] == "while" for o in ops)
    assert [o[3] for o in ops] == ["tick", "tick", "prefill", "prefill",
                                   "admit", "tick", "tick", "tick", "other"]
    assert [o[4] for o in ops][:2] == ["model.mlp", "tick.sample"]


def test_reduce_busy_idle_programs_kernels(plain):
    r = devtrace.reduce(plain)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(10000 * ns)
    # busy: 1000-3500, 5000-7200, 8000-9500, 10500-11000 (clipped)
    assert r["busy_s"] == pytest.approx((2500 + 2200 + 1500 + 500) * ns)
    # idle gaps: 3500-5000 (drain 3600-4900 covers 1300), 7200-8000 (step
    # 7400-8100 covers 600), 9500-10500 (wait_arrival covers 900); no span
    # covers the other 500
    assert r["idle_by_span"] == pytest.approx(
        {"engine.drain": 1300 * ns, "engine.step": 600 * ns,
         "bench.wait_arrival": 900 * ns, "other": 500 * ns})
    progs = r["programs"]
    assert progs["tick"]["n"] == 2            # the third is cut by the window
    assert progs["tick"]["s_whole"] == pytest.approx(5000 * ns)
    assert progs["tick"]["s"] == pytest.approx(5500 * ns)
    assert progs["prefill"]["s"] == pytest.approx(2000 * ns)
    assert progs["admit"]["n"] == 1
    k = r["kernels"]
    assert k["tick:qmatvec"]["n"] == 3
    assert k["tick:qmatvec"]["s"] == pytest.approx(2500 * ns)
    assert k["prefill:qmatvec"]["s"] == pytest.approx(1500 * ns)
    assert k["tick:attn_decode"]["n"] == 1
    top = dict(r["breakdown"]["device_ops"])
    assert top["tick:qmatvec"] == pytest.approx(2500 * ns)
    assert top["tick:fusion"] == pytest.approx(1500 * ns)
    assert r["breakdown"]["idle_gaps"][0][0] == "engine.drain"


@pytest.mark.parametrize("op, kernel", [
    ("qmatvec_pallas", "qmatvec"), ("qmatmul_pallas", "qmatmul"),
    ("attn_decode_pallas", "attn_decode"),
    ("attn_prefill_pallas", "attn_prefill"), ("newkern_pallas", "newkern"),
    ("fusion", None), ("_pallas", None), ("pallas", None)])
def test_kernel_of(op, kernel):
    assert devtrace.kernel_of(op) == kernel


def test_every_pallas_kernel_by_its_name(plain):
    """A kernel no table names is reduced by its custom call's name; the
    labels of the kernels before it are unchanged."""
    raw = RAW_OPS + [
        ("%newkern_pallas.7 = bf16[16,8] custom-call(%p)", 9500, 9700,
         "model.mlp"),
        ("%newkern_pallas.7 = bf16[16,8] custom-call(%p)", 3500, 3800,
         "model.mlp")]
    r = devtrace.reduce(dict(plain,
                             ops=devtrace.attribute(sorted(MODULES), raw)))
    k = r["kernels"]
    assert k["tick:newkern"]["n"] == 2
    assert k["tick:newkern"]["s"] == pytest.approx(500 * 1e-9)
    assert set(k) == {"tick:qmatvec", "prefill:qmatvec",
                      "prefill:attn_prefill", "tick:attn_decode",
                      "tick:newkern"}
    assert k["tick:qmatvec"]["n"] == 3
    assert dict(r["breakdown"]["device_ops"])["tick:newkern"] == \
        pytest.approx(500 * 1e-9)
    assert dict(r["breakdown"]["tick_scopes"])["model.mlp:newkern"] == \
        pytest.approx(500 * 1e-9)


def test_metric_readers_on_the_reduced_trace(plain):
    from bench import run
    from bench.weights import Shapes
    r = devtrace.reduce(plain)
    rec = {"trace": r, "window": {"decode_calls": 2, "requests": []},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "shapes": Shapes(1, 64, 96, 4, 2, 16, 512, True),
           "serve": {"slots": 16}}
    assert run.read_metric("decode_tick_ms", rec) == pytest.approx(2500e-6)
    assert run.read_metric("device_idle_share", rec) == pytest.approx(33.0)
    assert run.read_metric("prefill_share", rec) == pytest.approx(
        100 * 2500 / 6700)
    assert 0 < run.read_metric("qmatvec_roofline.decode", rec) <= 100


def test_no_window_or_no_device_reads_nothing():
    assert devtrace.reduce({"window": None, "host": [], "modules": [],
                            "ops": []}) is None
    assert devtrace.reduce({"window": [0, 10], "host": [], "modules": [],
                            "ops": []}) is None


def test_prefill_runs_pair_each_prefill_with_its_admission(plain):
    # one prefill program (5000-7000) and the span that dispatched it
    tr = dict(plain, admits=[[4800, 4900, 64, 3]])
    runs = devtrace.reduce(tr)["prefill_runs"]
    assert len(runs) == 1
    run = runs[0]
    assert (run["bucket"], run["rows"]) == (64, 3)
    assert run["s"] == pytest.approx(2000e-9)
    assert run["kernels"]["qmatvec"] == {"s": pytest.approx(1500e-9),
                                         "n": 1}
    assert run["kernels"]["attn_prefill"]["n"] == 1
    # a later admission whose prefill had not run when the trace stopped
    more = devtrace.reduce(dict(tr, admits=tr["admits"] + [[9000, 9100,
                                                            256, 1]]))
    assert more["prefill_runs"] == runs
    # a prefill cut by the window is left out
    cut = devtrace.reduce(dict(tr, window=[6000, 11000]))
    assert cut["prefill_runs"] == []
    # a prefill with no span to pair it with: nothing is paired
    assert devtrace.reduce(dict(tr, admits=[]))["prefill_runs"] is None
    assert devtrace.reduce(plain)["prefill_runs"] is None
