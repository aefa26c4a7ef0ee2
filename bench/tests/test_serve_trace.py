"""``devtrace``'s reduction of the engine's own spans and scopes, on the
hand-worked trace of test_devtrace.py with ``serve.*`` spans added (times in
ns); the three readers of what the engine records; and one window of
``serve_trace.py`` on the CPU."""
import json

import pytest

from bench import devtrace, serve_trace
from bench.tests.conftest import DATA
from bench.tests.test_devtrace import HOST, MODULES, RAW_OPS

NS = 1e-9
# idle gaps of the window 1000-11000: 3500-5000, 7200-8000, 9500-10500
SPANS = [["serve.sync.wait", 3400, 4400], ["serve.sync.host", 4400, 4900],
         ["serve.admit", 5000, 7300], ["serve.tick", 7400, 7900],
         ["serve.tick", 8000, 8100]]


@pytest.fixture
def plain():
    return {"window": [1000, 11000], "host": HOST, "modules": MODULES,
            "ops": devtrace.attribute(sorted(MODULES), RAW_OPS),
            "spans": SPANS}


def test_idle_by_program_span(plain):
    r = devtrace.reduce(plain)
    # 3500-5000: wait covers 900, host 500, none 100; 7200-8000: admit
    # 100, tick 500, none 200; 9500-10500: no serve.* span
    assert r["idle_by_program_span"] == pytest.approx(
        {"serve.sync.wait": 900 * NS, "serve.sync.host": 500 * NS,
         "serve.admit": 100 * NS, "serve.tick": 500 * NS,
         "none": 1300 * NS})
    assert [k for k, _ in r["breakdown"]["idle_gaps_program"][:2]] == \
        ["none", "serve.sync.wait"]


def test_program_idle_adds_up_to_devtrace_idle(plain):
    r = devtrace.reduce(plain)
    mine, theirs = r["idle_by_program_span"], r["idle_by_span"]
    assert sum(mine.values()) == pytest.approx(sum(theirs.values()))


def test_scope_seconds(plain):
    r = devtrace.reduce(plain)
    assert r["scope_s"] == pytest.approx(
        {"tick:model.mlp": 1500 * NS, "tick:tick.sample": 1500 * NS,
         "prefill:model.mlp": 1500 * NS, "prefill:model.attention": 500 * NS,
         "admit:unscoped": 200 * NS, "tick:model.attn_qkv": 1000 * NS,
         "tick:model.attention": 500 * NS})
    assert dict(r["breakdown"]["tick_scopes"]) == pytest.approx(
        {"model.mlp:qmatvec": 1500 * NS, "tick.sample:fusion": 1500 * NS,
         "model.attn_qkv:qmatvec": 1000 * NS,
         "model.attention:attn_decode": 500 * NS})


def test_nothing_to_reduce(plain):
    assert devtrace.reduce({"window": None, "host": [], "modules": [],
                            "ops": [], "spans": SPANS}) is None
    # no serve.* span in the trace: no idle by engine span
    r = devtrace.reduce(dict(plain, spans=[]))
    assert "idle_by_program_span" not in r
    assert "idle_gaps_program" not in r["breakdown"]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_tick)/while/body/closed_call/model.mlp/dot_general:",
     "model.mlp"),
    ("jit(_tick)/model.readout/jit(qmatmul)/jit(qmatmul_pallas)/jit(_pad)/"
     "pad:", "model.readout"),
    ("jit(_tick)/tick.sample/argmax", "tick.sample"),
    # a fusion of ops from two scopes takes the first listed
    ("jit(_tick)/while/body/closed_call/model.kv_write/squeeze;"
     "model.attn_qkv/reshape:", "model.kv_write"),
    ("jit(_tick)/while/body/squeeze;jit(_tick)/model.embed/mul:",
     "model.embed"),
    ("jit(_tick)/while/body/dynamic_update_slice:", "unscoped"),
    ("params['embed']['q']:", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert devtrace.scope_of(op_name) == scope


def test_op_names_from_the_json_beside_the_xplane(tmp_path):
    import gzip
    events = [
        {"ph": "X", "name": "fusion.16", "args": {
            "device_offset_ps": "49215862500",
            "tf_op": "jit(_tick)/model.embed/broadcast_in_dim:"}},
        {"ph": "X", "name": "copy.45", "args": {
            "device_offset_ps": "49215900000"}},
        {"ph": "M", "name": "thread_name", "args": {"name": "XLA Ops"}},
    ]
    with gzip.open(tmp_path / "h.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    names = devtrace.op_names(tmp_path / "h.xplane.pb")
    assert names == {("fusion.16", "49215862500"):
                     "jit(_tick)/model.embed/broadcast_in_dim:"}
    assert devtrace.op_names(tmp_path / "none.xplane.pb") == {}


def _read(name, rec):
    from bench import run
    return run.read_metric(name, rec)


def test_counter_readers():
    rec = {"counters": {"prefill_tokens": 70, "prefill_positions": 1024,
                        "live_slot_ticks": 86, "decode_calls": 10}}
    assert _read("prefill_row_use", rec) == pytest.approx(6.8359375)
    assert _read("live_slots_per_tick", rec) == pytest.approx(8.6)
    idle = {"prefill_tokens": 0, "prefill_positions": 0,
            "live_slot_ticks": 0, "decode_calls": 0}
    for name in ("prefill_row_use", "live_slots_per_tick"):
        assert _read(name, {"counters": idle}) is None
        assert _read(name, {}) is None        # a harness that reads none


def test_sync_idle_reader(plain):
    tr = devtrace.reduce(plain)
    # 1400 ns of idle under the syncs in a 10000 ns window
    assert _read("sync_idle_share", {"trace": tr}) == pytest.approx(14.0)
    no_spans = devtrace.reduce(dict(plain, spans=[]))
    assert _read("sync_idle_share", {"trace": no_spans}) is None
    assert _read("sync_idle_share", {"trace": None}) is None


def test_load_finds_spans_in_a_cpu_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.traced"):
            with TraceAnnotation("serve.admit", bucket=64, rows=1):
                jax.numpy.ones(4).block_until_ready()
            with TraceAnnotation("serve.tick"):
                pass
            with TraceAnnotation("engine.step"):
                pass
    tr = devtrace.load(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    assert sorted(n for n, _, _ in tr["spans"]) == ["serve.admit",
                                                     "serve.tick"]
    assert [n for n, _, _ in tr["host"]] == ["engine.step"]
    assert tr["window"] is not None
    assert all(len(o) == 5 for o in tr["ops"])


def test_load_keeps_the_admissions_bucket_and_rows(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.traced"):
            for bucket, rows in ((256, 1), (4096, 3)):
                with TraceAnnotation("serve.admit", bucket=bucket,
                                     rows=rows):
                    jax.numpy.ones(4).block_until_ready()
    tr = devtrace.load(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    assert [a[2:] for a in tr["admits"]] == [[256, 1], [4096, 3]]
    assert all(a[0] <= a[1] for a in tr["admits"])


def _probe(capsys, argv, require_tpu=False):
    rc = serve_trace.main(argv, bench_file=DATA / "BENCHMARK.json",
                          require_tpu=require_tpu)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


def test_probe_window_on_cpu(capsys):
    rc, res = _probe(capsys, ["--workload", "tiny.decode_open", "--seed",
                              "2147483749", "--seconds", "2",
                              "--traced-seconds", "1"])
    assert rc == 0 and res["correct"]
    c, m = res["counters"], res["metrics"]
    assert c["decode_calls"] > 0 and c["admitted"] > 0
    assert m["prefill_row_use"]["value"] == pytest.approx(
        100.0 * c["prefill_tokens"] / c["prefill_positions"])
    assert m["live_slots_per_tick"]["value"] == pytest.approx(
        c["live_slot_ticks"] / c["decode_calls"])
    assert 0 < m["live_slots_per_tick"]["value"] <= 4      # tiny: 4 slots
    assert {"out_tok_s", "itl_p95_ms", "tokens_per_tick"} <= set(m)
    assert res["span_us"]["off"] > 0 and res["span_us"]["on"] > 0


def test_probe_needs_a_tpu(capsys):
    rc, res = _probe(capsys, ["--workload", "tiny.decode_open", "--seed",
                              "1", "--seconds", "1"], require_tpu=True)
    assert rc == 1 and res is None
