"""The serving engine's instrumentation: its counters count exactly, ride
snapshots, and feed the watchdog's diagnostics; its ``serve.*`` host spans
land in a profiler trace; its tick and prefill carry the ``model.*`` and
``tick.sample`` name scopes."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import W3A8
from repro.models import get_model
from repro.serving.engine import COUNTERS, ServingEngine

W3 = dataclasses.replace(W3A8, act_bits=None)
SLOTS = 3
# every prompt fits the smallest bucket (8), so each prefill call computes
# SLOTS x 8 positions
PROMPTS = [[1, 2, 3], [7, 8, 9, 10, 11], [20, 21, 22, 23], [30, 31],
           [40, 41, 42, 43, 44, 45]]
MAX_NEW = [6, 4, 7, 3, 5]
SPANS = ("serve.admit", "serve.tick", "serve.sync.wait", "serve.sync.host")
TICK_SCOPES = ("model.embed", "model.attn_qkv", "model.kv_write",
               "model.attention", "model.attn_out", "model.mlp",
               "model.final_norm", "model.readout", "tick.sample")
PREFILL_SCOPES = ("model.embed", "model.attn_qkv", "model.kv_write",
                  "model.attention", "model.attn_out", "model.mlp",
                  "model.final_norm", "model.readout")


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=32, vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, quant_dense.export_container(params, W3)


def _engine(model, **kw):
    cfg, params = model
    return ServingEngine(params, cfg, policy=W3, slots=SLOTS, max_len=64,
                         dtype=jnp.float32, **kw)


def _submit_all(eng):
    for p, m in zip(PROMPTS, MAX_NEW):
        eng.submit(list(p), max_new=m)


def test_counters_count_exactly(model):
    eng = _engine(model)
    _submit_all(eng)
    done = eng.run_all()
    c = eng.counters()
    assert set(c) == set(COUNTERS)
    assert c["admitted"] == len(PROMPTS)
    assert c["prefill_tokens"] == sum(len(p) for p in PROMPTS)
    assert c["prefill_calls"] >= 2                 # 5 requests, 3 slots
    assert c["prefill_positions"] == c["prefill_calls"] * SLOTS * 8
    # without EOS a request holds its slot for max_new - 1 ticks: prefill
    # makes its first token, each tick one more
    assert c["live_slot_ticks"] == sum(m - 1 for m in MAX_NEW)
    assert sum(len(r.out) for r in done) == \
        c["live_slot_ticks"] + c["admitted"]


def test_live_slot_ticks_is_occupancy_per_tick(model):
    eng = _engine(model)
    _submit_all(eng)
    held = 0
    while eng.queue or any(r is not None for r in eng._slot_req):
        ticks = eng.decode_calls
        eng._spin_up()                 # step() admits first, then ticks
        occupied = sum(r is not None for r in eng._slot_req)
        eng.step()
        if eng.decode_calls != ticks:
            held += occupied
        eng.drain()
    assert eng.live_slot_ticks == held > 0


def test_diagnostics_report_every_counter(model):
    eng = _engine(model)
    _submit_all(eng)
    eng.run_all()
    d = eng._diagnostics()
    assert {k: d[k] for k in COUNTERS} == eng.counters()


def test_snapshot_keeps_counters(model, tmp_path):
    eng = _engine(model)
    _submit_all(eng)
    for _ in range(4):
        eng.step()
    taken = eng.counters()         # snapshot() counts itself after saving
    eng.snapshot(str(tmp_path / "s"))
    fresh = _engine(model)
    fresh.restore(str(tmp_path / "s"))
    assert fresh.counters() == taken
    assert fresh.live_slot_ticks > 0 and fresh.prefill_positions > 0


def test_snapshot_without_new_counters_restores_zero(model, tmp_path):
    """A snapshot taken before a counter existed restores it as 0."""
    eng = _engine(model)
    _submit_all(eng)
    for _ in range(3):
        eng.step()
    full = eng.counters()
    new = ("admitted", "prefill_tokens", "prefill_positions",
           "live_slot_ticks")
    eng.counters = lambda: {k: v for k, v in full.items() if k not in new}
    eng.snapshot(str(tmp_path / "s"))
    fresh = _engine(model)
    fresh.restore(str(tmp_path / "s"))
    got = fresh.counters()
    assert all(got[k] == 0 for k in new)
    assert all(got[k] == full[k] for k in COUNTERS if k not in new)


def _events(tmp_path, eng):
    """Run ``eng`` to completion under the profiler; the host events of
    the trace as (name, start, end, stats), stats read for spans only."""
    from jax.profiler import ProfileData, TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        while eng.queue or any(r is not None for r in eng._slot_req):
            eng.step()
            with TraceAnnotation("test.drain"):
                eng.drain()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    return [(ev.name, ev.start_ns, ev.end_ns,
             dict(ev.stats) if ev.name.startswith("serve.") else {})
            for plane in pd.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events]


def test_spans_in_profiler_trace(model, tmp_path):
    eng = _engine(model)
    _submit_all(eng)
    evs = _events(tmp_path, eng)
    names = {n for n, *_ in evs}
    assert set(SPANS) <= names
    ticks = [e for e in evs if e[0] == "serve.tick"]
    assert len(ticks) == eng.decode_calls
    admits = [e for e in evs if e[0] == "serve.admit"]
    assert len(admits) == eng.prefill_calls
    assert all(st["bucket"] == 8 and 1 <= st["rows"] <= SLOTS
               for *_, st in admits)
    assert sum(st["rows"] for *_, st in admits) == len(PROMPTS)
    # each drain that had something pending: its wait, then its host half
    waits = [e for e in evs if e[0] == "serve.sync.wait"]
    hosts = [e for e in evs if e[0] == "serve.sync.host"]
    assert len(waits) == len(hosts) > 0
    pairs = zip(sorted(waits, key=lambda e: e[1]),
                sorted(hosts, key=lambda e: e[1]))
    for (_, w0, w1, _), (_, h0, h1, _) in pairs:
        assert w0 <= w1 <= h0 <= h1
        assert any(d0 <= w0 and h1 <= d1 for n, d0, d1, _ in evs
                   if n == "test.drain")


def _op_name_parts(fn, args):
    """Every component of the compiled ops' ``op_name`` metadata: what a
    profiler trace reports for each device op."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


@pytest.fixture(scope="module")
def tick_parts(model):
    point = _engine(model).contract_points()[0]
    assert point["name"] == "decode_tick"
    return _op_name_parts(point["fn"], point["args"])


@pytest.fixture(scope="module")
def prefill_parts(model):
    point = _engine(model).contract_points(bucket=8)[1]
    assert point["name"] == "prefill_bucketed"
    return _op_name_parts(point["fn"], point["args"])


@pytest.mark.parametrize("scope", TICK_SCOPES)
def test_tick_carries_scope(tick_parts, scope):
    assert scope in tick_parts


@pytest.mark.parametrize("scope", PREFILL_SCOPES)
def test_prefill_carries_scope(prefill_parts, scope):
    assert scope in prefill_parts
