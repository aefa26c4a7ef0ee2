"""What a run records beside its result line: the engine's counters over
the window, in traced and untraced runs alike, and the per-layer metrics
that read them."""
import json

import pytest

from bench import run
from bench.tests.conftest import harness


def _side(seed, trace):
    return json.loads((run.OUT / f"tiny.decode_open.seed{seed}.trace{trace}"
                       ".json").read_text())


def test_counter_delta():
    assert run.counter_delta({"a": 3, "b": 1}, {"a": 5, "b": 1, "c": 2}) == \
        {"a": 2, "b": 0, "c": 2}


@pytest.mark.parametrize("trace", [0, 1])
def test_window_counters_in_the_record(capsys, trace):
    seed = 2147483000 + trace
    rc, res = harness(capsys, seed=seed, trace=trace)
    assert rc == 0 and res["correct"], res
    side = _side(seed, trace)
    c, w = side["counters"], side["window"]
    # the window's own counts and the engine's agree over the window
    assert c["decode_calls"] == w["decode_calls"] > 0
    assert c["prefill_calls"] == w["prefill_calls"] > 0
    assert c["admitted"] > 0 and c["prefill_positions"] > 0
    # warm-up's admissions and ticks are not in it
    assert c["admitted"] <= w["attempted"]
    m = res["metrics"]
    if trace:
        assert m["prefill_row_use"]["value"] == pytest.approx(
            100.0 * c["prefill_tokens"] / c["prefill_positions"])
        assert m["live_slots_per_tick"]["value"] == pytest.approx(
            c["live_slot_ticks"] / c["decode_calls"])
        assert 0 < m["live_slots_per_tick"]["value"] <= 4   # tiny: 4 slots
    else:
        assert "prefill_row_use" not in m and "out_tok_s" in m


@pytest.mark.parametrize("trace", [0, 1])
def test_admissions_are_stamped(capsys, trace):
    """Every request whose first token came was admitted before it, the
    window's admissions are as many as the engine counted, and a traced
    run records when its trace began."""
    seed = 2147483010 + trace
    rc, res = harness(capsys, seed=seed, trace=trace)
    assert rc == 0 and res["correct"], res
    side = _side(seed, trace)
    reqs = side["window"]["requests"]
    admitted = [r for r in reqs if r["admitted"] is not None]
    assert len(admitted) == side["counters"]["admitted"]
    assert all(r["admitted"] is not None and r["admitted"] <= r["first"]
               for r in reqs if r["first"] is not None)
    assert (side["traced_from_s"] is not None) == bool(trace)


class _Engine:
    """Records the prompt lengths of each admission round."""
    drain_every = 4

    def __init__(self):
        self.queue, self.rounds = [], []

    def submit(self, prompt, max_new):
        self.queue.append(len(prompt))

    def run_all(self):
        self.rounds.append(self.queue)
        self.queue = []


def test_warm_up_admits_the_two_longest_buckets_back_to_back():
    """Every bucket once, then the two longest in one admission round, so
    that set-up holds two admissions' prefill outputs at once as the
    window does when two requests come together."""
    from bench import traffic

    eng = _Engine()
    mix = traffic.load(run.BENCH / "traffic" / "decode_open.json")
    run.warm_up(eng, mix, 1280)
    assert eng.rounds == [[16, 32, 64, 128, 256], [256, 128]]


@pytest.mark.parametrize("workload, queued", [
    ("qwen2-1.5b.decode_open", 2), ("qwen2-1.5b.prompt_open", None)])
def test_warm_up_count_is_the_configurations(monkeypatch, workload, queued):
    """``warm_admissions`` of the configuration file, 2 where it has none;
    ``decode_open``'s file has none."""
    cell = run.load_cell(workload, run.ROOT / "BENCHMARK.json")
    conf, mix = cell["config"], cell["traffic"]
    assert ("warm_admissions" in conf) == (queued is None)
    queued = conf.get("warm_admissions", queued)
    seen, warm_up = [], run.warm_up
    monkeypatch.setattr(run, "warm_up",
                        lambda eng, mix, max_len, n=2: seen.append(n))
    monkeypatch.setattr(run, "family", lambda conf: type(
        "Family", (), {"FORMS": ("qp",), "program_config": lambda c: None,
                       "serve_params": lambda seed, c: {}}))
    monkeypatch.setattr("repro.serving.engine.ServingEngine",
                        lambda *a, **k: _Engine())
    run.build_engine(conf, mix, 1)
    assert seen == [queued]
    eng = _Engine()
    warm_up(eng, mix, conf["serve"]["max_len"], queued)
    assert eng.rounds[1] == sorted(eng.rounds[0], reverse=True)[:queued]
