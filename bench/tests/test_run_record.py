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


def test_warm_up_admits_the_two_longest_buckets_back_to_back():
    """Every bucket once, then the two longest in one admission round, so
    that set-up holds two admissions' prefill outputs at once as the
    window does when two requests come together."""
    from bench import traffic

    class Engine:
        drain_every = 4

        def __init__(self):
            self.queue, self.rounds = [], []

        def submit(self, prompt, max_new):
            self.queue.append(len(prompt))

        def run_all(self):
            self.rounds.append(self.queue)
            self.queue = []

    eng = Engine()
    mix = traffic.load(run.BENCH / "traffic" / "decode_open.json")
    run.warm_up(eng, mix, 1280)
    assert eng.rounds == [[16, 32, 64, 128, 256], [256, 128]]
