"""Metric readers on hand-made run records, and the lookups by name that let
a later cell, configuration or metric come as files of its own."""
import importlib
import json

import pytest

from bench import run
from bench.tests.conftest import ROOT


def _req(due, first, gaps=(), tokens=0):
    return {"due": due, "first": first, "gaps": list(gaps), "tokens": tokens}


def test_ttft_counts_requests_still_waiting_at_the_close():
    # 19 requests served 0.1 s after they were due; one due at 5 s that had
    # no first token when the 10 s window closed waits 5 s
    reqs = [_req(0.5 * i, 0.5 * i + 0.1) for i in range(19)]
    rec = {"window": {"window_s": 10.0, "requests": reqs + [_req(5.0, None)]}}
    assert run.read_metric("ttft_p95_ms", rec) == pytest.approx(
        0.1e3 + 0.05 * (5.0 - 0.1) * 1e3)
    rec["window"]["requests"] = reqs
    assert run.read_metric("ttft_p95_ms", rec) == pytest.approx(100.0)


def test_itl_and_rate():
    reqs = [_req(0, 0.1, gaps=[0.0, 0.02, 0.02, 0.02], tokens=4),
            _req(0, 0.2, gaps=[0.0, 0.5], tokens=2)]
    rec = {"window": {"window_s": 2.0, "requests": reqs}}
    assert run.read_metric("out_tok_s", rec) == pytest.approx(3.0)
    assert 0.02e3 < run.read_metric("itl_p95_ms", rec) <= 0.5e3


def test_everything_the_benchmark_names_is_found_by_name():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert run.metric_file(m["name"]).is_file()
    for c in spec["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert hasattr(run.family(conf), "program_config")
        assert hasattr(run.reference(conf), "gaps")
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"], ROOT / "BENCHMARK.json")
        assert cell["traffic"]["loop"] in ("open", "closed")
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


def test_a_family_is_a_module_of_its_own():
    mod = importlib.import_module("bench.families.qwen2")
    assert set(mod.FORMS) == {"qp"}


def _prefill_rec(runs, slots=8, admitted=()):
    """A traced run's record: ``admitted`` holds (host seconds of the
    admission, prompt length) of the window's requests; the trace began at
    1.0 s."""
    from bench.weights import Shapes
    return {"trace": {"prefill_runs": runs},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "shapes": Shapes(2, 64, 96, 4, 2, 16, 512, True),
            "serve": {"slots": slots}, "traced_from_s": 1.0,
            "window": {"requests": [{"admitted": t, "prompt_len": n}
                                    for t, n in admitted]}}


def test_prefill_roofline_and_mfu_read_each_admissions_rows():
    """Two whole prefills, at buckets 256 and 1024 on 8 slots: the roofline
    of their packed matmuls at M = 8 x bucket over those calls' time, and
    the model operations of the three prompts admitted after the trace
    began (not their padding, nor the request admitted before it) over
    their programs' time."""
    from bench import counts
    rec = _prefill_rec([
        {"bucket": 256, "rows": 1, "s": 4e-3,
         "kernels": {"qmatvec": {"s": 2e-3, "n": 14}}},
        {"bucket": 1024, "rows": 2, "s": 9e-3,
         "kernels": {"qmatvec": {"s": 5e-3, "n": 14},
                     "attn_prefill": {"s": 1e-3, "n": 2}}}],
        admitted=[(0.5, 3000), (1.2, 200), (1.5, 700), (1.6, 900),
                  (None, 64)])
    s, peaks = rec["shapes"], rec["peaks"]
    need = 2 * (counts.qmatvec_roofline_s(s, 8 * 256, peaks)
                + counts.qmatvec_roofline_s(s, 8 * 1024, peaks))
    assert run.read_metric("qmatvec_roofline.prefill", rec) == \
        pytest.approx(100.0 * need / 7e-3)
    ops = sum(counts.prompt_flops(s, n) for n in (200, 700, 900))
    assert run.read_metric("prefill_mfu", rec) == pytest.approx(
        100.0 * ops / (13e-3 * 197e12))
    # by hand: 2 layers of 7 matrices (30,720 weights a layer) and causal
    # attention over 4 heads of 16, and the readout at the last position
    assert counts.prompt_flops(s, 4) == \
        2 * 2 * 30720 * 4 + 2 * 2 * 4 * 16 * 4 * 5 + 2 * 512 * 64
    # a prompt that the traced prefills' rows do not account for: no reading
    rec["window"]["requests"].append({"admitted": 1.9, "prompt_len": 50})
    assert run.read_metric("prefill_mfu", rec) is None
    assert run.read_metric("prefill_mfu", dict(rec, traced_from_s=None)) \
        is None


@pytest.mark.parametrize("runs", [None, [], [
    {"bucket": 256, "rows": 1, "s": 0.0, "kernels": {}}]])
def test_prefill_readers_without_whole_prefills_read_nothing(runs):
    rec = _prefill_rec(runs)
    assert run.read_metric("qmatvec_roofline.prefill", rec) is None
    assert run.read_metric("prefill_mfu", rec) is None
    assert run.read_metric("prefill_mfu", dict(rec, trace=None)) is None


def test_the_prompt_cell_is_found_by_name():
    cell = run.load_cell("qwen2-1.5b.prompt_open", ROOT / "BENCHMARK.json")
    assert cell["config"]["serve"]["max_len"] == 4224
    assert cell["config"]["serve"]["slots"] == 8
    assert cell["traffic"]["prompt_len"]["max"] == 4096
    assert cell["config"]["model"] == run.load_cell(
        "qwen2-1.5b.decode_open", ROOT / "BENCHMARK.json")["config"]["model"]
    names = {m["name"] for m in cell["end_to_end"]}
    assert {"out_tok_s", "ttft_p95_ms", "peak_hbm_gb", "setup_s"} <= names
    # its ITL p95 sits where the gaps that prefills stretch begin
    # (PERF.md section 2)
    assert "itl_p95_ms" not in names
    per_layer = {m["name"] for m in cell["per_layer"]}
    assert {"admit_per_prefill.ttft", "prefill_share.ttft",
            "prefill_row_use.ttft", "qmatvec_roofline.prefill",
            "prefill_mfu"} <= per_layer
    assert not {"admit_per_prefill", "prefill_share",
                "prefill_row_use"} & per_layer


def test_every_per_layer_metric_moves_what_its_cells_report():
    """A cell reports a per-layer metric only where it reports the
    end-to-end metric that it moves, and every cell that a metric lists
    reports that metric."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: run.load_cell(w["name"], ROOT / "BENCHMARK.json")
             for w in spec["workloads"]}
    for m in spec["per_layer"]:
        for name in m.get("workloads", ()):
            reports = {e["name"] for e in cells[name]["end_to_end"]}
            assert m["moves"] in reports, (m["name"], name)
    for cell in cells.values():
        reports = {e["name"] for e in cell["end_to_end"]}
        assert all(m["moves"] in reports for m in cell["per_layer"])


def test_a_split_metric_is_read_by_its_base_file():
    """``<base>.<suffix>`` without a file of its own is read by
    ``<base>.py``; one with its own file by that."""
    metrics = run.BENCH / "metrics"
    assert run.metric_file("prefill_share.ttft") == \
        metrics / "prefill_share.py"
    assert run.metric_file("qmatvec_roofline.prefill") == \
        metrics / "qmatvec_roofline.prefill.py"
    rec = {"counters": {"prefill_tokens": 30, "prefill_positions": 120}}
    assert run.read_metric("prefill_row_use.ttft", rec) == \
        run.read_metric("prefill_row_use", rec) == 25.0
