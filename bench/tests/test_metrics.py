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
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
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
