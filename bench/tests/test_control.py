"""The check's control at test size: the reference in float8 activations,
put in the program's place, fails the limit that the program's own served
tokens pass (bench/control.py reads the same on the chip at the cells'
sizes)."""
import json

from bench import control
from bench.tests.conftest import DATA


def test_control_fails_the_limit_the_program_passes():
    limit = json.loads((DATA / "tiny.json").read_text())["check"][
        "served_gap_limit"]
    out = control.readings("tiny.decode_open", [77, 78], 1, 2.0,
                           bench_file=DATA / "BENCHMARK.json",
                           require_tpu=False)
    s = out["summary"]
    assert s["lower"] <= limit < s["fp8"], s


def test_control_in_the_programs_place_is_not_correct(capsys):
    """A whole run with the float8 reference's choices standing in for the
    served tokens: run.py's own checks find it not correct."""
    out = control.judged("tiny.decode_open", [77], 2.0,
                         bench_file=DATA / "BENCHMARK.json",
                         require_tpu=False)
    (row,) = out["rows"]
    assert row["correct"] is False, row
    gap = row["checks"]["served_gap"]
    assert gap["value"] > gap["limit"], row
    assert all(c["value"] <= c["limit"] for name, c in row["checks"].items()
               if name != "served_gap"), row
