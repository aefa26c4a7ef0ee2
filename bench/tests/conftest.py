"""Shared helpers: the repository's ``src`` and root on the path, and a
tiny harness run on the CPU."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def harness(capsys, workload="tiny.decode_open", seed=12345, seconds=2.0,
            trace=0, engine_hook=None):
    """Run bench/run.py's main on the CPU against the test benchmark file;
    returns (exit code, result or None)."""
    from bench import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  bench_file=DATA / "BENCHMARK.json", require_tpu=False,
                  engine_hook=engine_hook)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


@pytest.fixture
def tiny_conf():
    return json.loads((DATA / "tiny.json").read_text())
