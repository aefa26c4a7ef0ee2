#!/usr/bin/env python3
"""What tracing costs a window, and one window of a cell with every metric
it can read.

    python3 bench/serve_trace.py --workload qwen2-1.5b.decode_open \
        --seed 7 --seconds 51 [--traced-seconds 4]

From the checkout's root, on the chip. It makes one ``bench/run.py`` run of
the cell (the same engine, warm-up, traffic, loop, trace reduction and
check) that traces the window's last ``--traced-seconds``: the whole window
when that is at least ``--seconds``, nothing when 0. The trace is read
after the window closes, so what tracing costs shows in the window's
numbers against a run with ``--traced-seconds 0``. It prints one JSON line: ``correct``, ``metrics`` (the cell's end-to-end
and per-layer metrics, each where it has something to read), the window's
engine ``counters``, ``device``, with a trace read back ``breakdown``,
``scope_s`` and ``tick_scope_ops`` (see ``bench/devtrace.py``), and
``span_us``: what one span costs the host with the profiler off and on.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def span_cost_us(n: int = 100_000) -> dict:
    """Host microseconds per ``serve.tick``-like span, with the profiler
    off and while it records."""
    import jax
    from jax.profiler import TraceAnnotation

    def per_span():
        t0 = time.perf_counter()
        for _ in range(n):
            with TraceAnnotation("serve.tick"):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    off = per_span()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        on = per_span()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return {"off": off, "on": on}


def probe(args, bench_file: Path, require_tpu: bool = True) -> dict:
    """One run of the cell, traced as asked; the result object."""
    from bench import run
    cell = run.load_cell(args.workload, bench_file)
    args.trace = int(args.traced_seconds > 0)
    res, rec = run.run(args, bench_file, require_tpu,
                       traced_seconds=args.traced_seconds)
    metrics = {}
    for m in cell["end_to_end"] + cell["per_layer"]:
        v = run.read_metric(m["name"], rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"workload": args.workload, "seed": args.seed,
              "traced_seconds": args.traced_seconds,
              "correct": res["correct"], "metrics": metrics,
              "counters": rec["counters"], "span_us": span_cost_us(),
              "device": res["device"]}
    if rec["trace"] is not None:
        result["breakdown"] = rec["trace"]["breakdown"]
        result["scope_s"] = rec["trace"]["scope_s"]
        result["tick_scope_ops"] = rec["trace"]["tick_scope_ops"]
    return result


def main(argv=None, bench_file: Path = ROOT / "BENCHMARK.json",
         require_tpu: bool = True) -> int:
    from bench import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced-seconds", type=float,
                    default=run.TRACE_SECONDS)
    args = ap.parse_args(argv)
    try:
        result = probe(args, bench_file, require_tpu)
    except run.CellError as e:
        run.log(f"serve_trace: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
