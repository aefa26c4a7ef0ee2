#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, on the chip, and the
control judged by the benchmark's own checks.

    python3 bench/control.py --workload qwen2-1.5b.decode_open \
        --seeds 101,102,103 --control-seeds 3 --seconds 30
    python3 bench/control.py --workload qwen2-1.5b.decode_open \
        --seeds 104,105,106 --judge fp8 --seconds 51

One process, one seed after another. Without ``--judge``: the program
serves the cell's own mix for ``--seconds`` as in a benchmark run, and the
reference reads the widest gap of the served tokens (the lower reading,
over every seed). On the first ``--control-seeds`` seeds it also reads the
controls on the same prompts and tokens: the reference in float8_e4m3fn
activations (``fp8``, one step below the configuration's bfloat16) and with
an int8 KV cache (``kv8``, the program's own ``kv_bits=8`` path). The
smallest control reading is the upper one. Prints one JSON line per seed
and a summary; writes ``bench/out/control-<workload>.json``.

With ``--judge <control>``: whole runs of ``bench/run.py`` in which that
control's first choices stand in the served tokens' place, so that
``run.py``'s own checks decide ``correct``, which has to come out false on
every seed. Prints one JSON line per seed and a summary.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CONTROLS = ("fp8", "kv8")


def readings(workload: str, seeds, control_seeds: int, seconds: float,
             bench_file: Path = ROOT / "BENCHMARK.json",
             require_tpu: bool = True, engine_hook=None) -> dict:
    from bench import loop, run, traffic
    cell = run.load_cell(workload, bench_file)
    run.check_devices(cell, require_tpu)
    conf, mix = cell["config"], cell["traffic"]
    vocab = run.family(conf).shapes(conf["model"]).vocab
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        eng = run.build_engine(conf, mix, seed, engine_hook)
        items = traffic.generate(mix, seed, seconds, vocab)
        win = loop.Window(eng, mix, items, seconds)
        win.run()
        reqs = run.sample_requests(win, conf, seed)
        del eng, win
        gc.collect()
        controls = CONTROLS if i < control_seeds else ()
        g = run.reference_gaps(conf, mix, seed, reqs, controls)
        row = dict(g, seed=seed, requests=len(reqs),
                   secs=time.perf_counter() - t0)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": workload, "seconds": seconds,
               "lower": max(r["served"] for r in rows)}
    for c in CONTROLS:
        got = [r[c] for r in rows if c in r]
        if got:
            summary[c] = min(got)
    return {"rows": rows, "summary": summary}


def judged(workload: str, seeds, seconds: float, control: str = "fp8",
           bench_file: Path = ROOT / "BENCHMARK.json",
           require_tpu: bool = True) -> dict:
    """Whole ``run.py`` runs with ``control`` in the program's place; each
    row holds the run's ``correct`` and its ``checks``."""
    from bench import run
    rows = []
    for seed in seeds:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0)
        res, _ = run.run(args, bench_file, require_tpu, control=control)
        gc.collect()
        row = {"seed": seed, "control": control, "correct": res["correct"],
               "checks": res["checks"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": workload, "seconds": seconds, "control": control,
               "correct": [r["correct"] for r in rows],
               "served_gap": [r["checks"]["served_gap"]["value"]
                              for r in rows]}
    return {"rows": rows, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--judge", choices=CONTROLS,
                    help="judge this control by run.py's checks instead")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        if args.judge:
            out = judged(args.workload, seeds, args.seconds, args.judge)
        else:
            out = readings(args.workload, seeds, args.control_seeds,
                           args.seconds)
    except run.CellError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    (BENCH / "out").mkdir(exist_ok=True)
    tag = f"judge-{args.judge}" if args.judge else "control"
    (BENCH / "out" / f"{tag}-{args.workload}.json").write_text(
        json.dumps(out))
    print(json.dumps(out["summary"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
