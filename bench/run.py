#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload qwen2-1.5b.decode_open --seed 7 \
        --seconds 30 --trace 0

From the checkout's root. The cell is found by name in ``BENCHMARK.json``;
its configuration, traffic mix and metric readers by name under ``bench/``
(see bench/README.md). One process: it builds the serve-form weights on the
chip from the seed, warms up the engine's programs for the cell's shapes,
measures for ``--seconds``, reads peak device memory, frees the engine, and
checks a sample of the served tokens against the plain reference. With
``--trace 1`` it also traces the last seconds of the window and reports the
per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its limit).
Everything else goes to standard error or ``bench/out/``. With no TPU, with
fewer chips than the cell asks for, or outside a checkout of the
repository, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
TRACE_SECONDS = 4.0          # traced tail of a --trace 1 window, at most


class CellError(Exception):
    """A run that cannot produce a result."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, bench_file: Path) -> dict:
    """The cell's entry, configuration, mix and metrics, found by name. The
    mix is ``traffic/<name>.json`` beside ``bench_file`` where that
    directory exists (the tests' own mixes), else in ``bench/traffic``. A
    metric with ``workloads`` is the listed cells'; a per-layer metric is
    also only that of cells that report the end-to-end metric it moves."""
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in {bench_file}")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf_entry["file"]).read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    from bench import traffic
    mixes = bench_file.parent / "traffic"
    if not mixes.is_dir():
        mixes = BENCH / "traffic"
    end_to_end = [m for m in spec["end_to_end"] if mine(m)]
    reported = {m["name"] for m in end_to_end}
    return {"cell": cell, "config": config,
            "traffic": traffic.load(mixes / f"{cell['traffic']}.json"),
            "end_to_end": end_to_end,
            "per_layer": [m for m in spec["per_layer"]
                          if mine(m) and m.get("moves", "") in reported
                          | {""}]}


def metric_file(name: str) -> Path:
    """``bench/metrics/<name>.py``. A metric split by the end-to-end metric
    it moves, ``<base>.<suffix>`` with no file of its own, is read by
    ``<base>.py`` (``prefill_share.ttft`` by ``prefill_share.py``)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def read_metric(name: str, rec: dict):
    """Run the metric's file's ``read(rec)`` (``metric_file``); None =
    nothing to read, and the metric is left out."""
    path = metric_file(name)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def family(conf: dict):
    """The configuration's family module, ``bench/families/<family>.py``:
    the program's ModelConfig, sizes and serve-form weights."""
    return importlib.import_module(f"bench.families.{conf['family']}")


def reference(conf: dict):
    """The configuration's plain reference, ``bench/reference/<family>.py``,
    which imports nothing of the program."""
    return importlib.import_module(f"bench.reference.{conf['family']}")


def device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class CompileCounter:
    """Counts JAX compilation events while ``armed``."""

    def __init__(self):
        import jax
        self.armed, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if self.armed and "compil" in event:
            self.events.append(event)


WARM_ADMISSIONS = 2       # queued admissions warmed up, where unstated


def warm_up(eng, mix: dict, max_len: int, queued: int = WARM_ADMISSIONS):
    """Compile every program the window will run (decode ticks, and one
    admission per bucket the mix's prompts can fall in), then admit the
    ``queued`` longest buckets back to back with every program compiled, as
    requests that come together in the window are admitted. An admission's
    prefill hands the cache it fills to the insert as one buffer of the
    whole cache's size, made when the prefill is dispatched and freed when
    the insert has run: each later admission's is made while the earlier
    ones' are still held, which is the peak of device memory the window
    reaches when it queues that many, and set-up then holds it in every
    run. The count is the configuration's ``warm_admissions``, 2 where the
    file has none: what ``decode_open``'s window queues at once (see
    bench/README.md)."""
    from bench import loop, traffic
    buckets = traffic.buckets(mix, lambda n: loop.bucket_of(n, max_len))
    for group in (buckets, buckets[::-1][:queued]):
        for b in group:
            eng.submit([1] * min(b, mix["prompt_len"]["max"]),
                       max_new=eng.drain_every + 2)
        eng.run_all()


def sample_requests(win, conf: dict, seed: int):
    """The check's sample, as (prompt, served tokens, admission bucket): the
    finished request with the most served tokens, and others drawn from the
    seed, ``check.sample_requests`` in all."""
    import numpy as np

    from bench import loop
    done = sorted((t.req for t in win.tracks.values()
                   if t.finished is not None and t.req.status == "ok"),
                  key=lambda r: (-len(r.out), r.uid))
    if not done:
        return []
    rng = np.random.default_rng((int(seed) * 7919 + 17) & ((1 << 63) - 1))
    rest = [done[i + 1] for i in rng.permutation(len(done) - 1)]
    n = conf["check"]["sample_requests"]
    return [(list(r.prompt), list(r.out),
             loop.bucket_of(len(r.prompt), conf["serve"]["max_len"]))
            for r in [done[0]] + rest[:n - 1]]


def reference_gaps(conf: dict, mix: dict, seed: int, reqs,
                   controls=()) -> dict:
    """The reference's widest gaps over ``reqs`` (see ``gaps`` in
    ``bench/reference/<family>.py``), at one of the cell's few batch shapes:
    the served tokens are padded to the power of two (at least 64) that
    holds the longest, so that a cell compiles the reference for at most a
    handful of shapes and a slow cell does not pay for answers it never
    finishes."""
    from bench import loop
    if not reqs:
        return dict({c: float("inf") for c in ("served",) + tuple(controls)},
                    tokens=0)
    need = max(len(o) - 1 for _, o, _ in reqs)
    shape = (conf["check"]["sample_requests"],
             loop.bucket_of(mix["prompt_len"]["max"],
                            conf["serve"]["max_len"]),
             min(mix["output_len"]["max"] - 1,
                 max(64, 1 << max(need - 1, 0).bit_length())))
    return reference(conf).gaps(conf["model"], seed, reqs, controls,
                                shape=shape)


def build_engine(conf: dict, mix: dict, seed: int, engine_hook=None):
    """Serve-form weights made on the device from the seed, the engine the
    configuration states, and its programs warmed up for the mix."""
    import jax
    import jax.numpy as jnp

    from repro.core import precision
    from repro.serving.engine import ServingEngine
    serve, fam = conf["serve"], family(conf)
    if serve["form"] not in fam.FORMS:
        raise CellError(f"{conf['family']} weights are made in the forms "
                        f"{fam.FORMS}, not {serve['form']!r}")
    try:
        cfg = fam.program_config(conf)
    except ValueError as e:
        raise CellError(str(e))
    params = jax.block_until_ready(fam.serve_params(seed, conf))
    eng = ServingEngine(
        params, cfg, policy=getattr(precision, serve["policy"]),
        slots=serve["slots"], max_len=serve["max_len"],
        dtype=jnp.dtype(serve["activation_dtype"]), temperature=0.0,
        eos_id=None, seed=seed % (1 << 31),
        drain_every=serve["drain_every"], matmul_mode=serve["matmul_mode"],
        attn_mode=serve["attn_mode"], degrade=False)
    if engine_hook is not None:
        engine_hook(eng)
    warm_up(eng, mix, serve["max_len"],
            conf.get("warm_admissions", WARM_ADMISSIONS))
    return eng


def check_devices(cell: dict, require_tpu: bool):
    """The devices, or CellError where the cell cannot run here."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < cell["cell"]["chips"]:
        raise CellError(f"{cell['cell']['chips']} chips asked for, "
                        f"{len(devs)} found")
    return devs


def counter_delta(c0: dict, c1: dict) -> dict:
    """What each engine counter added between two readings."""
    return {k: c1[k] - c0.get(k, 0) for k in c1}


def run(args, bench_file: Path, require_tpu: bool = True,
        engine_hook=None, control: str = None,
        traced_seconds: float = None):
    """One run; returns the result object and the run's record, from which
    the metrics are read (raises CellError). ``control`` (``bench/control.py
    --judge``; never in a benchmark run) puts that control's first choices
    in the served tokens' place: the reference in a lower precision, on the
    same prompts and tokens, judged by the same checks. ``traced_seconds``
    (``bench/serve_trace.py``; never in a benchmark run) traces that much
    of the window's tail in place of ``TRACE_SECONDS``."""
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise CellError(f"run from a checkout of the repository ({e})")
    import jax

    from bench import devtrace, loop, traffic

    cell = load_cell(args.workload, bench_file)
    devs = check_devices(cell, require_tpu)
    cache_dir = enable_compile_cache()
    log(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
        f"jax {jax.__version__}, compile cache {cache_dir}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if require_tpu and devs[0].device_kind not in peaks:
        raise CellError(f"no peaks for {devs[0].device_kind!r} in "
                        f"bench/peaks.json")
    conf, mix = cell["config"], cell["traffic"]
    shapes = family(conf).shapes(conf["model"])
    eng = build_engine(conf, mix, args.seed, engine_hook)
    items = traffic.generate(mix, args.seed, args.seconds, shapes.vocab)
    counter = CompileCounter()
    traces0 = eng.trace_counts()
    setup_peak = device_info(devs)["memory_peak_bytes"]
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f}s, peak after set-up {setup_peak} B, "
        f"{len(items)} requests drawn, trace counts {traces0}")

    win = loop.Window(eng, mix, items, args.seconds)
    if traced_seconds is None:
        traced_seconds = min(TRACE_SECONDS, args.seconds / 2) \
            if args.trace else 0.0
    # one directory per run, so that runs side by side do not share one
    tracer = devtrace.Tracer(OUT / f"trace-{args.workload}.seed{args.seed}"
                             ) if traced_seconds > 0 else None
    on_tick, traced_from = None, []
    if tracer is not None:
        def on_tick(now):
            if not tracer.started and \
                    now >= win.t0 + args.seconds - traced_seconds:
                # nothing dispatched before the trace is left to run, so
                # that each prefill it holds is paired with its admission
                # (devtrace.prefill_runs)
                jax.block_until_ready(eng.cache)
                tracer.start()
                traced_from.append(win.clock() - win.t0)
    c0 = eng.counters()
    counter.armed = True
    win.run(on_tick)
    counter.armed = False
    counters = counter_delta(c0, eng.counters())
    dev = device_info(devs)
    traces1 = eng.trace_counts()
    fallbacks = list(eng.fallback_events)
    wrec = win.record()
    if tracer is not None and tracer.started:
        # and what the window dispatched runs to its end inside the trace
        jax.block_until_ready(eng.cache)
    trace = tracer.stop() if tracer is not None else None
    log(f"window {wrec['window_s']:.3f}s: {wrec['decode_calls']} ticks, "
        f"{wrec['prefill_calls']} prefill calls, {wrec['attempted']} "
        f"requests, {wrec['queued_at_end']} queued at the end, generator "
        f"late by at most {max(wrec['submit_lag_s'], default=0.0):.4f}s, "
        f"peak {dev['memory_peak_bytes']} B (set-up {setup_peak} B)")

    reqs = sample_requests(win, conf, args.seed)
    del eng, win
    gc.collect()
    t_ref = time.perf_counter()
    controls = (control,) if control else ()
    gap = reference_gaps(conf, mix, args.seed, reqs, controls)[
        control or "served"]
    log(f"reference{f' and control {control}' if control else ''} over "
        f"{len(reqs)} requests, "
        f"{sum(len(o) for _, o, _ in reqs)} served tokens: "
        f"{time.perf_counter() - t_ref:.1f}s")

    failed = wrec["refused"] + sum(
        1 for r in wrec["requests"]
        if r["status"] not in (None, "ok"))
    compiles = len(counter.events) + sum(
        traces1[k] - traces0.get(k, 0) for k in traces1)
    checks = {
        "served_gap": {"value": gap,
                       "limit": conf["check"]["served_gap_limit"]},
        "window_compiles": {"value": compiles, "limit": 0},
        "fallbacks": {"value": len(fallbacks), "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    rec = {"window": wrec, "setup_s": setup_s, "counters": counters,
           "memory": {"peak_bytes": dev["memory_peak_bytes"],
                      "setup_peak_bytes": setup_peak},
           "trace": trace,
           "traced_from_s": traced_from[0] if traced_from else None,
           "model": conf["model"], "serve": conf["serve"],
           "shapes": shapes, "peaks": peaks.get(devs[0].device_kind)}
    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = read_metric(m["name"], rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": wrec["attempted"],
              "failed": failed, "metrics": metrics, "device": dev}
    if trace is not None:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    result["checks"] = checks
    OUT.mkdir(exist_ok=True)
    side = {k: v for k, v in rec.items() if k != "shapes"}
    side["window"] = dict(wrec, requests=[
        {k: v for k, v in r.items() if k != "gaps"}
        for r in wrec["requests"]])
    (OUT / f"{args.workload}.seed{args.seed}.trace{args.trace}.json"
     ).write_text(json.dumps(side, default=float))
    return result, rec


def main(argv=None, bench_file: Path = ROOT / "BENCHMARK.json",
         require_tpu: bool = True, engine_hook=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result, _ = run(args, bench_file, require_tpu, engine_hook)
    except CellError as e:
        log(f"bench: {e}")
        return 1
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
