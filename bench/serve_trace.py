#!/usr/bin/env python3
"""The serving engine's own instrumentation in one window: its ``serve.*``
host spans, the ``model.*``/``tick.*`` name scopes of its device ops, and
its counters.

    python3 bench/serve_trace.py --workload qwen2-1.5b.decode_open \
        --seed 7 --seconds 51 [--traced-seconds 4] [--read-trace 1]

From the checkout's root, on the chip. It runs the cell's window as
``bench/run.py`` does (the same engine, warm-up, traffic and loop, but no
check against the reference) and traces the window's last
``--traced-seconds``: the whole window when that is at least
``--seconds``, nothing when 0. ``--read-trace 0`` records the trace but
reads nothing back, to measure what tracing costs. It prints one JSON
line: ``metrics`` (the cell's end-to-end and per-layer metrics, and
``prefill_row_use``, ``live_slots_per_tick`` and ``sync_idle_share``),
``breakdown`` (``device_ops`` and ``idle_gaps`` as ``bench/run.py`` gives
them, and ``idle_gaps_program`` and ``tick_scopes``), the window's
``counters`` and ``span_us``: what one span costs the host with the
profiler off and on.

``load`` and ``reduce`` read what ``bench/devtrace.py`` leaves out, in the
same plain form, in nanoseconds on the trace's clock:

    {"window": [t0, t1], "ops": [...],              # as devtrace.load
     "spans": [[name, t0, t1], ...],                # serve.* host spans
     "scopes": [[scope, t0, t1, program], ...]}     # one per entry of ops

A device op's scope is the innermost ``model.*`` or ``tick.*`` component of
its HLO op name, else ``unscoped``; control flow is left out as in
``devtrace``. ``ProfileData`` gives a device op's own stats only, and the op
name is a stat of the op's metadata: it is read from the ``.trace.json.gz``
that the profiler writes beside the ``.xplane.pb``, matched by instruction
name and device offset.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

SPANS = ("serve.admit", "serve.tick", "serve.sync.wait", "serve.sync.host")
SCOPE_PREFIXES = ("model.", "tick.")
OP_NAME_STAT = "tf_op"
OFFSET_STAT = "device_offset_ps"
# the metrics that read what the engine records (bench/metrics/)
METRICS = (("prefill_row_use", "%"), ("live_slots_per_tick", "slots"),
           ("sync_idle_share", "%"))


def scope_of(op_name: str) -> str:
    """``jit(_tick)/while/body/model.mlp/dot_general:`` -> ``model.mlp``.
    A fusion lists the names of its ops, joined by ``;``: the first that
    has a scope gives it."""
    for name in op_name.rstrip(":").split(";"):
        for part in reversed(name.split("/")):
            if part.startswith(SCOPE_PREFIXES):
                return part
    return "unscoped"


def op_names(xplane_path) -> Dict[Tuple[str, str], str]:
    """(instruction name, device offset) -> HLO op name, for every device
    op of the ``.trace.json.gz`` beside ``xplane_path``; empty without
    one."""
    path = Path(str(xplane_path).replace(".xplane.pb", ".trace.json.gz"))
    if not path.is_file():
        return {}
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    return {(ev["name"], ev["args"][OFFSET_STAT]): ev["args"][OP_NAME_STAT]
            for ev in events
            if OP_NAME_STAT in ev.get("args", ())
            and OFFSET_STAT in ev["args"]}


def load(path) -> dict:
    """``devtrace.load`` of one ``.xplane.pb``, with ``spans`` and
    ``scopes`` (see the module doc)."""
    from jax.profiler import ProfileData
    out = devtrace.load(path)
    pd = ProfileData.from_file(str(path))
    out["spans"], raw = [], []
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device is None \
                and any(ln.name == "XLA Ops" for ln in plane.lines):
            device = plane
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    out["spans"].append([ev.name, ev.start_ns, ev.end_ns])
    names = op_names(path) if device is not None else {}
    for line in (device.lines if device is not None else ()):
        if line.name == "XLA Ops":
            for ev in line.events:
                key = (ev.name.split(" = ", 1)[0].lstrip("%"),
                       str(dict(ev.stats).get(OFFSET_STAT)))
                raw.append((ev.name, ev.start_ns, ev.end_ns,
                            scope_of(names.get(key, ""))))
    raw.sort(key=lambda o: o[1])
    ops = devtrace.attribute(out["modules"], [o[:3] for o in raw])
    scopes = [o[3] for o in raw
              if devtrace.op_base(o[0]) not in devtrace.CONTROL_FLOW]
    out["scopes"] = [[sc, a, b, prog]
                     for sc, (_, a, b, prog) in zip(scopes, ops)]
    return out


def idle_gaps(tr: dict):
    """The window's spans in which no device op runs, as devtrace.reduce
    finds them."""
    w0, w1 = tr["window"]
    busy = devtrace.union([(max(o[1], w0), min(o[2], w1))
                           for o in tr["ops"] if o[2] > w0 and o[1] < w1])
    gaps, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def reduce(tr: dict) -> Optional[dict]:
    """Idle seconds by the ``serve.*`` span that overlaps each gap most
    (``none`` where none does), by ``devtrace.reduce``'s rule; device
    seconds by ``program:scope``; the tick program's device seconds by
    ``scope:op``; and the breakdown's two top-10 lists. None when the trace
    holds no window or no device op."""
    if tr["window"] is None or not tr["ops"]:
        return None
    ns = 1e-9
    w0, w1 = tr["window"]
    # the engine's spans come one after another from one thread: sorted by
    # start they are sorted by end too
    spans = sorted((a, b, n) for n, a, b in tr["spans"])
    ends = [b for _, b, _ in spans]
    idle: Dict[str, float] = {}
    for g0, g1 in idle_gaps(tr):
        best, label = 0.0, "none"
        for a, b, n in spans[bisect.bisect_right(ends, g0):]:
            if a >= g1:
                break
            over = min(b, g1) - max(a, g0)
            if over > best:
                best, label = over, n
        idle[label] = idle.get(label, 0.0) + (g1 - g0) * ns
    scope_s: Dict[str, float] = {}
    tick: Dict[str, float] = {}
    for (sc, a, b, prog), (op, _, _, _) in zip(tr["scopes"], tr["ops"]):
        if b <= w0 or a >= w1:
            continue
        s = (min(b, w1) - max(a, w0)) * ns
        key = f"{prog}:{sc}"
        scope_s[key] = scope_s.get(key, 0.0) + s
        if prog == "tick":
            key = f"{sc}:{devtrace.KERNELS.get(op, op)}"
            tick[key] = tick.get(key, 0.0) + s

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:10]

    return {"idle_by_program_span": idle, "scope_s": scope_s,
            "tick_scope_ops": tick,
            "breakdown": {"idle_gaps_program": top(idle),
                          "tick_scopes": top(tick)}}


class Tracer(devtrace.Tracer):
    """``devtrace.Tracer`` whose ``stop`` also reduces the engine's spans
    and scopes into the result, or with ``read=False`` reads nothing."""

    def __init__(self, directory: Path, read: bool = True):
        super().__init__(directory)
        self.read = read

    def stop(self) -> Optional[dict]:
        if not self.started:
            return None
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = sorted(self.dir.rglob("*.xplane.pb"))
        try:
            if not self.read:
                return None
            tr = load(files[-1])
            base, mine = devtrace.reduce(tr), reduce(tr)
            if base is None or mine is None:
                return base
            breakdown = dict(base["breakdown"], **mine.pop("breakdown"))
            return dict(base, **mine, breakdown=breakdown)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


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


def counter_delta(c0: dict, c1: dict) -> dict:
    return {k: c1[k] - c0.get(k, 0) for k in c1}


def probe(args, bench_file: Path, require_tpu: bool = True) -> dict:
    """One window of the cell, instrumented; the result object."""
    from bench import loop, run, traffic
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cell = run.load_cell(args.workload, bench_file)
    devs = run.check_devices(cell, require_tpu)
    enable_compile_cache()
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    conf, mix = cell["config"], cell["traffic"]
    shapes = run.family(conf).shapes(conf["model"])
    eng = run.build_engine(conf, mix, args.seed)
    items = traffic.generate(mix, args.seed, args.seconds, shapes.vocab)
    setup_peak = run.device_info(devs)["memory_peak_bytes"]
    setup_s = time.perf_counter() - T_START
    win = loop.Window(eng, mix, items, args.seconds)
    tracer = Tracer(run.OUT / f"serve-trace-{args.workload}",
                    read=bool(args.read_trace)) \
        if args.traced_seconds > 0 else None
    on_tick = None
    if tracer is not None:
        t_from = min(args.traced_seconds, args.seconds)

        def on_tick(now):
            if not tracer.started and now >= win.t0 + args.seconds - t_from:
                tracer.start()
    c0 = eng.counters()
    win.run(on_tick)
    counters = counter_delta(c0, eng.counters())
    dev = run.device_info(devs)
    wrec = win.record()
    trace = tracer.stop() if tracer is not None else None
    cost = span_cost_us()
    rec = {"window": wrec, "setup_s": setup_s, "counters": counters,
           "memory": {"peak_bytes": dev["memory_peak_bytes"],
                      "setup_peak_bytes": setup_peak},
           "trace": trace, "model": conf["model"], "serve": conf["serve"],
           "shapes": shapes, "peaks": peaks.get(devs[0].device_kind)}
    wanted = [(m["name"], m["unit"])
              for m in cell["end_to_end"] + cell["per_layer"]] + list(METRICS)
    metrics = {}
    for name, unit in wanted:
        v = run.read_metric(name, rec)
        if v is not None:
            metrics[name] = {"value": v, "unit": unit}
    result = {"workload": args.workload, "seed": args.seed,
              "traced_seconds": args.traced_seconds, "metrics": metrics,
              "counters": counters, "span_us": cost, "device": dev}
    if trace is not None:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = trace["breakdown"]
        result["scope_s"] = trace["scope_s"]
        result["tick_scope_ops"] = trace["tick_scope_ops"]
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
    ap.add_argument("--read-trace", type=int, choices=(0, 1), default=1)
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
