"""Profiler trace of a window, and its reduction to device time.

``Tracer`` captures the last seconds of a ``--trace 1`` window with
``jax.profiler`` and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``. ``load`` keeps what the metrics need, in a
plain form that tests can write by hand:

    {"window": [t0, t1],                       # the bench.traced host span
     "host": [[name, t0, t1], ...],            # bench.* and engine.* spans
     "spans": [[name, t0, t1], ...],           # the engine's serve.* spans
     "admits": [[t0, t1, bucket, rows], ...],  # serve.admit, with its args
     "modules": [[name, t0, t1], ...],         # XLA Modules line
     "ops": [[op, t0, t1, program, scope], ...]}   # XLA Ops line

all in nanoseconds on the trace's clock, for device 0 (the cells run on
one chip). ``op`` is the HLO instruction's name without its number
(``fusion``, ``qmatvec_pallas``); a Pallas kernel's custom call carries
the name of the jitted function around its ``pallas_call``, and an op whose
name ends in ``_pallas`` is reduced as the kernel named by the rest
(``qmatvec_pallas`` is the kernel ``qmatvec``). ``program`` is the family
(``tick``, ``prefill``, ``admit`` or ``other``) of the XLA module the op ran
inside. ``scope`` is the innermost ``model.*`` or ``tick.*`` component of
the op's HLO op name, else ``unscoped``: ``ProfileData`` gives a device op's
own stats only, and the op name is a stat of the op's metadata, so it is
read from the ``.trace.json.gz`` that the profiler writes beside the
``.xplane.pb``, matched by instruction name and device offset. Control-flow
ops (``while``, ``conditional``, ``call``) span the ops of their bodies and
are left out. ``reduce`` turns that into device busy time, idle gaps by
host span and by engine span, time per program family, per kernel and per
scope, each admission's prefill with its bucket, and the ``breakdown`` of
the result line.
"""
from __future__ import annotations

import bisect
import gzip
import json
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# program families by XLA module name prefix
PROGRAMS = {"tick": ("jit__tick", "jit__spec_tick"),
            "prefill": ("jit__prefill",),
            "admit": ("jit__admit_many", "jit__admit_device")}
HOST_SPANS = ("bench.wait_arrival", "engine.submit", "engine.step",
              "engine.drain")
# the engine's own spans (src/repro/serving/engine.py)
PROGRAM_SPANS = ("serve.admit", "serve.tick", "serve.sync.wait",
                 "serve.sync.host")
ADMIT_SPAN = "serve.admit"     # args bucket, rows; one prefill dispatched
KERNEL_SUFFIX = "_pallas"
CONTROL_FLOW = ("while", "conditional", "call")
SCOPE_PREFIXES = ("model.", "tick.")
OP_NAME_STAT = "tf_op"
OFFSET_STAT = "device_offset_ps"


class Tracer:
    """Start and stop a profiler trace into ``directory`` (emptied)."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)
        self.started = False
        self._span = None

    def start(self):
        import jax
        from jax.profiler import TraceAnnotation
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir))
        self._span = TraceAnnotation("bench.traced")
        self._span.__enter__()
        self.started = True

    def stop(self) -> Optional[dict]:
        """Stop, read and reduce the trace, delete the files."""
        if not self.started:
            return None
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = sorted(self.dir.rglob("*.xplane.pb"))
        try:
            return reduce(load(files[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _family(module: str) -> str:
    for fam, prefixes in PROGRAMS.items():
        if module.startswith(prefixes):
            return fam
    return "other"


def op_base(event_name: str) -> str:
    """``%qmatvec_pallas.60 = bf16[16,8960] custom-call(...)`` ->
    ``qmatvec_pallas``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def kernel_of(op: str) -> Optional[str]:
    """``qmatvec_pallas`` -> ``qmatvec``; None for an op of no kernel."""
    if op.endswith(KERNEL_SUFFIX) and len(op) > len(KERNEL_SUFFIX):
        return op[:-len(KERNEL_SUFFIX)]
    return None


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
    """The plain form of one ``.xplane.pb`` (see the module doc)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"window": None, "host": [], "spans": [], "admits": [],
           "modules": [], "ops": []}
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device is None \
                and any(ln.name == "XLA Ops" for ln in plane.lines):
            device = plane
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.traced":
                    out["window"] = [ev.start_ns, ev.end_ns]
                elif ev.name in HOST_SPANS:
                    out["host"].append([ev.name, ev.start_ns, ev.end_ns])
                elif ev.name in PROGRAM_SPANS:
                    out["spans"].append([ev.name, ev.start_ns, ev.end_ns])
                    if ev.name == ADMIT_SPAN:
                        args = dict(ev.stats)
                        out["admits"].append([ev.start_ns, ev.end_ns,
                                              int(args["bucket"]),
                                              int(args["rows"])])
    names = op_names(path) if device is not None else {}
    raw_ops = []
    for line in (device.lines if device is not None else ()):
        if line.name == "XLA Modules":
            out["modules"] = sorted(([ev.name, ev.start_ns, ev.end_ns]
                                     for ev in line.events),
                                    key=lambda m: m[1])
        elif line.name == "XLA Ops":
            for ev in line.events:
                key = (ev.name.split(" = ", 1)[0].lstrip("%"),
                       str(dict(ev.stats).get(OFFSET_STAT)))
                raw_ops.append((ev.name, ev.start_ns, ev.end_ns,
                                scope_of(names.get(key, ""))))
    out["ops"] = attribute(out["modules"], raw_ops)
    return out


def attribute(modules, raw_ops) -> list:
    """[op, t0, t1, program, scope] for each (HLO event name, t0, t1, scope)
    that is no control flow; ``modules`` are [name, t0, t1]."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, a, b, scope in sorted(raw_ops, key=lambda o: o[1]):
        op = op_base(name)
        if op in CONTROL_FLOW:
            continue
        i = bisect.bisect_right(starts, a) - 1
        prog = (_family(modules[i][0])
                if i >= 0 and modules[i][2] >= a else "other")
        out.append([op, a, b, prog, scope])
    return out


def union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def idle_by(gaps, spans, none: str) -> Dict[str, float]:
    """Idle seconds under each span name: how much of the idle gaps the
    spans of that name cover, and under ``none`` what no span covers. The
    spans come from one thread, one after another: sorted by start they
    are sorted by end too."""
    spans = sorted((a, b, n) for n, a, b in spans)
    ends = [b for _, b, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        left = g1 - g0
        for a, b, n in spans[bisect.bisect_right(ends, g0):]:
            if a >= g1:
                break
            over = min(b, g1) - max(a, g0)
            out[n] += over * 1e-9
            left -= over
        if left > 0:
            out[none] += left * 1e-9
    return dict(out)


def prefill_runs(tr: dict, w0: float, w1: float) -> Optional[list]:
    """Each prefill program that ran wholly inside [w0, w1], with the
    bucket and rows of the ``serve.admit`` span that dispatched it, its
    device seconds and its kernels' seconds and calls. Each span dispatches
    one prefill and the device runs programs in the order they came, so the
    trace's j-th prefill is its j-th span's, provided that nothing
    dispatched before the trace began was still to run (``bench/run.py``
    waits for the device before it starts a trace). None where the trace
    holds more prefills than spans, so that the two cannot be paired."""
    runs = [m for m in sorted(tr["modules"], key=lambda m: m[1])
            if _family(m[0]) == "prefill"]
    admits = sorted(tr.get("admits") or [])
    if len(runs) > len(admits):
        return None
    ops = [o for o in tr["ops"] if o[3] == "prefill"]
    starts = [o[1] for o in ops]
    out = []
    for (_, a, b), (_, _, bucket, rows) in zip(runs, admits):
        if a < w0 or b > w1:
            continue
        kernels: Dict[str, Dict[str, float]] = {}
        for op in ops[bisect.bisect_left(starts, a):
                      bisect.bisect_right(starts, b)]:
            kernel = kernel_of(op[0])
            if kernel and op[2] <= b:
                k = kernels.setdefault(kernel, {"s": 0.0, "n": 0})
                k["s"] += (op[2] - op[1]) * 1e-9
                k["n"] += 1
        out.append({"bucket": bucket, "rows": rows, "s": (b - a) * 1e-9,
                    "kernels": kernels})
    return out


def _top(d: Dict[str, float]) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
            ][:10]


def reduce(tr: dict) -> Optional[dict]:
    """Device busy and idle time in the traced window; idle time by host
    span (``idle_by_span``) and, where the trace holds the engine's spans,
    by engine span (``idle_by_program_span``); device time by program
    family, by kernel (time and calls), by ``program:scope`` and, in the
    tick program, by ``scope:op``; each prefill wholly inside the window
    with its admission's bucket (``prefill_runs``); and the breakdown's
    top-10 lists. None when the trace holds no window or no device op."""
    if tr["window"] is None or not tr["ops"]:
        return None
    w0, w1 = tr["window"]
    ns = 1e-9
    ops = [(o[0], max(o[1], w0), min(o[2], w1), o[3], o[4])
           for o in tr["ops"] if o[2] > w0 and o[1] < w1]
    busy = union([(a, b) for _, a, b, _, _ in ops])
    busy_ns = sum(b - a for a, b in busy)
    gaps, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    # per family: device time inside the window ("s"), and the count and
    # time of the runs wholly inside it ("n", "s_whole")
    programs: Dict[str, Dict[str, float]] = {}
    for name, a, b in tr["modules"]:
        if b <= w0 or a >= w1:
            continue
        p = programs.setdefault(_family(name),
                                {"s": 0.0, "n": 0, "s_whole": 0.0})
        p["s"] += (min(b, w1) - max(a, w0)) * ns
        if a >= w0 and b <= w1:
            p["n"] += 1
            p["s_whole"] += (b - a) * ns
    kernels: Dict[str, Dict[str, float]] = {}
    by_op: Dict[str, float] = defaultdict(float)
    scope_s: Dict[str, float] = defaultdict(float)
    tick_scope_ops: Dict[str, float] = defaultdict(float)
    for op, a, b, prog, scope in ops:
        s = (b - a) * ns
        kernel = kernel_of(op)
        name = kernel or op
        by_op[f"{prog}:{name}"] += s
        scope_s[f"{prog}:{scope}"] += s
        if prog == "tick":
            tick_scope_ops[f"{scope}:{name}"] += s
        if kernel:
            k = kernels.setdefault(f"{prog}:{kernel}", {"s": 0.0, "n": 0})
            k["s"] += s
            k["n"] += 1
    idle = idle_by(gaps, tr["host"], "other")
    out = {"window_s": (w1 - w0) * ns, "busy_s": busy_ns * ns,
           "idle_by_span": idle, "programs": programs, "kernels": kernels,
           "prefill_runs": prefill_runs(tr, w0, w1),
           "scope_s": dict(scope_s),
           "tick_scope_ops": dict(tick_scope_ops),
           "breakdown": {"device_ops": _top(by_op), "idle_gaps": _top(idle),
                         "tick_scopes": _top(tick_scope_ops)}}
    if tr.get("spans"):
        out["idle_by_program_span"] = idle_by(gaps, tr["spans"], "none")
        out["breakdown"]["idle_gaps_program"] = _top(
            out["idle_by_program_span"])
    return out
