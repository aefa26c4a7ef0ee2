"""Profiler trace of a window, and its reduction to device time.

``Tracer`` captures the last seconds of a ``--trace 1`` window with
``jax.profiler`` and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``. ``load`` keeps what the metrics need, in a
plain form that tests can write by hand:

    {"window": [t0, t1],                       # the bench.traced host span
     "host": [[name, t0, t1], ...],            # bench.* and engine.* spans
     "modules": [[name, t0, t1], ...],         # XLA Modules line
     "ops": [[op, t0, t1, program], ...]}      # XLA Ops line

all in nanoseconds on the trace's clock, for device 0 (the cells run on
one chip). ``op`` is the HLO instruction's name without its number
(``fusion``, ``qmatvec_pallas``); a Pallas kernel's custom call carries
the name of the jitted function around its ``pallas_call``. ``program`` is
the family (``tick``, ``prefill``, ``admit`` or ``other``) of the XLA
module the op ran inside. Control-flow ops (``while``, ``conditional``,
``call``) span the ops of their bodies and are left out. ``reduce`` turns
that into device busy time, idle gaps by host span, time per program
family and per kernel, and the ``breakdown`` of the result line.
"""
from __future__ import annotations

import bisect
import shutil
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# program families by XLA module name prefix
PROGRAMS = {"tick": ("jit__tick", "jit__spec_tick"),
            "prefill": ("jit__prefill",),
            "admit": ("jit__admit_many", "jit__admit_device")}
HOST_SPANS = ("bench.wait_arrival", "engine.submit", "engine.step",
              "engine.drain")
KERNELS = {"qmatvec_pallas": "qmatvec", "qmatmul_pallas": "qmatmul",
           "attn_decode_pallas": "attn_decode",
           "attn_prefill_pallas": "attn_prefill"}
CONTROL_FLOW = ("while", "conditional", "call")


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


def load(path) -> dict:
    """The plain form of one ``.xplane.pb`` (see the module doc)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"window": None, "host": [], "modules": [], "ops": []}
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
    raw_ops = []
    for line in (device.lines if device is not None else ()):
        if line.name == "XLA Modules":
            out["modules"] = sorted(([ev.name, ev.start_ns, ev.end_ns]
                                     for ev in line.events),
                                    key=lambda m: m[1])
        elif line.name == "XLA Ops":
            raw_ops = [(ev.name, ev.start_ns, ev.end_ns)
                       for ev in line.events]
    out["ops"] = attribute(out["modules"], raw_ops)
    return out


def attribute(modules, raw_ops) -> list:
    """[op, t0, t1, program] for each (HLO event name, t0, t1) that is no
    control flow; ``modules`` are [name, t0, t1]."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, a, b in sorted(raw_ops, key=lambda o: o[1]):
        op = op_base(name)
        if op in CONTROL_FLOW:
            continue
        i = bisect.bisect_right(starts, a) - 1
        prog = (_family(modules[i][0])
                if i >= 0 and modules[i][2] >= a else "other")
        out.append([op, a, b, prog])
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


def reduce(tr: dict) -> Optional[dict]:
    """Device busy and idle time in the traced window, idle time by host
    span, device time by program family and by kernel, and the breakdown.
    None when the trace holds no window or no device op."""
    if tr["window"] is None or not tr["ops"]:
        return None
    w0, w1 = tr["window"]
    ns = 1e-9
    ops = [(o[0], max(o[1], w0), min(o[2], w1), o[3])
           for o in tr["ops"] if o[2] > w0 and o[1] < w1]
    busy = union([(a, b) for _, a, b, _ in ops])
    busy_ns = sum(b - a for a, b in busy)
    gaps, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    # the host spans come from one thread, one after another: sorted by
    # start they are sorted by end too
    host = sorted((a, b, n) for n, a, b in tr["host"])
    ends = [b for _, b, _ in host]
    idle_by: Dict[str, float] = {}
    for g0, g1 in gaps:
        best, label = 0.0, "other"
        for a, b, n in host[bisect.bisect_right(ends, g0):]:
            if a >= g1:
                break
            over = min(b, g1) - max(a, g0)
            if over > best:
                best, label = over, n
        idle_by[label] = idle_by.get(label, 0.0) + (g1 - g0) * ns
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
    by_op: Dict[str, float] = {}
    for op, a, b, prog in ops:
        kernel = KERNELS.get(op)
        label = f"{prog}:{kernel or op}"
        by_op[label] = by_op.get(label, 0.0) + (b - a) * ns
        if kernel:
            k = kernels.setdefault(label, {"s": 0.0, "n": 0})
            k["s"] += (b - a) * ns
            k["n"] += 1
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) * ns, "busy_s": busy_ns * ns,
            "idle_by_span": idle_by, "programs": programs,
            "kernels": kernels,
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in idle]}}
