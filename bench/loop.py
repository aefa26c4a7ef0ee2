"""The loop that drives the engine through a measured window, and what it
records.

It follows ``ServingEngine.run_all``: submit what is due, ``step()``,
``drain()`` every ``drain_every`` ticks (and whenever a step ran no tick),
and sleep only when the engine is idle, until the next arrival. After each
drain it stamps, per request, when its tokens became visible on the host.
An open-loop request's time counts from when it was due, not from when it
was submitted; a closed-loop client sends its next request as soon as a
drain shows its last one finished.

Host spans (``jax.profiler.TraceAnnotation``) mark what the host is doing
for the trace reduction: ``bench.wait_arrival``, ``engine.submit``,
``engine.step`` and ``engine.drain``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Track:
    """What the window saw of one request."""
    req: object                      # the engine's Request
    due: float                       # host clock, seconds
    prompt_len: int
    client: Optional[int] = None
    seen: int = 0                    # tokens visible so far
    first: Optional[float] = None    # host clock of the first visible token
    last: Optional[float] = None
    gaps: List[float] = dataclasses.field(default_factory=list)
    tokens: int = 0                  # tokens that became visible in window
    admitted: Optional[float] = None  # host clock after the admitting step
    tick_tokens: int = 0             # of those, made by decode ticks
    context: int = 0                 # sum of attended positions of those
    finished: Optional[float] = None


def bucket_of(n: int, max_len: int) -> int:
    """The engine's admission bucket of an n-token prompt: the next power of
    two, at least 8, at most the cache length."""
    return min(max(8, 1 << (n - 1).bit_length()), max_len)


class Window:
    """Drive ``eng`` with ``items`` for ``seconds``; ``spec`` is the mix."""

    def __init__(self, eng, spec: dict, items, seconds: float,
                 clock: Callable[[], float] = time.perf_counter):
        self.eng, self.spec, self.items = eng, spec, items
        self.seconds, self.clock = seconds, clock
        self.tracks: Dict[int, Track] = {}
        self.inflight: Dict[int, Track] = {}
        self.refused = 0
        self.next_item = 0
        self.ready: List[Optional[int]] = []   # closed loop: clients to send
        self.lag: List[float] = []             # open loop: submit - due

    def _submit(self, item, due: float, client: Optional[int]):
        with TraceAnnotation("engine.submit"):
            out = self.eng.submit(item.prompt, max_new=item.max_new)
        if not out:
            self.refused += 1
            return
        req = self.eng.queue[-1]
        assert req.uid == int(out), (req.uid, out)
        tr = Track(req, due, len(item.prompt), client)
        self.tracks[req.uid] = self.inflight[req.uid] = tr

    def _submit_due(self, now: float):
        items = self.items
        if self.spec["loop"] == "open":
            while (self.next_item < len(items)
                   and self.t0 + items[self.next_item].due <= now):
                due = self.t0 + items[self.next_item].due
                self._submit(items[self.next_item], due, None)
                self.lag.append(now - due)
                self.next_item += 1
        else:
            for client in self.ready:
                self._submit(items[self.next_item % len(items)], now, client)
                self.next_item += 1
            self.ready = []

    def _stamp(self, finished, now: float):
        """Record what the last drain made visible."""
        for tr in list(self.inflight.values()):
            n = len(tr.req.out)
            if n > tr.seen:
                new = n - tr.seen
                if tr.first is None:
                    tr.first = now
                    tr.gaps.extend([0.0] * (new - 1))
                else:
                    tr.gaps.extend([(now - tr.last) / new] * new)
                for j in range(tr.seen, n):
                    if j >= 1:                 # token 0 comes from prefill
                        tr.tick_tokens += 1
                        tr.context += tr.prompt_len + j
                tr.tokens += new
                tr.last, tr.seen = now, n
        for req in finished:
            tr = self.inflight.pop(req.uid, None)
            if tr is None:
                continue
            tr.finished = now
            if self.spec["loop"] == "closed":
                self.ready.append(tr.client)

    def run(self, on_tick: Optional[Callable[[float], None]] = None):
        """The measured window. ``on_tick(now)`` is called once per loop
        turn (the traced run starts and annotates its trace there)."""
        eng = self.eng
        every = eng.drain_every
        self.t0 = self.clock()
        t_end = self.t0 + self.seconds
        if self.spec["loop"] == "closed":
            self.ready = list(range(int(self.spec["clients"])))
        self.c0 = (eng.decode_calls, eng.prefill_calls)
        while True:
            now = self.clock()
            if now >= t_end:
                break
            if on_tick is not None:
                on_tick(now)
            self._submit_due(now)
            ticks, admitted = eng.decode_calls, eng.admitted
            with TraceAnnotation("engine.step"):
                eng.step()
            if eng.admitted != admitted:
                self._stamp_admitted(self.clock())
            ticked = eng.decode_calls != ticks
            if not ticked or eng.decode_calls % every == 0:
                with TraceAnnotation("engine.drain"):
                    fin = eng.drain()
                self._stamp(fin, self.clock())
            if not ticked and not eng.queue and not self.ready:
                self._wait(t_end)
        self.t1 = self.clock()
        self.c1 = (eng.decode_calls, eng.prefill_calls)

    def _stamp_admitted(self, now: float):
        """Stamp the requests that the last step took off the queue."""
        queued = {id(r) for r in self.eng.queue}
        for tr in self.inflight.values():
            if tr.admitted is None and id(tr.req) not in queued:
                tr.admitted = now

    def _wait(self, t_end: float):
        if self.spec["loop"] != "open":
            return
        nxt = (self.t0 + self.items[self.next_item].due
               if self.next_item < len(self.items) else t_end)
        with TraceAnnotation("bench.wait_arrival"):
            time.sleep(max(0.0, min(nxt, t_end) - self.clock()))

    def record(self) -> dict:
        """The window's numbers, in seconds relative to its start."""
        t0 = self.t0
        reqs = []
        for tr in self.tracks.values():
            reqs.append({
                "uid": tr.req.uid, "due": tr.due - t0,
                "prompt_len": tr.prompt_len, "max_new": tr.req.max_new,
                "admitted": None if tr.admitted is None
                else tr.admitted - t0,
                "first": None if tr.first is None else tr.first - t0,
                "finished": None if tr.finished is None else tr.finished - t0,
                "status": tr.req.status if tr.finished is not None else None,
                "tokens": tr.tokens, "tick_tokens": tr.tick_tokens,
                "context": tr.context, "gaps": tr.gaps})
        return {
            "window_s": self.t1 - t0,
            "requests": reqs,
            "attempted": len(self.tracks) + self.refused,
            "refused": self.refused,
            "queued_at_end": len(self.eng.queue),
            "decode_calls": self.c1[0] - self.c0[0],
            "prefill_calls": self.c1[1] - self.c0[1],
            "submit_lag_s": self.lag,
        }
