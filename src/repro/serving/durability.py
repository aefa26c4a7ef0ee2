"""Crash durability for the serving engine: snapshots + write-ahead journal.

The paper's deployment premise — the whole quantized model resident in
on-chip memory for the life of the service — makes a process death
expensive: the packed weight image, the slot-major KV/SSM state, and every
in-flight request die with it. This module makes that loss bounded and
recoverable with two cooperating mechanisms:

  * **Snapshots** — :func:`snapshot_engine` captures the COMPLETE engine
    state at a tick boundary: the device trees (shared cache, drafter
    cache, per-slot token/active/emitted/budget vectors, the sampling RNG
    key) via :func:`repro.models.api.cache_to_host`, plus the host
    bookkeeping (queue / resident / finished requests, per-slot tick
    budgets, every counter, the degradation-ladder mode the engine was
    running in). Persistence rides :func:`repro.checkpoint.save` — atomic
    tmp+rename step dirs keyed by ``decode_calls``, keep-k GC — so a crash
    mid-snapshot never leaves a half-written restore point. The engine
    syncs its async pending buffer first, so a snapshot is always at a
    consistent "everything attributed" boundary, and restoring it resumes
    the token stream exactly where it left off (token-identical at T=0:
    decode is deterministic given cache + RNG key, both captured).
  * **Write-ahead journal** — :class:`Journal`, an append-only JSONL log
    of ``submit`` / ``admit`` / ``commit`` / ``finish`` / ``shed`` events
    (flushed per event; a torn final line from a mid-write crash is
    detected and dropped on read). Replay does NOT try to reconstruct
    device state from events — it restores the latest snapshot and then
    RESUBMITS the journal tail's accepted submits (uid and deadline
    preserved). Determinism does the rest: a resubmitted request
    recomputes the exact tokens the dead process would have produced
    (T=0; same weight-only-quant row-independence argument as
    preemption), so recovery is at-least-once delivery with zero accepted
    tokens lost. Requests the dead process had already shed, expired, or
    quarantined stay dead (their terminal outcome was already reported).

:func:`recover` glues the two together: restore the newest snapshot (if
any), find the last ``snapshot`` marker for that step in the journal, and
resubmit the accepted-but-not-terminal submits recorded after it. A
journal with no snapshot replays from the beginning onto a fresh engine.

The at-risk window is what was DRAINED to the caller between the last
snapshot and the crash: those requests are gone from the engine and are
simply recomputed and re-delivered (at-least-once). Nothing accepted is
ever silently lost — the acceptance test in tests/test_durability.py
crashes ``run_all`` at arbitrary ticks and checks the union of pre-crash
drains and post-recovery output against an uncrashed run.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.models import api as model_api
from repro.serving.engine import COUNTERS

__all__ = ["Journal", "snapshot_engine", "restore_engine", "recover"]

FORMAT = 1

# terminal Request.status values that stay dead across recovery: their
# outcome was already reported to the caller, so replay must not resurrect
# them ("ok" finishes ARE recomputed — at-least-once delivery)
_DEAD_STATUS = ("shed", "deadline", "poisoned")


class Journal:
    """Append-only JSONL write-ahead log. One JSON object per line,
    flushed per event, opened in append mode so a recovered engine keeps
    extending the same history. ``fsync=True`` additionally fsyncs every
    append (durable against power loss, not just process death)."""

    def __init__(self, path: str, *, fsync: bool = False):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "a")
        self._fsync = fsync

    def append(self, event: Dict[str, Any]):
        self._f.write(json.dumps(event, separators=(",", ":")) + "\n")
        self._f.flush()
        if self._fsync:
            os.fsync(self._f.fileno())

    def close(self):
        if not self._f.closed:
            self._f.close()

    @staticmethod
    def read(path: str) -> List[Dict[str, Any]]:
        """Events in order. A torn final line (crash mid-append) is
        dropped; a torn line ANYWHERE truncates the replay there — events
        after a corruption can't be trusted to be ordered."""
        events: List[Dict[str, Any]] = []
        if not os.path.exists(path):
            return events
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    break
        return events


# --- Request (de)serialization ------------------------------------------------

def _req_to_state(r) -> Dict[str, Any]:
    return {"uid": r.uid, "prompt": list(r.prompt), "max_new": r.max_new,
            "out": list(r.out), "done": r.done, "ticks": r.ticks,
            "accept_hist": {int(k): int(v) for k, v in r.accept_hist.items()},
            "status": r.status, "deadline_at": r.deadline_at,
            "preemptions": r.preemptions, "submit_time": r.submit_time,
            "finish_time": r.finish_time}


def _req_from_state(d: Dict[str, Any]):
    from repro.serving.engine import Request
    return Request(
        uid=int(d["uid"]), prompt=[int(t) for t in d["prompt"]],
        max_new=int(d["max_new"]), out=[int(t) for t in d["out"]],
        done=bool(d["done"]), ticks=int(d["ticks"]),
        # JSON stringifies int keys; undo that on the way back
        accept_hist={int(k): int(v) for k, v in d["accept_hist"].items()},
        status=str(d["status"]),
        deadline_at=None if d["deadline_at"] is None else int(d["deadline_at"]),
        preemptions=int(d["preemptions"]),
        submit_time=float(d["submit_time"]),
        finish_time=float(d["finish_time"]))


# --- snapshot / restore -------------------------------------------------------

def snapshot_engine(eng, snapshot_dir: str, *, keep: int = 3) -> str:
    """Persist the engine's complete state under ``snapshot_dir`` (one
    atomic ``step_<decode_calls>`` dir; ``keep`` newest retained). Syncs
    the async pending buffer first so every emitted token is attributed —
    the snapshot is a consistent tick boundary. Returns the path and logs
    a ``snapshot`` marker to the journal (the replay cut point)."""
    from repro import checkpoint
    eng._sync()
    dev: Dict[str, Any] = {
        "cache": model_api.cache_to_host(eng.cfg, eng.cache),
        "tokens": eng._tokens, "active": eng._active,
        "emitted": eng._emitted, "budget": eng._budget,
        # the ONLY sampling randomness in the engine: every tick/admission
        # splits from this key host-side, so capturing it makes a restored
        # run reproducible at any temperature
        "rng_key": eng._key,
    }
    if eng._spec:
        dev["draft_cache"] = model_api.cache_to_host(eng.draft_cfg,
                                                     eng.draft_cache)
    state = {
        "format": FORMAT,
        "compat": {
            "cfg": eng.cfg.name, "family": eng.cfg.family,
            "slots": eng.slots, "max_len": eng.max_len,
            "kv_bits": eng.kv_bits, "temperature": eng.temperature,
            "eos_id": eng.eos_id, "dtype": str(np.dtype(eng.dtype)),
        },
        "modes": {"spec": eng._spec, "was_spec": eng._was_spec,
                  "spec_k": eng.spec_k, "matmul_mode": eng.matmul_mode,
                  "attn_mode": eng.attn_mode},
        "queue": [_req_to_state(r) for r in eng.queue],
        "slots": [None if r is None else _req_to_state(r)
                  for r in eng._slot_req],
        "finished": [_req_to_state(r) for r in eng._finished],
        "ticks_left": [int(x) for x in eng._ticks_left],
        "slot_ticks": [int(x) for x in eng._slot_ticks],
        "uid": eng._uid,
        # every engine counter, restored verbatim: a recovered engine
        # reports the totals the dead one had accumulated
        "counters": eng.counters(),
        "fallback_events": [[int(t), str(lbl)]
                            for t, lbl in eng.fallback_events],
    }
    path = checkpoint.save(snapshot_dir, eng.decode_calls, dev,
                           meta={"serving_state": state}, keep=keep)
    eng.snapshots_written += 1
    eng._last_snapshot_tick = eng.decode_calls
    eng._log_event({"e": "snapshot", "step": eng.decode_calls, "path": path})
    return path


def _check_compat(eng, compat: Dict[str, Any]):
    mine = {"cfg": eng.cfg.name, "family": eng.cfg.family,
            "slots": eng.slots, "max_len": eng.max_len,
            "kv_bits": eng.kv_bits, "temperature": eng.temperature,
            "eos_id": eng.eos_id, "dtype": str(np.dtype(eng.dtype))}
    bad = [f"{k}: snapshot {compat[k]!r} != engine {mine[k]!r}"
           for k in mine if compat.get(k) != mine[k]]
    if bad:
        raise ValueError("snapshot is incompatible with this engine — "
                         + "; ".join(bad))


def _apply_modes(eng, modes: Dict[str, Any]):
    """Put the engine in the mode the snapshot was taken in. A pre-crash
    degradation (spec dropped, kernels swapped for fallback graphs) is
    part of the state: replaying it keeps the restored token stream
    identical to the dead engine's."""
    from repro.serving import engine as engine_mod
    if modes["spec"] and not eng._spec:
        raise ValueError(
            "snapshot was taken in speculative mode but this engine was "
            "built with spec_k=0 — construct it with the original spec_k")
    if modes["spec"] and modes["spec_k"] != eng.spec_k:
        raise ValueError(f"snapshot spec_k {modes['spec_k']} != engine "
                         f"spec_k {eng.spec_k}")
    if not modes["spec"] and eng._spec:
        eng._disable_spec()                  # the dead engine had degraded
    eng._was_spec = bool(modes["was_spec"])
    if (modes["matmul_mode"] != eng.matmul_mode
            or modes["attn_mode"] != eng.attn_mode):
        eng.matmul_mode = modes["matmul_mode"]
        eng.attn_mode = modes["attn_mode"]
        eng._attn_kw = engine_mod._attn_kwargs(eng.cfg, eng.attn_mode,
                                               eng.kv_bits)
        if eng._spec:
            eng._dattn_kw = engine_mod._attn_kwargs(eng.draft_cfg,
                                                    eng.attn_mode,
                                                    eng.kv_bits)
        eng._build_jits()


def restore_engine(eng, snapshot_dir: str,
                   step: Optional[int] = None) -> Dict[str, Any]:
    """Load a snapshot into ``eng`` (a freshly constructed engine with the
    same params/config). Validates compatibility loudly, replays the
    snapshot's degradation mode, and swaps in the device trees via
    :func:`repro.models.api.cache_from_host` (structure/shape/dtype
    checked against the live cache). Returns the snapshot's host state."""
    from repro import checkpoint
    dev, meta = checkpoint.restore(snapshot_dir, step)
    state = meta["serving_state"]
    if state.get("format") != FORMAT:
        raise ValueError(f"unknown snapshot format {state.get('format')!r}")
    _check_compat(eng, state["compat"])
    _apply_modes(eng, state["modes"])
    eng.cache = model_api.cache_from_host(eng.cfg, dev["cache"],
                                          like=eng.cache)
    if eng._spec:
        if "draft_cache" not in dev:
            raise ValueError("speculative engine but snapshot carries no "
                             "draft cache")
        eng.draft_cache = model_api.cache_from_host(
            eng.draft_cfg, dev["draft_cache"], like=eng.draft_cache)
    eng._tokens = jnp.asarray(np.asarray(dev["tokens"], np.int32))
    eng._active = jnp.asarray(np.asarray(dev["active"], bool))
    eng._emitted = jnp.asarray(np.asarray(dev["emitted"], np.int32))
    eng._budget = jnp.asarray(np.asarray(dev["budget"], np.int32))
    eng._key = jnp.asarray(np.asarray(dev["rng_key"], np.uint32))
    eng.queue = [_req_from_state(d) for d in state["queue"]]
    eng._slot_req = [None if d is None else _req_from_state(d)
                     for d in state["slots"]]
    eng._finished = [_req_from_state(d) for d in state["finished"]]
    eng._ticks_left = [int(x) for x in state["ticks_left"]]
    eng._slot_ticks = [int(x) for x in state["slot_ticks"]]
    eng._pending = []
    eng._uid = int(state["uid"])
    for k in COUNTERS:              # one added since the snapshot: 0
        setattr(eng, k, int(state["counters"].get(k, 0)))
    eng.fallback_events = [(int(t), str(lbl))
                           for t, lbl in state["fallback_events"]]
    # a restored engine must not immediately re-snapshot the same tick
    eng._last_snapshot_tick = eng.decode_calls
    return state


# --- journal replay -----------------------------------------------------------

def recover(eng, *, snapshot_dir: Optional[str] = None,
            journal: Optional[str] = None) -> Dict[str, Any]:
    """Full recovery onto a freshly constructed engine: restore the newest
    snapshot under ``snapshot_dir`` (if any), then replay the journal tail
    — every accepted submit recorded after that snapshot's marker whose
    request is neither already baked into the snapshot nor terminally dead
    (shed/deadline/poisoned) is resubmitted with its original uid and
    deadline. Returns ``{"restored_step", "replayed_events",
    "resubmitted"}``. ``run_all()`` afterwards completes every recovered
    request; at T=0 the recomputed tokens are identical to what the dead
    engine would have produced."""
    import time as _time
    from repro import checkpoint
    from repro.serving.engine import Request
    stats = {"restored_step": None, "replayed_events": 0, "resubmitted": 0}
    step = None
    if snapshot_dir is not None:
        step = checkpoint.latest_step(snapshot_dir)
        if step is not None:
            restore_engine(eng, snapshot_dir, step)
            stats["restored_step"] = step
    if journal is None:
        return stats
    events = Journal.read(journal)
    start = 0
    if step is not None:
        for i, ev in enumerate(events):
            if ev.get("e") == "snapshot" and ev.get("step") == step:
                start = i + 1                # LAST marker for that step wins
    tail = events[start:]
    stats["replayed_events"] = len(tail)
    known = ({r.uid for r in eng.queue}
             | {r.uid for r in eng._slot_req if r is not None}
             | {r.uid for r in eng._finished})
    submits: Dict[int, Dict[str, Any]] = {}
    dead: set = set()
    order: List[int] = []
    for ev in tail:
        kind = ev.get("e")
        uid = ev.get("uid")
        if kind == "submit" and uid is not None:
            submits[uid] = ev
            order.append(uid)
        elif kind == "shed" and uid is not None:
            dead.add(uid)
        elif kind == "finish" and ev.get("status") in _DEAD_STATUS:
            dead.add(uid)
    for uid in order:
        if uid in dead or uid in known:
            continue
        ev = submits[uid]
        req = Request(uid=int(uid), prompt=[int(t) for t in ev["prompt"]],
                      max_new=int(ev["max_new"]),
                      deadline_at=(None if ev.get("deadline_at") is None
                                   else int(ev["deadline_at"])),
                      submit_time=_time.perf_counter())
        eng.queue.append(req)
        stats["resubmitted"] += 1
    if submits:
        eng._uid = max(eng._uid, max(submits))
    eng.queue_peak = max(eng.queue_peak, len(eng.queue))
    eng.replayed_events += stats["replayed_events"]
    return stats
