"""Crash durability: snapshot/restore parity (dense + hybrid x weight
forms x spec), kill-at-arbitrary-tick recovery from latest snapshot +
write-ahead journal tail (zero accepted requests lost, token-identical at
T=0), journal-only replay onto a fresh engine, torn-tail tolerance,
deterministic resume at temperature > 0 (the sampling RNG key is explicit
serialized state), and loud snapshot/engine compatibility checks.

Weight-only quantization (``act_bits=None``) for the parity assertions —
re-admission after recovery enters batched prefill at a grown length, so
exactness is a weight-only property (the same caveat as preemption and
bucketed admission; see the engine docstring).
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

import _serving_mix as mix
from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8
from repro.models import get_model
from repro.serving.durability import Journal
from repro.serving.engine import ServingEngine
from repro.serving.resilience import FaultPlan, InjectedCrash

W3 = dataclasses.replace(W3A8, act_bits=None)


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    yield
    jax.clear_caches()


ARCH_FOR = {"dense": "qwen2-1.5b", "hybrid": "zamba2-1.2b"}

PROMPTS = [[1, 2, 3], [7, 8, 9, 10, 11], [20, 21, 22, 23], [30, 31],
           [40, 41, 42, 43, 44, 45], [50, 51, 52]]
MAX_NEW = [7, 5, 9, 6, 8, 4]


def _setup(family="dense", form="qp"):
    layers = 4 if family == "hybrid" else 2
    cfg = reduced(get_config(ARCH_FOR[family]), layers=layers, d_model=32,
                  vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    if form == "w":
        return cfg, params, FLOAT
    export = {"q": quant_dense.export_levels,
              "qp": quant_dense.export_container}[form]
    return cfg, export(params, W3), W3


def _engine(params, cfg, policy, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("dtype", jnp.float32)
    return ServingEngine(params, cfg, policy=policy, **kw)


def _submit_all(eng):
    for p, m in zip(PROMPTS, MAX_NEW):
        eng.submit(list(p), max_new=m)


def _outputs(done):
    return {r.uid: (tuple(r.prompt), tuple(r.out)) for r in done}


def _reference(params, cfg, policy, **kw):
    eng = _engine(params, cfg, policy, **kw)
    _submit_all(eng)
    return _outputs(eng.run_all(max_ticks=400))


# --- snapshot / restore parity ----------------------------------------------

@pytest.mark.parametrize("family", ["dense", "hybrid"])
@pytest.mark.parametrize("form", ["w", "qp"])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_snapshot_restore_token_identical(tmp_path, family, form, spec_k):
    """A fresh engine restored from a mid-run snapshot continues
    token-identically at T=0 — across both families, float and packed
    serve forms, plain and speculative ticks."""
    cfg, params, policy = _setup(family, form)
    kw = dict(spec_k=spec_k)
    ref = _reference(params, cfg, policy, **kw)

    eng = _engine(params, cfg, policy, **kw)
    _submit_all(eng)
    for _ in range(4):
        eng.step()
    path = eng.snapshot(str(tmp_path / "snaps"))
    assert os.path.isdir(path)
    mid_done = _outputs(eng.drain())          # finished before the snapshot?
    a = _outputs(eng.run_all(max_ticks=400))

    fresh = _engine(params, cfg, policy, **kw)
    fresh.restore(str(tmp_path / "snaps"))
    assert fresh.decode_calls == 4
    b = _outputs(fresh.run_all(max_ticks=400))

    merged_a = {**mid_done, **a}
    merged_b = {**mid_done, **b}              # snapshot kept undrained work
    assert merged_b == merged_a == ref


def test_restore_midstream_state(tmp_path):
    """The snapshot captures in-flight requests mid-stream: the restored
    engine resumes them from their committed prefix (not from scratch) and
    the remaining budgets/tick bounds carry over."""
    cfg, params, policy = _setup()
    eng = _engine(params, cfg, policy, snapshot_dir=str(tmp_path / "s"))
    _submit_all(eng)
    for _ in range(5):
        eng.step()
    eng.snapshot()
    resident = [r for r in eng._slot_req if r is not None]
    assert resident, "expected in-flight requests at the snapshot"

    fresh = _engine(params, cfg, policy)
    fresh.restore(str(tmp_path / "s"))
    rest = {r.uid: list(r.out) for r in fresh._slot_req if r is not None}
    assert rest == {r.uid: list(r.out) for r in resident}
    assert fresh._ticks_left == eng._ticks_left
    assert fresh._slot_ticks == eng._slot_ticks
    assert fresh._uid == eng._uid


def test_snapshot_compat_checked_loudly(tmp_path):
    """Restoring onto a mismatched engine (different slot count, max_len,
    temperature) raises a ValueError naming the field instead of serving
    from inconsistent state."""
    cfg, params, policy = _setup()
    eng = _engine(params, cfg, policy)
    _submit_all(eng)
    eng.step()
    eng.snapshot(str(tmp_path / "s"))
    for bad_kw, field in ((dict(slots=4), "slots"),
                          (dict(max_len=32), "max_len"),
                          (dict(temperature=0.5), "temperature")):
        other = _engine(params, cfg, policy, **bad_kw)
        with pytest.raises(ValueError, match=field):
            other.restore(str(tmp_path / "s"))


def test_restore_refuses_old_kv_layout(tmp_path):
    """A snapshot whose KV cache is in the old (L, B, S, KV, D) layout —
    same bytes, other shape — is refused, naming the leaf and both shapes,
    never read as the stored (L, B, S, KV*D) layout."""
    from repro import checkpoint
    cfg, params, policy = _setup()
    eng = _engine(params, cfg, policy)
    _submit_all(eng)
    eng.step()
    eng.snapshot(str(tmp_path / "s"))
    dev, meta = checkpoint.restore(str(tmp_path / "s"))
    old = dict(dev["cache"])
    for name in ("k", "v"):
        lyr, b, s, _ = old[name].shape
        old[name] = old[name].reshape(lyr, b, s, cfg.num_kv_heads,
                                      cfg.head_dim)
    checkpoint.save(str(tmp_path / "old"), 1, dict(dev, cache=old),
                    meta=meta)
    fresh = _engine(params, cfg, policy)
    shape = r"\({}, 3, 64, {}, {}\)".format(cfg.num_layers,
                                          cfg.num_kv_heads, cfg.head_dim)
    with pytest.raises(ValueError, match=r"\['k'\].*" + shape):
        fresh.restore(str(tmp_path / "old"))


# --- crash + recovery ---------------------------------------------------------

def _crash_cases():
    # the toy workload on 3 slots with a snapshot every 3 ticks, killed at
    # three ticks (ids kept as the crash tick)
    for crash_at in (1, 4, 9):
        yield pytest.param("toy", "qp", 3, crash_at, id=str(crash_at))
    # 12 mixed-length requests at the reduced serving shape with a snapshot
    # every 8 ticks, killed about mid-run (crash tick chosen in the test)
    for form in ("qp", "w"):
        for slots in (2, 4):
            yield pytest.param("mixed", form, slots, None,
                               id=f"mixed-{form}-{slots}")


@pytest.mark.parametrize("workload,form,slots,crash_at", _crash_cases())
def test_crash_recovery_loses_nothing(tmp_path, workload, form, slots,
                                      crash_at):
    """Kill the engine at an arbitrary tick; recover a FRESH engine from
    the latest snapshot + journal tail. Every accepted request appears in
    the union of pre-crash drains and the recovered run, token-identical
    to an uncrashed run at T=0 — zero accepted-token loss. A crash past
    the first periodic snapshot restores from it."""
    if workload == "toy":
        cfg, params, policy = _setup(form=form)
        work, max_len, every = list(zip(PROMPTS, MAX_NEW)), 64, 3
    else:
        cfg, params, policy = mix.model(form)
        max_new = 12
        work = [(p, max_new) for p in mix.prompts(12)]
        max_len, every = max(mix.LENGTHS) + max_new + 1, 8
        crash_at = max(every + 1, len(work) * max_new // (2 * slots))

    def run(**kw):
        eng = _engine(params, cfg, policy, slots=slots, max_len=max_len,
                      **kw)
        for p, m in work:
            eng.submit(list(p), max_new=m)
        return eng

    ref = _outputs(run().run_all(max_ticks=400))

    snaps, jpath = str(tmp_path / "snaps"), str(tmp_path / "wal.jsonl")
    eng = run(snapshot_dir=snaps, snapshot_every=every, journal=jpath,
              fault_plan=FaultPlan(crash_at_tick=crash_at))
    delivered = {}
    with pytest.raises(InjectedCrash):
        while eng.queue or eng._occupied():
            eng.step()
            delivered.update(_outputs(eng.drain()))

    fresh = _engine(params, cfg, policy, slots=slots, max_len=max_len,
                    snapshot_dir=snaps, journal=jpath)
    stats = fresh.recover()
    assert stats["replayed_events"] >= 0
    if crash_at > every:
        assert stats["restored_step"] is not None, "no snapshot restored"
    recovered = _outputs(fresh.run_all(max_ticks=400))

    merged = {**delivered, **recovered}
    assert set(merged) == set(ref), "an accepted request was lost"
    assert merged == ref, "recovered output differs from the uncrashed run"
    # anything delivered both before the crash and after recovery must
    # agree (at-least-once, never divergent)
    for uid in set(delivered) & set(recovered):
        assert delivered[uid] == recovered[uid]


def test_journal_only_replay(tmp_path):
    """With no snapshot at all, recovery replays the journal from the
    start: every accepted submit is resubmitted (uid preserved) onto the
    fresh engine and completes identically."""
    cfg, params, policy = _setup()
    ref = _reference(params, cfg, policy)
    jpath = str(tmp_path / "wal.jsonl")
    eng = _engine(params, cfg, policy, journal=jpath,
                  fault_plan=FaultPlan(crash_at_tick=2))
    _submit_all(eng)
    with pytest.raises(InjectedCrash):
        eng.run_all(max_ticks=400)

    fresh = _engine(params, cfg, policy, journal=jpath)
    stats = fresh.recover()
    assert stats["restored_step"] is None
    assert stats["resubmitted"] == len(PROMPTS)
    assert fresh._uid == len(PROMPTS)         # uid counter past replayed uids
    assert _outputs(fresh.run_all(max_ticks=400)) == ref


def test_replay_keeps_terminal_requests_dead(tmp_path):
    """Requests the dead engine had already shed stay dead across
    recovery — their terminal outcome was reported once; replay must not
    resurrect them."""
    cfg, params, policy = _setup()
    jpath = str(tmp_path / "wal.jsonl")
    eng = _engine(params, cfg, policy, journal=jpath, queue_limit=2,
                  shed_policy="drop_oldest",
                  fault_plan=FaultPlan(crash_at_tick=1))
    for p, m in zip(PROMPTS, MAX_NEW):       # queue_limit 2 sheds the oldest
        eng.submit(list(p), max_new=m)
    shed_uids = {r.uid for r in eng._finished if r.status == "shed"}
    assert shed_uids
    with pytest.raises(InjectedCrash):
        eng.run_all(max_ticks=400)

    fresh = _engine(params, cfg, policy, journal=jpath)
    fresh.recover()
    replayed = {r.uid for r in fresh.queue}
    assert not (replayed & shed_uids)
    assert fresh.queue                        # the survivors DID come back


def test_journal_torn_tail_tolerated(tmp_path):
    """A crash mid-append leaves a torn final line; Journal.read drops it
    and recovery proceeds on the intact prefix."""
    jpath = str(tmp_path / "wal.jsonl")
    j = Journal(jpath)
    j.append({"e": "submit", "uid": 1, "prompt": [1, 2], "max_new": 4,
              "deadline_at": None})
    j.close()
    with open(jpath, "a") as f:
        f.write('{"e": "submit", "uid": 2, "prom')   # torn write
    events = Journal.read(jpath)
    assert [e["uid"] for e in events] == [1]

    cfg, params, policy = _setup()
    fresh = _engine(params, cfg, policy)
    stats = fresh.recover(journal=jpath)
    assert stats["resubmitted"] == 1
    done = fresh.run_all(max_ticks=100)
    assert [r.uid for r in done] == [1] and done[0].status == "ok"


def test_periodic_snapshots_and_counters(tmp_path):
    """snapshot_every lands snapshots on tick boundaries with keep-k GC;
    the durability counters ride the watchdog diagnostics."""
    from repro import checkpoint
    cfg, params, policy = _setup()
    snaps = str(tmp_path / "snaps")
    eng = _engine(params, cfg, policy, snapshot_dir=snaps, snapshot_every=2,
                  journal=str(tmp_path / "wal.jsonl"))
    _submit_all(eng)
    eng.run_all(max_ticks=400)
    assert eng.snapshots_written >= 3
    assert checkpoint.latest_step(snaps) is not None
    assert len(checkpoint.all_steps(snaps)) <= 3          # keep-k GC
    assert eng.journal_events > 0
    d = eng._diagnostics()
    for k in ("snapshots_written", "journal_events", "replayed_events",
              "integrity_probes", "heal_count"):
        assert k in d
    # the journal is a valid event stream with snapshot markers
    events = Journal.read(str(tmp_path / "wal.jsonl"))
    kinds = {e["e"] for e in events}
    assert {"submit", "admit", "commit", "finish", "snapshot"} <= kinds


# --- deterministic resume (explicit RNG state) --------------------------------

def test_restore_is_reproducible_at_temperature(tmp_path):
    """The sampling key is explicit serialized state: two fresh engines
    restored from the same mid-run snapshot produce IDENTICAL streams even
    at temperature > 0 — and identical to the donor engine continuing."""
    cfg, params, policy = _setup()
    kw = dict(temperature=0.8, seed=7)
    eng = _engine(params, cfg, policy, **kw)
    _submit_all(eng)
    for _ in range(4):
        eng.step()
    eng.snapshot(str(tmp_path / "s"))
    mid = _outputs(eng.drain())
    donor = {**mid, **_outputs(eng.run_all(max_ticks=400))}

    restored = []
    for _ in range(2):
        fresh = _engine(params, cfg, policy, **kw)
        fresh.restore(str(tmp_path / "s"))
        restored.append({**mid, **_outputs(fresh.run_all(max_ticks=400))})
    assert restored[0] == restored[1] == donor


def test_submit_is_write_ahead(tmp_path):
    """The journal line for a submit is durable BEFORE the queue sees the
    request — a crash immediately after submit() can always replay it."""
    cfg, params, policy = _setup()
    jpath = str(tmp_path / "wal.jsonl")
    eng = _engine(params, cfg, policy, journal=jpath)
    eng.submit([1, 2, 3], max_new=4, deadline_ticks=50)
    with open(jpath) as f:
        ev = json.loads(f.readline())
    assert ev["e"] == "submit" and ev["uid"] == 1
    assert ev["prompt"] == [1, 2, 3] and ev["max_new"] == 4
    assert ev["deadline_at"] == 50
