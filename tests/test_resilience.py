"""Overload-hardened serving: bounded admission (reject/drop_oldest),
machine-readable submit rejections, per-request deadlines (mid-stream and
in-queue), fair-share slot preemption with token-exact requeue (dense +
hybrid x weight forms x spec), NaN-logit quarantine, the degradation
ladder (spec -> plain, kernel -> fallback), deterministic fault injection
recovery for every fault class, and the run_all watchdog.

Weight-only quantization (``act_bits=None``) for every parity assertion:
per-row dynamic activation scales differ between a request's original
admission and its re-admission at a grown (prompt + committed) length, so
exact preemption parity — like bucketed-admission parity — is a
weight-only property (see the engine docstring's moe/act-quant caveat).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _serving_mix as mix
from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8
from repro.models import get_model
from repro.serving.engine import ServingEngine, generate
from repro.serving.resilience import (FaultPlan, SubmitRejected,
                                      WatchdogExpired)

W3 = dataclasses.replace(W3A8, act_bits=None)


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    """This module compiles hundreds of engine graphs (the preemption
    matrix alone builds 8 engines x requeue buckets x solo refs); release
    the compiled executables when it finishes so the whole-suite process
    doesn't exhaust JIT code memory in later modules."""
    yield
    jax.clear_caches()

ARCH_FOR = {"dense": "qwen2-1.5b", "hybrid": "zamba2-1.2b"}

# distinct prompts spanning both small admission buckets, so requeue after
# preemption crosses bucket boundaries as the effective prompt grows
PROMPTS = [
    [1, 2, 3],
    [7, 8, 9, 10, 11],
    [20, 21, 22, 23, 24, 25, 26, 27, 28],
    [30, 31, 32, 33],
]


def _setup(family="dense", form="w"):
    layers = 4 if family == "hybrid" else 2
    cfg = reduced(get_config(ARCH_FOR[family]), layers=layers, d_model=32,
                  vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    if form == "w":
        return cfg, params, FLOAT
    export = {"q": quant_dense.export_levels,
              "qp": quant_dense.export_container}[form]
    return cfg, export(params, W3), W3


def _ref(params, cfg, policy, prompt, max_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                   policy=policy, max_new_tokens=max_new, dtype=jnp.float32)
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


# --- bounded admission -------------------------------------------------------

def test_submit_rejected_reason_codes():
    """Every submit() validation failure is a SubmitRejected with a
    machine-readable reason — and still a ValueError, so legacy callers
    keep working. The engine stays usable after each rejection."""
    cfg, params, policy = _setup()
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=16,
                        dtype=jnp.float32)
    cases = [
        (dict(prompt=[], max_new=4), "empty_prompt"),
        (dict(prompt=[1, 2], max_new=0), "bad_max_new"),
        (dict(prompt=list(range(1, 20)), max_new=4), "too_long"),
        (dict(prompt=[1, 2], max_new=4, deadline_ticks=0), "bad_deadline"),
    ]
    for kw, reason in cases:
        with pytest.raises(SubmitRejected) as ei:
            eng.submit(**kw)
        assert ei.value.reason == reason
        assert isinstance(ei.value, ValueError)
    assert eng.queue == []                    # nothing half-enqueued
    eng.submit([1, 2], max_new=3)
    done = eng.run_all()
    assert len(done) == 1 and done[0].status == "ok"


def test_bounded_admission_reject():
    """queue_limit with the reject policy: excess submissions return a
    falsy SubmitOutcome with reason 'queue_full' instead of growing the
    queue; accepted requests are unaffected and complete."""
    cfg, params, policy = _setup()
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=16,
                        dtype=jnp.float32, queue_limit=2)
    outs = [eng.submit([1, 2, 3], max_new=3) for _ in range(4)]
    assert [bool(o) for o in outs] == [True, True, False, False]
    assert outs[0].accepted and outs[0].uid == 1 and outs[0].reason is None
    assert not outs[2].accepted and outs[2].uid is None
    assert outs[2].reason == "queue_full"
    assert eng.shed_count == 2 and eng.queue_depth == 2
    done = eng.run_all()
    assert len(done) == 2 and all(r.status == "ok" for r in done)
    # the outcome IS the uid for accepted requests (legacy dict-key use)
    assert sorted(r.uid for r in done) == [int(outs[0]), int(outs[1])]


def test_bounded_admission_drop_oldest():
    """drop_oldest: the new request is admitted, the oldest QUEUED request
    is evicted — reported in the outcome's shed tuple and drained with
    status 'shed' and no output."""
    cfg, params, policy = _setup()
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=16,
                        dtype=jnp.float32, queue_limit=1,
                        shed_policy="drop_oldest")
    u1 = eng.submit([1, 2, 3], max_new=3)     # queued
    u2 = eng.submit([4, 5, 6], max_new=3)     # evicts u1
    assert u2.accepted and u2.shed == (int(u1),)
    assert eng.shed_count == 1 and eng.queue_depth == 1
    done = eng.run_all()
    by_uid = {r.uid: r for r in done}
    assert by_uid[int(u1)].status == "shed" and by_uid[int(u1)].out == []
    assert by_uid[int(u2)].status == "ok" and len(by_uid[int(u2)].out) == 3


# --- deadlines ---------------------------------------------------------------

def test_deadline_cancels_midstream():
    """A resident request past its deadline is cancelled mid-stream: the
    slot frees (the next request gets it), partial output is returned with
    status 'deadline'."""
    cfg, params, policy = _setup()
    ref = _ref(params, cfg, policy, [1, 2, 3], 12)
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=32,
                        dtype=jnp.float32)
    u1 = eng.submit([1, 2, 3], max_new=12, deadline_ticks=4)
    u2 = eng.submit([4, 5, 6], max_new=3)
    done = eng.run_all()
    by_uid = {r.uid: r for r in done}
    hit = by_uid[int(u1)]
    assert hit.status == "deadline"
    assert 0 < len(hit.out) < 12
    assert hit.out == ref[:len(hit.out)]      # partial stream, not garbage
    assert by_uid[int(u2)].status == "ok" and len(by_uid[int(u2)].out) == 3
    assert eng.deadline_miss_count == 1


def test_deadline_expires_in_queue():
    """default_deadline applies to every request; one stuck behind a long
    resident request expires WHILE QUEUED (never holds a slot) and drains
    with empty output."""
    cfg, params, policy = _setup()
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=32,
                        dtype=jnp.float32, default_deadline=2)
    u1 = eng.submit([1, 2, 3], max_new=6, deadline_ticks=50)  # long resident
    u2 = eng.submit([4, 5, 6], max_new=3)     # default deadline, queued
    done = eng.run_all()
    by_uid = {r.uid: r for r in done}
    assert by_uid[int(u1)].status == "ok"
    assert by_uid[int(u2)].status == "deadline"
    assert by_uid[int(u2)].out == [] and by_uid[int(u2)].ticks == 0
    assert eng.deadline_miss_count == 1


# --- preemption / requeue parity ---------------------------------------------

@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("form", ["w", "qp"])
@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_preemption_parity(family, form, spec_k):
    """Forced preemption of EVERY request (fair-share budget of one tick
    while waiters exist), staggered admission, and drain() interleaved at
    every step: each requeued request's final stream is token-identical to
    its solo ``generate`` run — nothing lost, nothing duplicated across
    preempt/requeue/drain boundaries. Composes with speculative decoding
    (spec_k=2) and both weight forms."""
    cfg, params, policy = _setup(family, form)
    refs = {tuple(p): _ref(params, cfg, policy, p, 10) for p in PROMPTS}
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, preempt_after=1,
                        spec_k=spec_k, max_ticks=200)
    uid_to_prompt = {}
    for p in PROMPTS[:2]:                     # first wave fills both slots
        uid_to_prompt[eng.submit(p, max_new=10)] = tuple(p)
    eng.step()
    for p in PROMPTS[2:]:                     # waiters force preemption
        uid_to_prompt[eng.submit(p, max_new=10)] = tuple(p)
    done = []
    for _ in range(200):                      # drain at EVERY step boundary
        if not (eng.queue or eng._occupied()):
            break
        eng.step()
        done.extend(eng.drain())
    done.extend(eng.drain())
    assert len(done) == len(PROMPTS) and all(r.status == "ok" for r in done)
    for r in done:
        assert r.out == refs[uid_to_prompt[r.uid]], \
            (family, form, spec_k, uid_to_prompt[r.uid], r.out)
    # every request was actually preempted at least once — the parity
    # claim is about the requeue path, so it must have been exercised
    assert all(r.preemptions >= 1 for r in done), \
        [(r.uid, r.preemptions) for r in done]
    assert eng.preempt_count == sum(r.preemptions for r in done)


def test_preemption_with_early_eos():
    """EOS mid-stream while preemption churns: truncation lands exactly
    where the solo run's does, and freed-by-EOS slots are reobserved (the
    _sync-in-_spin_up path) rather than deadlocking the queue."""
    cfg, params, policy = _setup()
    full = _ref(params, cfg, policy, PROMPTS[0], 8)
    idx = next(i for i in range(1, len(full)) if full[i] not in full[:i])
    eos = full[idx]
    refs = {tuple(p): None for p in PROMPTS}
    for p in PROMPTS:
        r = _ref(params, cfg, policy, p, 8)
        refs[tuple(p)] = r[:r.index(eos) + 1] if eos in r else r
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, preempt_after=1, eos_id=eos,
                        max_ticks=200)
    uid_to_prompt = {eng.submit(p, max_new=8): tuple(p) for p in PROMPTS}
    done = eng.run_all()
    assert len(done) == len(PROMPTS)
    for r in done:
        assert r.out == refs[uid_to_prompt[r.uid]], \
            (uid_to_prompt[r.uid], r.out, refs[uid_to_prompt[r.uid]])


# --- health quarantine + degradation ladder ----------------------------------

@pytest.mark.parametrize("spec_k", [0, 2])
def test_nan_quarantine(spec_k):
    """An injected NaN in one slot's logits quarantines THAT request
    (status 'poisoned', partial prefix output, slot zeroed and reusable)
    while its neighbor finishes token-exact — in both tick modes."""
    cfg, params, policy = _setup()
    ref = _ref(params, cfg, policy, [4, 5, 6], 6)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, spec_k=spec_k,
                        fault_plan=FaultPlan(nan_logits=[(1, 0)]))
    u_bad = eng.submit([1, 2, 3], max_new=6)   # lands in slot 0
    u_ok = eng.submit([4, 5, 6], max_new=6)
    done = eng.run_all()
    by_uid = {r.uid: r for r in done}
    bad, ok = by_uid[int(u_bad)], by_uid[int(u_ok)]
    assert bad.status == "poisoned" and len(bad.out) < 6
    assert ok.status == "ok" and ok.out == ref
    assert eng.poisoned_count == 1
    # the quarantined slot was zeroed: a new request reuses it cleanly
    u3 = eng.submit([4, 5, 6], max_new=6)
    done2 = eng.run_all()
    assert len(done2) == 1 and done2[0].uid == int(u3)
    assert done2[0].status == "ok" and done2[0].out == ref


def test_tick_failure_degrades_spec_to_plain():
    """An injected tick failure on a speculative engine walks the first
    ladder step — the drafter is abandoned mid-run, the plain tick takes
    over, and the output stream is unaffected (spec is exact)."""
    cfg, params, policy = _setup()
    ref = _ref(params, cfg, policy, [1, 2, 3], 7)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, spec_k=2,
                        fault_plan=FaultPlan(fail_ticks=[1]))
    eng.submit([1, 2, 3], max_new=7)
    done = eng.run_all()
    assert done[0].status == "ok" and done[0].out == ref
    assert (1, "spec->plain") in eng.fallback_events
    assert not eng._spec and eng.spec_k == 0


def test_tick_failure_degrades_kernel_to_fallback():
    """On a non-speculative engine the ladder's second step rebuilds the
    dequant/ref graphs; the run completes token-exact with the event
    recorded."""
    cfg, params, policy = _setup(form="qp")
    ref = _ref(params, cfg, policy, [1, 2, 3], 6)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32,
                        fault_plan=FaultPlan(fail_ticks=[1]))
    eng.submit([1, 2, 3], max_new=6)
    done = eng.run_all()
    assert done[0].status == "ok" and done[0].out == ref
    assert (1, "kernel->fallback") in eng.fallback_events
    assert eng.matmul_mode == "dequant" and eng.attn_mode == "ref"


def test_tick_failure_transient_retry_without_degrade():
    """degrade=False: an injected (one-shot, i.e. transient) fault earns a
    same-graph retry instead of a ladder step; the retry succeeds and the
    run is token-exact."""
    cfg, params, policy = _setup()
    ref = _ref(params, cfg, policy, [1, 2, 3], 5)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, degrade=False,
                        fault_plan=FaultPlan(fail_ticks=[0, 2]))
    eng.submit([1, 2, 3], max_new=5)
    done = eng.run_all()
    assert done[0].status == "ok" and done[0].out == ref
    assert eng.fallback_events == [(0, "retry"), (2, "retry")]


def test_admission_delay_recovery():
    """Injected admission stalls defer the queued request without touching
    the resident one; admission resumes after the stall window and every
    request completes normally."""
    cfg, params, policy = _setup()
    ref1 = _ref(params, cfg, policy, [1, 2, 3], 4)
    ref2 = _ref(params, cfg, policy, [4, 5, 6], 4)
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=32,
                        dtype=jnp.float32, max_ticks=100,
                        fault_plan=FaultPlan(delay_admission=[1, 2]))
    u1 = eng.submit([1, 2, 3], max_new=4)
    u2 = eng.submit([4, 5, 6], max_new=4)
    done = eng.run_all()
    by_uid = {r.uid: r for r in done}
    assert by_uid[int(u1)].out == ref1 and by_uid[int(u2)].out == ref2
    assert all(r.status == "ok" for r in done)


# --- watchdog ----------------------------------------------------------------

def test_watchdog_raises_with_diagnostics():
    """A wedged engine (admission stalled forever) trips the run_all
    watchdog: WatchdogExpired carries a diagnostic dump naming the stuck
    queue, and work finished BEFORE the wedge stays drainable."""
    cfg, params, policy = _setup()
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=32,
                        dtype=jnp.float32,
                        fault_plan=FaultPlan(delay_admission=range(2, 10_000)))
    u1 = eng.submit([1, 2, 3], max_new=3)     # admitted at tick 0, finishes
    eng.submit([4, 5, 6], max_new=3)          # stuck behind the stall
    with pytest.raises(WatchdogExpired) as ei:
        eng.run_all(max_ticks=12)
    diag = ei.value.diagnostics
    assert diag["queue_depth"] == 1 and not diag["active_slots"]
    assert "shed_count" in diag and "fallback_events" in diag
    drained = eng.drain()
    assert [r.uid for r in drained] == [int(u1)]
    assert drained[0].status == "ok" and len(drained[0].out) == 3


def test_watchdog_constructor_default():
    """max_ticks set at construction applies to every run_all (the serve
    launcher path)."""
    cfg, params, policy = _setup()
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=32,
                        dtype=jnp.float32, max_ticks=2,
                        fault_plan=FaultPlan(delay_admission=range(10_000)))
    eng.submit([1, 2, 3], max_new=3)
    with pytest.raises(WatchdogExpired):
        eng.run_all()


# --- fault-plan determinism + chaos smoke ------------------------------------

def test_fault_plan_random_deterministic():
    """Same seed -> identical plan (the CI chaos generator must be
    reproducible); plans are immutable value objects."""
    a = FaultPlan.random(7, ticks=200, slots=4)
    b = FaultPlan.random(7, ticks=200, slots=4)
    assert a == b and not a.empty
    assert a != FaultPlan.random(8, ticks=200, slots=4)
    assert FaultPlan().empty
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.fail_ticks = frozenset()


def test_chaos_smoke_completes():
    """Seeded chaos (NaNs + tick failures + admission stalls) over an
    overloaded engine with deadlines and preemption: the run always
    terminates under the watchdog and every submitted request drains with
    a terminal status."""
    cfg, params, policy = _setup()
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, queue_limit=4,
                        shed_policy="drop_oldest", default_deadline=30,
                        preempt_after=2, spec_k=2, max_ticks=300,
                        fault_plan=FaultPlan.random(3, ticks=60, slots=2))
    outs = [eng.submit(PROMPTS[i % len(PROMPTS)], max_new=6)
            for i in range(8)]
    done = eng.run_all()
    assert len(done) == sum(1 for o in outs if o.accepted)
    assert all(r.status in ("ok", "deadline", "shed", "poisoned")
               for r in done)
    assert any(r.status == "ok" for r in done)


# --- overload at the reduced serving shape -----------------------------------

@pytest.mark.parametrize("slots", [2, 4])
@pytest.mark.parametrize("form", ["qp", "w"])
def test_overload_never_deadlocks(form, slots):
    """Waves of 2 x slots mixed-length requests every 4 ticks, about twice
    what the slots serve, into a queue bounded at 2 x slots that rejects
    the rest; deadlines cycle none / loose / tight and fair-share
    preemption is on. The run drains without the watchdog firing (run_all
    raises WatchdogExpired), sheds less than everything and finishes some
    requests."""
    cfg, params, policy = mix.model(form)
    max_new, wave = 12, 2 * slots
    eng = ServingEngine(params, cfg, policy=policy, slots=slots,
                        max_len=max(mix.LENGTHS) + max_new + 1,
                        dtype=jnp.float32, queue_limit=wave,
                        shed_policy="reject",
                        preempt_after=max(2, max_new // 4), max_ticks=4096)
    deadlines = [None, 4 * max_new, max_new // 2]
    prompts = mix.prompts(24)
    outcomes, done = [], []
    for i in range(0, len(prompts), wave):
        for j, p in enumerate(prompts[i:i + wave]):
            outcomes.append(eng.submit(
                p, max_new=max_new,
                deadline_ticks=deadlines[(i + j) % len(deadlines)]))
        for _ in range(4):
            eng.step()
        done.extend(eng.drain())
    done.extend(eng.run_all())
    assert eng.shed_count < len(outcomes)
    assert len(done) == sum(1 for o in outcomes if o.accepted)
    assert any(r.status == "ok" for r in done)
