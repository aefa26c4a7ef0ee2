"""Speculative serving through the batched engine: T=0 token parity with
the non-speculative path for every rollback-capable family x weight form
under staggered admission, the one-jitted-call tick contract (trace-count
and jaxpr asserted — no per-draft-token host sync), budget/EOS exactness
with variable tokens per tick, per-request stats, and the loud rejections
(ssm, ring wrap, missing headroom)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _serving_mix as mix
from repro.analysis import check_no_host_callback, retrace_report
from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8
from repro.models import api as model_api
from repro.models import get_model
from repro.serving.engine import ServingEngine, generate

W3 = dataclasses.replace(W3A8, act_bits=None)

ARCH_FOR = {"dense": "qwen2-1.5b", "ssm": "mamba2-2.7b",
            "hybrid": "zamba2-1.2b"}
PROMPT = [1, 2, 3, 4]


def _setup(family, form="w"):
    layers = 4 if family == "hybrid" else 2
    cfg = reduced(get_config(ARCH_FOR[family]), layers=layers, d_model=32,
                  vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    if form == "w":
        return cfg, params, FLOAT
    export = {"q": quant_dense.export_levels,
              "qp": quant_dense.export_container}[form]
    return cfg, export(params, W3), W3


def _ref_tokens(params, cfg, policy, max_new, **kw):
    out = generate(params, jnp.asarray([PROMPT], jnp.int32), cfg,
                   policy=policy, max_new_tokens=max_new, dtype=jnp.float32,
                   **kw)
    return [int(t) for t in np.asarray(out[0, len(PROMPT):])]


@pytest.mark.parametrize("family", ["dense", "hybrid"])
@pytest.mark.parametrize("form", ["w", "q", "qp"])
def test_spec_parity_greedy_staggered(family, form):
    """Spec engine output == NON-spec greedy output, with a request
    admitted mid-decode next to busy slots — rollback and per-slot
    acceptance must stay row-independent. The drafter is the derived qp
    export (api.draft_of default), i.e. a genuinely imperfect drafter:
    parity must hold through real rejections."""
    cfg, params, policy = _setup(family, form)
    ref = _ref_tokens(params, cfg, policy, max_new=7)
    eng = ServingEngine(params, cfg, policy=policy, slots=3, max_len=32,
                        dtype=jnp.float32, spec_k=3)
    for _ in range(3):
        eng.submit(PROMPT, max_new=7)
    eng.step(); eng.step()                  # first wave mid-decode...
    eng.submit(PROMPT, max_new=7)           # ...late wave rides along
    done = eng.run_all()
    assert len(done) == 4 and all(r.done for r in done)
    for r in done:
        assert r.out == ref, (family, form, r.out, ref)
    assert 0.0 <= eng.spec_accept_rate <= 1.0


def test_spec_tick_single_jitted_call_and_no_callbacks():
    """The whole draft(K+1 steps)->verify->accept->rollback tick is ONE
    jitted function: it compiles exactly once across a staggered run
    (trace count), and its jaxpr contains no host-callback primitives —
    there is nothing to sync per draft token."""
    cfg, params, policy = _setup("dense")
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, spec_k=3, draft_params=params)
    calls = {"n": 0}
    inner = eng._tick_fn

    def counting(*a, **k):
        calls["n"] += 1
        return inner(*a, **k)
    eng._tick_fn = counting

    for _ in range(3):                      # 3 requests through 2 slots
        eng.submit(PROMPT, max_new=6)
    done = eng.run_all()
    assert len(done) == 3
    assert calls["n"] == eng.decode_calls   # one jitted call per tick
    # ...compiled exactly once: the retrace budget comes from the analysis
    # registry (engine._jits / trace_counts), same surface the sweep
    # report uses — not a private counter on the jit object
    rep = retrace_report(eng, budgets={"tick": 1})
    assert rep["counts"]["tick"] == 1 and not rep["violations"], rep
    # self-draft => every draft accepted => 4 tokens per live tick: far
    # fewer target passes than tokens (the whole point)
    dec_toks = sum(len(r.out) - 1 for r in done)
    live_ticks = sum(r.ticks for r in done)
    assert dec_toks == 4 * (live_ticks - len(done)) + sum(
        r.accept_hist.get(n, 0) * n for r in done for n in r.accept_hist
        if n < 4), "self-draft ticks emit full windows except the last"
    assert eng.spec_accept_rate == 1.0
    # jaxpr of the tick: traceable end to end, no callback primitives
    # (the shared no_host_callback pass also rejects device_put/infeed)
    jaxpr = jax.make_jaxpr(eng._spec_tick)(
        eng.params, eng.draft_params, eng.cache, eng.draft_cache,
        eng._tokens, eng._active, eng._emitted, eng._budget,
        eng._poison0, jax.random.PRNGKey(0))
    assert not check_no_host_callback(jaxpr)


def test_spec_budget_exact_when_not_window_multiple():
    """max_new=5 with spec_k=3 (windows of up to 4): the last window must
    truncate to the remaining budget, not overshoot."""
    cfg, params, policy = _setup("dense")
    ref = _ref_tokens(params, cfg, policy, max_new=5)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, spec_k=3, draft_params=params)
    eng.submit(PROMPT, max_new=5)
    done = eng.run_all()
    assert done[0].out == ref and len(done[0].out) == 5


def test_spec_eos_mid_window():
    """An EOS inside an accepted window truncates the request exactly
    where the non-speculative EOS path would."""
    cfg, params, policy = _setup("dense")
    ref = _ref_tokens(params, cfg, policy, max_new=8)
    # the EOS must FIRST appear mid-stream (a token repeated from earlier
    # would truncate at its first occurrence, not the index we picked)
    idx = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eos, want = ref[idx], ref[:idx + 1]
    for spec_k, draft in ((0, None), (3, params)):
        eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                            dtype=jnp.float32, eos_id=eos, spec_k=spec_k,
                            draft_params=draft)
        eng.submit(PROMPT, max_new=8)
        done = eng.run_all()
        assert done[0].out == want, (spec_k, done[0].out, want)


def test_spec_request_stats():
    """Drained requests carry ticks + the accept-length histogram; the
    histogram accounts for every decode-emitted token."""
    cfg, params, policy = _setup("dense")
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, spec_k=2)
    eng.submit(PROMPT, max_new=6)
    eng.submit(PROMPT, max_new=6)
    done = eng.run_all()
    for r in done:
        assert r.ticks >= 1
        assert sum(r.accept_hist.values()) == r.ticks
        assert sum(n * c for n, c in r.accept_hist.items()) == len(r.out) - 1
        assert all(1 <= n <= 3 for n in r.accept_hist)
    drafted = sum(r.ticks for r in done) * 2
    assert eng.spec_drafted == drafted
    assert 0 <= eng.spec_accepted <= drafted
    # non-spec engines keep the same stats surface ({1: ticks} histogram)
    eng0 = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                         dtype=jnp.float32)
    eng0.submit(PROMPT, max_new=4)
    r0 = eng0.run_all()[0]
    assert r0.accept_hist == {1: 3} and r0.ticks == 3
    assert eng0.spec_accept_rate == 0.0


@pytest.mark.parametrize("slots", [4, 8])
def test_spec_commits_more_than_one_token_per_tick(slots):
    """A w-form verifier with the packed 3-bit drafter of the same
    checkpoint (``api.draft_of``) at spec_k 4, on mixed-length traffic at
    the reduced serving shape, commits more than one token per slot-tick;
    otherwise drafting is pure overhead."""
    cfg, params, policy = mix.model("w")
    draft_cfg, draft_params = model_api.draft_of(cfg, params, policy=W3)
    spec_k, max_new = 4, 24
    eng = ServingEngine(params, cfg, policy=policy, slots=slots,
                        max_len=max(mix.LENGTHS) + max_new + 1 + spec_k,
                        dtype=jnp.float32, spec_k=spec_k,
                        draft_params=draft_params, draft_cfg=draft_cfg)
    for p in mix.prompts(16):
        eng.submit(p, max_new=max_new)
    done = eng.run_all()
    # each request's first token is sampled at admission, not by a tick
    committed = sum(len(r.out) - 1 for r in done)
    assert committed / sum(r.ticks for r in done) > 1


def test_generate_spec_matches_generate(capsys):
    """generate(spec_k=) is token-identical to plain greedy generate for a
    multi-row batch (the jitted while_loop path)."""
    cfg, params, policy = _setup("hybrid")
    prompts = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    ref = generate(params, prompts, cfg, policy=policy, max_new_tokens=6,
                   dtype=jnp.float32)
    spec = generate(params, prompts, cfg, policy=policy, max_new_tokens=6,
                    dtype=jnp.float32, spec_k=2)
    assert np.array_equal(np.asarray(ref), np.asarray(spec))
    one = generate(params, prompts, cfg, policy=policy, max_new_tokens=1,
                   dtype=jnp.float32, spec_k=2)
    assert np.array_equal(np.asarray(one), np.asarray(ref[:, :4]))


def test_spec_rejections():
    """ssm target and drafter, ring-wrapping SWA, bad spec_k, vocab
    mismatch, and missing submit headroom all fail loudly."""
    scfg, sparams, spolicy = _setup("ssm")
    with pytest.raises(ValueError, match="ssm"):
        ServingEngine(sparams, scfg, policy=spolicy, slots=2, max_len=16,
                      spec_k=2)
    with pytest.raises(ValueError, match="ssm"):
        generate(sparams, jnp.asarray([PROMPT], jnp.int32), scfg,
                 policy=spolicy, max_new_tokens=4, spec_k=2)

    cfg, params, policy = _setup("dense")
    swa = dataclasses.replace(cfg, sliding_window=8)
    with pytest.raises(ValueError, match="sliding"):
        ServingEngine(params, swa, policy=policy, slots=2, max_len=16,
                      spec_k=2)
    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(params, cfg, policy=policy, slots=2, max_len=16,
                      spec_k=-1)
    other = dataclasses.replace(cfg, vocab_size=32)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(params, cfg, policy=policy, slots=2, max_len=16,
                      spec_k=2, draft_params=params, draft_cfg=other)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=16,
                        dtype=jnp.float32, spec_k=4)
    with pytest.raises(ValueError, match="spec_k"):
        eng.submit(PROMPT, max_new=9)       # 4+9+4 > 16: no verify headroom
    eng.submit(PROMPT, max_new=8)           # 4+8+4 == 16: fits


def test_spec_swa_within_window_works():
    """SWA arch with max_len <= window (no ring wrap) serves speculatively
    and stays parity-exact."""
    cfg, params, policy = _setup("dense")
    swa = dataclasses.replace(cfg, sliding_window=64)
    ref = _ref_tokens(params, swa, policy, max_new=5)
    eng = ServingEngine(params, swa, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, spec_k=3)
    eng.submit(PROMPT, max_new=5)
    done = eng.run_all()
    assert done[0].out == ref


def test_spec_kv8_parity():
    """Speculation composes with the int8 KV cache: both caches quantized,
    rollback rewinds the scale arrays too."""
    cfg, params, policy = _setup("dense")
    ref = _ref_tokens(params, cfg, policy, max_new=5, kv_bits=8)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, kv_bits=8, spec_k=3)
    eng.submit(PROMPT, max_new=5)
    done = eng.run_all()
    assert done[0].out == ref
