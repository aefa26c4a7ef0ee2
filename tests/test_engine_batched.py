"""Batched continuous-batching engine: token parity with single-request
``generate`` for every family x weight form, slot isolation under mid-stream
admission, and the core scaling invariant — one jitted ``decode_step`` per
tick regardless of how many slots are active (the paper's weight-streaming
amortization depends on exactly this)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8
from repro.models import get_model
from repro.models import api as model_api
from repro.serving.engine import ServingEngine, generate

# weight-only W3 for the form sweep; full W3A8 (dynamic 8-bit act scales) is
# exercised separately below — scales are per-ROW since the kernel-dispatch
# PR, so act quant no longer couples batch rows
W3 = dataclasses.replace(W3A8, act_bits=None)

ARCH_FOR = {"dense": "qwen2-1.5b", "ssm": "mamba2-2.7b",
            "hybrid": "zamba2-1.2b"}
PROMPT = [1, 2, 3, 4]
# == the smallest admission bucket (engine._MIN_BUCKET): batched prefill
# adds no intra-row padding, so a row's dynamic act absmax sees exactly the
# tokens the solo run sees (padding POSITIONS inside a row would enter its
# per-row scale; padding ROWS never do)
PROMPT_BUCKET = [1, 2, 3, 4, 5, 6, 7, 8]


def _setup(family, form):
    layers = 4 if family == "hybrid" else 2    # hybrid: 2 groups of 2
    cfg = reduced(get_config(ARCH_FOR[family]), layers=layers, d_model=32,
                  vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    if form == "w":
        return cfg, params, FLOAT
    if form == "q":
        return cfg, quant_dense.export_levels(params, W3), W3
    return cfg, quant_dense.export_container(params, W3), W3


def _ref_tokens(params, cfg, policy, max_new):
    out = generate(params, jnp.asarray([PROMPT], jnp.int32), cfg,
                   policy=policy, max_new_tokens=max_new, dtype=jnp.float32)
    return [int(t) for t in np.asarray(out[0, len(PROMPT):])]


@pytest.mark.parametrize("form", ["w", "q", "qp"])
@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
def test_engine_matches_generate(family, form):
    """Every slot's tokens == single-request generate, all families/forms."""
    cfg, params, policy = _setup(family, form)
    ref = _ref_tokens(params, cfg, policy, max_new=5)
    eng = ServingEngine(params, cfg, policy=policy, slots=3, max_len=32,
                        dtype=jnp.float32)
    for _ in range(4):                      # 4 requests through 3 slots
        eng.submit(PROMPT, max_new=5)
    done = eng.run_all()
    assert len(done) == 4 and all(r.done for r in done)
    for r in done:
        assert r.out == ref, (family, form, r.out, ref)


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
def test_engine_matches_generate_act_bits(family):
    """Full W3A8 (dynamic 8-bit activation scales): per-ROW scales keep
    slots independent, so engine tokens == solo generate even under act
    quant — including a late wave admitted mid-decode next to busy slots.
    Prompts sit exactly on the admission bucket (see PROMPT_BUCKET)."""
    layers = 4 if family == "hybrid" else 2
    cfg = reduced(get_config(ARCH_FOR[family]), layers=layers, d_model=32,
                  vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    sp = quant_dense.export_container(params, W3A8)
    out = generate(sp, jnp.asarray([PROMPT_BUCKET], jnp.int32), cfg,
                   policy=W3A8, max_new_tokens=5, dtype=jnp.float32)
    ref = [int(t) for t in np.asarray(out[0, len(PROMPT_BUCKET):])]
    eng = ServingEngine(sp, cfg, policy=W3A8, slots=3, max_len=32,
                        dtype=jnp.float32)
    for _ in range(3):
        eng.submit(PROMPT_BUCKET, max_new=5)
    eng.step(); eng.step()                  # first wave mid-decode...
    eng.submit(PROMPT_BUCKET, max_new=5)    # ...second wave rides along
    done = eng.run_all()
    assert len(done) == 4 and all(r.done for r in done)
    for r in done:
        assert r.out == ref, (family, r.out, ref)


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
def test_mid_stream_admission_does_not_perturb_active_slots(family):
    """A request admitted while another is decoding must not change the
    active slot's continuation (slot-major rows are independent)."""
    cfg, params, policy = _setup(family, "w")
    ref_a = _ref_tokens(params, cfg, policy, max_new=6)
    eng = ServingEngine(params, cfg, policy=policy, slots=4, max_len=32,
                        dtype=jnp.float32)
    eng.submit(PROMPT, max_new=6)
    eng.step(); eng.step()                  # request A mid-decode
    eng.submit([7, 8, 9, 10, 11], max_new=4)   # different prompt + length
    done = eng.run_all()
    a = next(r for r in done if r.uid == 1)
    b = next(r for r in done if r.uid == 2)
    assert a.out == ref_a, (a.out, ref_a)
    # B itself matches its own solo run
    ref_b = generate(params, jnp.asarray([[7, 8, 9, 10, 11]], jnp.int32), cfg,
                     policy=policy, max_new_tokens=4, dtype=jnp.float32)
    assert b.out == [int(t) for t in np.asarray(ref_b[0, 5:])]


def test_one_decode_call_per_tick():
    """An engine tick issues exactly ONE decode_step regardless of the
    number of active slots — no per-slot Python loop. Counted at the family
    module so any fallback to per-request decoding would show up."""
    from repro.models import transformer

    cfg, params, policy = _setup("dense", "w")
    calls = {"n": 0}
    orig = transformer.decode_step

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    transformer.decode_step = counting
    try:
        with jax.disable_jit():
            eng = ServingEngine(params, cfg, policy=policy, slots=4,
                                max_len=16, dtype=jnp.float32)
            for _ in range(4):              # all four slots active at once
                eng.submit(PROMPT, max_new=3)
            ticks = 0
            while eng.queue or eng._occupied():
                eng.step()
                ticks += 1
            eng.drain()
        assert calls["n"] == ticks == eng.decode_calls
        assert ticks < 4 * 3                # batched: NOT requests x tokens
    finally:
        transformer.decode_step = orig

    # under jit the tick is traced once and replayed: still one decode_step
    # trace total, while the engine advances many ticks
    calls["n"] = 0
    transformer.decode_step = counting
    try:
        eng = ServingEngine(params, cfg, policy=policy, slots=4, max_len=16,
                            dtype=jnp.float32)
        for _ in range(4):
            eng.submit(PROMPT, max_new=3)
        done = eng.run_all()
        assert len(done) == 4
        assert eng.decode_calls >= 2        # several ticks ran...
        assert calls["n"] <= 2              # ...but only the trace called in
    finally:
        transformer.decode_step = orig


def test_shared_cache_allocated_once_per_slot_lens():
    """The engine owns ONE slot-major cache with per-slot length counters."""
    cfg, params, policy = _setup("dense", "w")
    eng = ServingEngine(params, cfg, policy=policy, slots=4, max_len=16,
                        dtype=jnp.float32)
    assert eng.cache["len"].shape == (4,)
    # (L, slots, S, KV*D): the attention kernels' layout, as stored
    assert eng.cache["k"].shape == (cfg.num_layers, 4, 16,
                                    cfg.num_kv_heads * cfg.head_dim)
    eng.submit(PROMPT, max_new=2)
    eng.submit(PROMPT, max_new=4)
    eng.step()
    lens = np.asarray(eng.cache["len"])
    assert lens[0] == lens[1] == len(PROMPT) + 1   # both slots advanced
    assert lens[2] == lens[3] == 0                 # free slots untouched


def test_insert_prefill_roundtrip_ssm():
    """insert_prefill drops a batch=1 prefill state into the right slot and
    leaves other slots bit-identical."""
    cfg, params, policy = _setup("ssm", "w")
    mod = get_model(cfg)
    shared = model_api.init_cache(cfg, 3, 16, jnp.float32, per_slot_len=True)
    before = jax.tree_util.tree_map(np.asarray, shared)
    _, src = mod.prefill(params, {"tokens": jnp.asarray([PROMPT], jnp.int32)},
                         cfg, policy=policy, dtype=jnp.float32, max_len=16)
    out = mod.insert_prefill(shared, jnp.asarray(1, jnp.int32), src)
    assert int(out["len"][1]) == len(PROMPT)
    assert int(out["len"][0]) == 0 and int(out["len"][2]) == 0
    # untouched slots identical
    for leaf_b, leaf_a in zip(jax.tree_util.tree_leaves(before["layers"]),
                              jax.tree_util.tree_leaves(out["layers"])):
        np.testing.assert_array_equal(leaf_b[:, 0], np.asarray(leaf_a)[:, 0])
        np.testing.assert_array_equal(leaf_b[:, 2], np.asarray(leaf_a)[:, 2])


@pytest.mark.parametrize("drain_every", [1, 4])
def test_eos_frees_slot_for_queue(drain_every):
    """EOS termination mid-budget frees the slot; queued work lands in it.
    drain_every > 1 exercises the admission-internal sync, which must not
    lose the finished request from run_all()'s results."""
    cfg, params, policy = _setup("dense", "w")
    ref = _ref_tokens(params, cfg, policy, max_new=8)
    # EOS = a token whose FIRST occurrence is mid-stream (not the prefill
    # sample), so termination exercises the decode path
    idx = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eng = ServingEngine(params, cfg, policy=policy, slots=1, max_len=32,
                        dtype=jnp.float32, eos_id=ref[idx],
                        drain_every=drain_every)
    eng.submit(PROMPT, max_new=8)
    eng.submit(PROMPT, max_new=8)
    done = eng.run_all()
    assert len(done) == 2, [r.uid for r in done]
    for r in done:
        assert r.out == ref[:idx + 1], (r.out, ref, idx)
