"""The KV cache in its stored layout, (L, B, S, KV*D), driven tick by tick.

The decode tick with the attention kernels (interpret mode on the CPU)
carries the stacked cache through its layer loop and writes each new row in
place. Here it runs against the einsum reference (``attn_mode="ref"``) over
ragged per-row lengths, for a bf16 and an int8 cache, through batched
admission (``insert_prefill_many``), release (``free_slots``) and a
speculative ``verify_step`` + ``rollback_cache``: the logits agree at every
step, the two caches agree, and each row of the cache holds what a prefill
of that row's whole committed sequence holds. Hybrid runs the same kernel
through a unit layer axis over its per-group cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.precision import FLOAT
from repro.models import api as model_api
from repro.models import hybrid, transformer

SLOTS, MAX_LEN, BUCKET = 4, 32, 8
PROMPT_LENS = [5, 2, 8, 3]
SLOT_MAP = [2, 0, 3, 1]                  # request i -> slot SLOT_MAP[i]


def _model(family):
    if family == "hybrid":
        cfg = reduced(get_config("zamba2-1.2b"), layers=4, d_model=32,
                      vocab=64)
        return hybrid, cfg, hybrid.init(jax.random.PRNGKey(0), cfg)
    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=32, vocab=64)
    return transformer, cfg, transformer.init(jax.random.PRNGKey(0), cfg)


def _kv(cache):
    return cache["kv"] if "kv" in cache else cache


def _logit_tol(kv_bits):
    """Both paths cast the probabilities to the cache's dtype for PV, the
    kernel before normalising and the reference after: a bf16 cache leaves
    bf16 rounding (2**-8) between them; an int8 cache computes in float32."""
    return 1e-4 if kv_bits == 8 else 5e-3


def _admit(mod, cfg, params, cache, prompts, slot_map, kv_bits):
    toks = np.zeros((len(prompts), BUCKET), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    _, src = mod.prefill(params, {"tokens": jnp.asarray(toks)}, cfg,
                         policy=FLOAT, dtype=jnp.float32, max_len=MAX_LEN,
                         lengths=lens, quantize_cache=kv_bits == 8,
                         attn_mode="ref")
    return mod.insert_prefill_many(cache, jnp.asarray(slot_map, jnp.int32),
                                   src)


def _close(a, b, what):
    for name, leaf in _kv(a).items():
        # an int8 entry may land one level apart where the values differ
        # by float error
        atol = 1.0 if leaf.dtype == jnp.int8 else 2e-2
        np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                   np.asarray(_kv(b)[name], np.float32),
                                   rtol=2e-2, atol=atol,
                                   err_msg=f"{what}: {name}")
    np.testing.assert_array_equal(np.asarray(a["len"]), np.asarray(b["len"]))


def _dequant(kv, name):
    x = np.asarray(kv[name], np.float32)
    if name + "_scale" in kv:
        x = x * np.asarray(kv[name + "_scale"])[..., None]
    return x


def _rows_match_prefill(mod, cfg, params, cache, seqs, kv_bits):
    """Row ``slot`` of the cache, at positions [0, len), holds the K/V a
    prefill of that row's committed sequence alone computes."""
    lens = np.asarray(cache["len"])
    for slot, seq in seqs.items():
        assert lens[slot] == len(seq), (slot, lens[slot], len(seq))
        _, one = mod.prefill(params, {"tokens": jnp.asarray([seq],
                                                            jnp.int32)},
                             cfg, policy=FLOAT, dtype=jnp.float32,
                             max_len=MAX_LEN, quantize_cache=kv_bits == 8,
                             attn_mode="ref")
        for name in ("k", "v"):
            got = _dequant(_kv(cache), name)[:, slot, :len(seq)]
            want = _dequant(_kv(one), name)[:, 0, :len(seq)]
            step = (2 * float(np.max(np.asarray(_kv(one)[name + "_scale"])))
                    if kv_bits == 8 else 2e-2)
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=step,
                                       err_msg=f"slot {slot} {name}")


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_tick_matches_einsum_reference(kv_bits):
    """Dense: 8 ticks, one admission into a released slot, and a verify +
    rollback; kernel and reference agree on logits and caches throughout,
    and every row holds its sequence's K/V in the stored layout."""
    mod, cfg, params = _model("dense")
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in PROMPT_LENS]
    cache = model_api.init_cache(cfg, SLOTS, MAX_LEN, jnp.bfloat16,
                                 per_slot_len=True, kv_bits=kv_bits)
    assert cache["k"].shape == (cfg.num_layers, SLOTS, MAX_LEN,
                                cfg.num_kv_heads * cfg.head_dim)
    cache = _admit(mod, cfg, params, cache, prompts, SLOT_MAP, kv_bits)
    seqs = {s: list(p) for s, p in zip(SLOT_MAP, prompts)}

    def steps(mode):
        kw = dict(policy=FLOAT, dtype=jnp.float32, attn_mode=mode)
        return (jax.jit(lambda c, t: mod.decode_step(params, c, t, cfg,
                                                     **kw)),
                jax.jit(lambda c, t: mod.verify_step(params, c, t, cfg,
                                                     **kw)[:2]))

    (dec_k, ver_k), (dec_r, ver_r) = steps("kernel"), steps("ref")
    ck = cr = cache
    tol = _logit_tol(kv_bits)

    def tick():
        nonlocal ck, cr
        tok = rng.integers(1, cfg.vocab_size, (SLOTS, 1)).astype(np.int32)
        lk, ck = dec_k(ck, jnp.asarray(tok))
        lr, cr = dec_r(cr, jnp.asarray(tok))
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lr),
                                   rtol=tol, atol=tol)
        for s in seqs:
            seqs[s].append(int(tok[s, 0]))

    for _ in range(4):
        tick()
    _close(ck, cr, "after 4 ticks")

    # release slot 1 and admit a new request there (one real row, one
    # padding row pointed out of range)
    idx = jnp.asarray([1] + [SLOTS] * (SLOTS - 1), jnp.int32)
    ck = mod.free_slots(ck, idx)
    cr = mod.free_slots(cr, idx)
    assert int(ck["len"][1]) == 0
    assert not np.any(np.asarray(_kv(ck)["k"])[:, 1])
    new = list(rng.integers(1, cfg.vocab_size, 6))
    ck = _admit(mod, cfg, params, ck, [new, [1]], [1, SLOTS], kv_bits)
    cr = _admit(mod, cfg, params, cr, [new, [1]], [1, SLOTS], kv_bits)
    seqs[1] = list(new)
    _close(ck, cr, "after free_slots + insert_prefill_many")

    # a speculative verify of 3 tokens, then reject a ragged suffix
    toks = rng.integers(1, cfg.vocab_size, (SLOTS, 3)).astype(np.int32)
    lk, ck = ver_k(ck, jnp.asarray(toks))
    lr, cr = ver_r(cr, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lr), rtol=tol,
                               atol=tol)
    keep = [3, 1, 0, 2]
    new_lens = np.asarray(ck["len"]) - 3 + np.asarray(keep)
    slots = jnp.arange(SLOTS)
    ck = mod.rollback_cache(ck, slots, jnp.asarray(new_lens, jnp.int32))
    cr = mod.rollback_cache(cr, slots, jnp.asarray(new_lens, jnp.int32))
    for s in seqs:
        seqs[s] += [int(x) for x in toks[s, :keep[s]]]
    _close(ck, cr, "after verify_step + rollback_cache")

    for _ in range(4):
        tick()
    _close(ck, cr, "after 8 ticks")
    _rows_match_prefill(mod, cfg, params, ck, seqs, kv_bits)


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_hybrid_tick_through_unit_layer(kv_bits):
    """Hybrid: its per-group cache, in the same stored layout, goes to the
    decode kernel as a one-layer stack; 8 ragged ticks agree with the
    reference, and every row holds its sequence's K/V."""
    mod, cfg, params = _model("hybrid")
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in PROMPT_LENS]
    cache = model_api.init_cache(cfg, SLOTS, MAX_LEN, jnp.bfloat16,
                                 per_slot_len=True, kv_bits=kv_bits)
    assert cache["kv"]["k"].shape == (cfg.num_layers // cfg.attn_every,
                                      SLOTS, MAX_LEN,
                                      cfg.num_kv_heads * cfg.head_dim)
    cache = _admit(mod, cfg, params, cache, prompts, SLOT_MAP, kv_bits)
    seqs = {s: list(p) for s, p in zip(SLOT_MAP, prompts)}
    dec = {mode: jax.jit(lambda c, t, mode=mode: mod.decode_step(
        params, c, t, cfg, policy=FLOAT, dtype=jnp.float32, attn_mode=mode))
        for mode in ("kernel", "ref")}
    ck = cr = cache
    tol = _logit_tol(kv_bits)
    for _ in range(8):
        tok = rng.integers(1, cfg.vocab_size, (SLOTS, 1)).astype(np.int32)
        lk, ck = dec["kernel"](ck, jnp.asarray(tok))
        lr, cr = dec["ref"](cr, jnp.asarray(tok))
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lr),
                                   rtol=tol, atol=tol)
        for s in seqs:
            seqs[s].append(int(tok[s, 0]))
    _close(ck, cr, "after 8 ticks")
    _rows_match_prefill(mod, cfg, params, ck, seqs, kv_bits)
