"""Batched engine with an int8 KV cache (``kv_bits=8``) and the fused
decode-attention dispatch (``attn_mode``): token parity with single-request
``generate`` under staggered admission for the transformer family AND
hybrid (mirrors tests/test_engine_batched.py), halved cache bytes per slot,
and explicit rejection for the no-KV family."""
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
    return cfg, quant_dense.export_container(params, W3), W3


def _ref_tokens(params, cfg, policy, max_new, **kw):
    out = generate(params, jnp.asarray([PROMPT], jnp.int32), cfg,
                   policy=policy, max_new_tokens=max_new, dtype=jnp.float32,
                   **kw)
    return [int(t) for t in np.asarray(out[0, len(PROMPT):])]


@pytest.mark.parametrize("family", ["dense", "hybrid"])
@pytest.mark.parametrize("form", ["w", "qp"])
def test_engine_kv8_matches_generate_staggered(family, form):
    """kv_bits=8 engine tokens == kv_bits=8 solo generate — including a
    request admitted mid-decode next to busy slots (the int8 scatter and
    per-slot scales must stay row-independent)."""
    cfg, params, policy = _setup(family, form)
    ref = _ref_tokens(params, cfg, policy, max_new=5, kv_bits=8)
    eng = ServingEngine(params, cfg, policy=policy, slots=3, max_len=32,
                        dtype=jnp.float32, kv_bits=8)
    for _ in range(3):
        eng.submit(PROMPT, max_new=5)
    eng.step(); eng.step()                  # first wave mid-decode...
    eng.submit(PROMPT, max_new=5)           # ...late wave rides along
    done = eng.run_all()
    assert len(done) == 4 and all(r.done for r in done)
    for r in done:
        assert r.out == ref, (family, form, r.out, ref)


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_engine_kv8_kernel_attn_matches_generate(family):
    """attn_mode='kernel' (fused Pallas decode attention, interpret mode on
    CPU) x kv_bits=8 through the batched engine == the same solo path."""
    cfg, params, policy = _setup(family)
    ref = _ref_tokens(params, cfg, policy, max_new=4, kv_bits=8,
                      attn_mode="kernel")
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, kv_bits=8, attn_mode="kernel")
    for _ in range(3):                      # 3 requests through 2 slots
        eng.submit(PROMPT, max_new=4)
    done = eng.run_all()
    assert len(done) == 3 and all(r.done for r in done)
    for r in done:
        assert r.out == ref, (family, r.out, ref)


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_kernel_attn_matches_ref_attn_tokens(family):
    """attn_mode='kernel' is token-identical to attn_mode='ref' for solo
    generate AND the engine (bf16-class cache)."""
    cfg, params, policy = _setup(family)
    ref = _ref_tokens(params, cfg, policy, max_new=5, attn_mode="ref")
    ker = _ref_tokens(params, cfg, policy, max_new=5, attn_mode="kernel")
    assert ker == ref, (family, ker, ref)
    eng = ServingEngine(params, cfg, policy=policy, slots=2, max_len=32,
                        dtype=jnp.float32, attn_mode="kernel")
    eng.submit(PROMPT, max_new=5)
    done = eng.run_all()
    assert done[0].out == ref, (family, done[0].out, ref)


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_kv8_halves_engine_cache_bytes(family):
    cfg, params, policy = _setup(family)

    def nbytes(eng):
        leaves = (eng.cache["kv"] if family == "hybrid"
                  else {k: eng.cache[k] for k in ("k", "v")})
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(leaves))

    e16 = ServingEngine(params, cfg, policy=policy, slots=4, max_len=64)
    e8 = ServingEngine(params, cfg, policy=policy, slots=4, max_len=64,
                       kv_bits=8)
    # ~0.5 + the per-token fp32 scales, which only matter at this toy
    # head_dim (8B vs 2*KV*D entry bytes; negligible at production sizes)
    assert nbytes(e8) < nbytes(e16) * 0.6, (nbytes(e8), nbytes(e16))


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_init_cache_kv8_is_int8_with_scales(family):
    """``api.init_cache(kv_bits=8)`` stores K/V as int8 with one fp32 scale
    per token, for the transformer family and for hybrid's shared KV."""
    cfg, _, _ = _setup(family)
    cache = model_api.init_cache(cfg, 2, 16, jnp.float32, kv_bits=8)
    kv = cache["kv"] if family == "hybrid" else cache
    for name in ("k", "v"):
        assert kv[name].dtype == jnp.int8
        assert kv[f"{name}_scale"].dtype == jnp.float32
        assert kv[f"{name}_scale"].shape == kv[name].shape[:-1]


def test_kv8_rejected_for_ssm():
    """No silent downgrade: a family without a KV cache must refuse
    kv_bits=8 loudly (engine AND the shared init_cache helper)."""
    cfg, params, policy = _setup("ssm")
    with pytest.raises(ValueError, match="ssm"):
        ServingEngine(params, cfg, policy=policy, slots=2, max_len=16,
                      kv_bits=8)
    with pytest.raises(ValueError, match="ssm"):
        model_api.init_cache(cfg, 2, 16, jnp.float32, kv_bits=8)
    with pytest.raises(ValueError):
        model_api.init_cache(cfg, 2, 16, jnp.float32, kv_bits=4)


def test_bad_attn_mode_rejected():
    cfg, params, policy = _setup("dense")
    with pytest.raises(ValueError, match="attn"):
        ServingEngine(params, cfg, policy=policy, slots=2, max_len=16,
                      attn_mode="einsum")
