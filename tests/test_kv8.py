"""int8 KV cache (beyond-paper, §Perf H-kv8): decode matches bf16-cache decode
within quantization tolerance; scales factor exactly through attention —
for the transformer family AND hybrid, through the sliding-window ring, and
through the launch-layer kv8 cache templates."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.core.precision import FLOAT
from repro.models import hybrid, transformer
from repro.models.transformer import _quantize_kv

B, S, P = 2, 20, 16


def test_quantize_kv_roundtrip():
    # one token's KV heads side by side, as the cache stores them: 4 x 16
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4 * 16)) * 3
    q, s = _quantize_kv(x)
    assert s.shape == (2, 8)
    back = q.astype(jnp.float32) * s[..., None]
    assert float(jnp.max(jnp.abs(back - x))) <= float(jnp.max(s)) / 2 + 1e-5


def test_kv8_decode_close_to_bf16():
    cfg = reduced(get_config("qwen3-32b"))
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    logits_f, cache_f = transformer.prefill(
        params, {"tokens": toks[:, :P]}, cfg, policy=FLOAT,
        dtype=jnp.float32, max_len=S)
    logits_q, cache_q = transformer.prefill(
        params, {"tokens": toks[:, :P]}, cfg, policy=FLOAT,
        dtype=jnp.float32, max_len=S, quantize_cache=True)
    assert cache_q["k"].dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(logits_q), np.asarray(logits_f),
                               atol=1e-4)   # prefill logits don't read cache

    for t in range(P, S):
        logits_f, cache_f = transformer.decode_step(
            params, cache_f, toks[:, t:t + 1], cfg, policy=FLOAT,
            dtype=jnp.float32)
        logits_q, cache_q = transformer.decode_step(
            params, cache_q, toks[:, t:t + 1], cfg, policy=FLOAT,
            dtype=jnp.float32)
        # int8 cache error stays small through multiple steps
        err = float(jnp.max(jnp.abs(logits_q - logits_f)))
        denom = float(jnp.max(jnp.abs(logits_f))) + 1e-6
        assert err / denom < 0.05, (t, err, denom)


def test_kv8_cache_is_half_the_bytes():
    cfg = reduced(get_config("qwen3-32b"))
    c_f = transformer.init_cache(cfg, 4, 64)
    c_q = transformer.init_cache(cfg, 4, 64, quantized=True)
    nb = lambda c: sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(c))
    assert nb(c_q) < nb(c_f) * 0.55


# --- hybrid family -----------------------------------------------------------------


def test_kv8_hybrid_decode_close_to_bf16():
    """Hybrid int8-KV (per shared-attention application) tracks the float
    cache through multiple decode steps."""
    cfg = reduced(get_config("zamba2-1.2b"), layers=4)   # 2 groups of 2
    params = hybrid.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    logits_f, st_f = hybrid.prefill(params, {"tokens": toks[:, :P]}, cfg,
                                    policy=FLOAT, dtype=jnp.float32, max_len=S)
    logits_q, st_q = hybrid.prefill(params, {"tokens": toks[:, :P]}, cfg,
                                    policy=FLOAT, dtype=jnp.float32, max_len=S,
                                    quantize_cache=True)
    assert st_q["kv"]["k"].dtype == jnp.int8
    # (G, B, S, KV*D) entries, (G, B, S) scales
    assert st_q["kv"]["k"].shape == (cfg.num_layers // cfg.attn_every, B, S,
                                     cfg.num_kv_heads * cfg.head_dim)
    assert st_q["kv"]["k_scale"].shape == st_q["kv"]["k"].shape[:3]
    np.testing.assert_allclose(np.asarray(logits_q), np.asarray(logits_f),
                               atol=1e-4)   # prefill logits don't read cache

    for t in range(P, S):
        logits_f, st_f = hybrid.decode_step(params, st_f, toks[:, t:t + 1],
                                            cfg, policy=FLOAT,
                                            dtype=jnp.float32)
        logits_q, st_q = hybrid.decode_step(params, st_q, toks[:, t:t + 1],
                                            cfg, policy=FLOAT,
                                            dtype=jnp.float32)
        err = float(jnp.max(jnp.abs(logits_q - logits_f)))
        denom = float(jnp.max(jnp.abs(logits_f))) + 1e-6
        assert err / denom < 0.05, (t, err, denom)


def test_kv8_hybrid_cache_is_half_the_kv_bytes():
    cfg = reduced(get_config("zamba2-1.2b"), layers=4)
    c_f = hybrid.init_cache(cfg, 4, 64)
    c_q = hybrid.init_cache(cfg, 4, 64, quantized=True)
    nb = lambda kv: sum(x.size * x.dtype.itemsize
                        for x in jax.tree_util.tree_leaves(kv))
    # mamba states are untouched; the KV part (entries + scales) halves
    assert nb(c_q["kv"]) < nb(c_f["kv"]) * 0.6


# --- sliding-window ring x int8 ----------------------------------------------------


def test_kv8_swa_ring_scales_rotate_with_slots():
    """Decode past the window: the int8 ring overwrites value AND scale at
    slot pos % window, so each slot's scale always matches its token."""
    cfg = reduced(get_config("mixtral-8x22b"))
    cfg = dataclasses.replace(cfg, sliding_window=8, num_experts=0,
                              family="dense")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    logits_f, cache_f = transformer.prefill(
        params, {"tokens": toks[:, :P]}, cfg, policy=FLOAT,
        dtype=jnp.float32, max_len=S)
    logits_q, cache_q = transformer.prefill(
        params, {"tokens": toks[:, :P]}, cfg, policy=FLOAT,
        dtype=jnp.float32, max_len=S, quantize_cache=True)
    # (L, B, S, KV*D): the ring is the position axis, bounded by the window
    cs = cache_q["k"].shape[2]
    assert cs == 8
    assert cache_q["k"].shape == (cfg.num_layers, B, cs,
                                  cfg.num_kv_heads * cfg.head_dim)
    assert cache_q["k_scale"].shape == (cfg.num_layers, B, cs)
    for t in range(P, S):
        logits_f, cache_f = transformer.decode_step(
            params, cache_f, toks[:, t:t + 1], cfg, policy=FLOAT,
            dtype=jnp.float32)
        prev_ks = cache_q["k_scale"]
        logits_q, cache_q = transformer.decode_step(
            params, cache_q, toks[:, t:t + 1], cfg, policy=FLOAT,
            dtype=jnp.float32)
        # exactly ONE ring slot's scale was rewritten this step: t % cs
        changed = np.nonzero(np.any(np.asarray(cache_q["k_scale"])
                                    != np.asarray(prev_ks), axis=(0, 1)))[0]
        assert list(changed) == [t % cs], (t, changed)
        err = float(jnp.max(jnp.abs(logits_q - logits_f)))
        denom = float(jnp.max(jnp.abs(logits_f))) + 1e-6
        assert err / denom < 0.05, (t, err, denom)


def test_kv8_ring_masking_parity_kernel_vs_ref():
    """Per-row cache_len masking over an int8 ring cache: the fused kernel
    agrees with the kernel-package oracle AND the einsum path when rows sit
    at different fill levels of the same ring."""
    from repro.kernels.attn_decode.ops import attn_decode
    from repro.kernels.attn_decode.ref import attn_decode_ref
    from repro.models.attention import decode_attention

    b, s, h, kv, d = 4, 24, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kc = jax.random.normal(ks[1], (1, b, s, kv * d))     # one stored layer
    vc = jax.random.normal(ks[2], (1, b, s, kv * d))
    kq, ksc = _quantize_kv(kc)
    vq, vsc = _quantize_kv(vc)
    lens = jnp.asarray([3, 24, 11, 17], jnp.int32)   # mixed ring fill
    out = attn_decode(q, kq, vq, lens, ksc, vsc, bm=2, bs=8, interpret=True)
    ref = attn_decode_ref(q, kq[0].reshape(b, s, kv, d),
                          vq[0].reshape(b, s, kv, d), lens, ksc[0], vsc[0])
    ein = decode_attention(q, kq, vq, lens, ksc, vsc, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ein), atol=2e-5)
