"""Per-assigned-architecture smoke tests (deliverable f): a REDUCED config of
the same family runs one forward + one train step on CPU, asserting output
shapes and no NaNs. The FULL configs are checked shape-only, in
tests/test_sharding.py."""
import jax
import jax.flatten_util
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, TrainConfig, get_config, reduced
from repro.core.precision import FLOAT, W3A8
from repro.models import get_model
from repro.models.frontends import synthetic_frontend_embeds, text_len
from repro.training.loop import make_train_step

B, S = 2, 16


def _batch(cfg, key, with_labels=False):
    st = text_len(cfg, S)
    out = {"tokens": jax.random.randint(key, (B, st), 0, cfg.vocab_size)}
    if with_labels:
        out["labels"] = jax.random.randint(key, (B, st), 0, cfg.vocab_size)
    if cfg.frontend:
        out["frontend_embeds"] = synthetic_frontend_embeds(key, cfg, B,
                                                           jnp.float32)
    return out


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("policy_name", ["float", "w3a8"])
def test_forward_smoke(arch, policy_name, key):
    cfg = reduced(get_config(arch))
    mod = get_model(cfg)
    params = mod.init(key, cfg)
    policy = FLOAT if policy_name == "float" else W3A8
    logits, aux = mod.forward(params, _batch(cfg, key), cfg, policy=policy,
                              dtype=jnp.float32)
    total = S if cfg.frontend else text_len(cfg, S)
    assert logits.shape == (B, total, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch, key):
    cfg = reduced(get_config(arch))
    mod = get_model(cfg)
    params = mod.init(key, cfg)
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=2,
                       remat="layer")
    step, init_state = make_train_step(cfg, tcfg, FLOAT, dtype=jnp.float32)
    state = init_state(params)
    state, metrics = step(state, _batch(cfg, key, with_labels=True))
    assert jnp.isfinite(metrics["loss"])
    assert not bool(jnp.any(jnp.isnan(
        jax.flatten_util.ravel_pytree(state["params"])[0])))
    assert int(state["step"]) == 1


@pytest.mark.parametrize("arch", ["qwen3-32b", "mixtral-8x22b", "mamba2-2.7b",
                                  "zamba2-1.2b", "internvl2-26b"])
def test_prefill_decode_smoke(arch, key):
    cfg = reduced(get_config(arch))
    mod = get_model(cfg)
    params = mod.init(key, cfg)
    toks = jax.random.randint(key, (B, 8), 0, cfg.vocab_size)
    logits, cache = mod.prefill(params, {"tokens": toks}, cfg, policy=FLOAT,
                                dtype=jnp.float32, max_len=12)
    assert logits.shape == (B, 1, cfg.vocab_size)
    for _ in range(3):
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        logits, cache = mod.decode_step(params, cache, tok, cfg, policy=FLOAT,
                                        dtype=jnp.float32)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))


def test_all_archs_have_exact_assigned_dims():
    """Pin the assigned-architecture table (guards against config drift)."""
    expect = {
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
        "stablelm-3b": (32, 2560, 32, 32, 6912, 50304),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
    }
    for arch, (l, d, h, kv, ff, v) in expect.items():
        c = get_config(arch)
        assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
                c.d_ff, c.vocab_size) == (l, d, h, kv, ff, v), arch
    # family-specific extras
    assert get_config("phi3.5-moe-42b-a6.6b").num_experts == 16
    assert get_config("mixtral-8x22b").num_experts == 8
    assert get_config("mamba2-2.7b").ssm_state == 128
    assert get_config("zamba2-1.2b").ssm_state == 64
    assert get_config("qwen3-32b").qk_norm
    assert get_config("qwen2.5-14b").qkv_bias
