"""Sharding rules: divisibility guards, spec validity on the production mesh
shapes (pure spec-level checks — no 512-device init in the test process)."""
import pytest

from repro.configs import ARCH_IDS, LM_SHAPES, get_config
from repro.core.treeutil import flatten_with_path
from repro.distributed import sharding as shd
from repro.launch.steps import input_specs, _params_template, _state_template
from repro.configs.base import TrainConfig


class FakeMesh:
    """Shape-only stand-in for the 16x16 / 2x16x16 production meshes."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


SINGLE = FakeMesh(data=16, model=16)
MULTI = FakeMesh(pod=2, data=16, model=16)


def _check_divisibility(spec_tree, shape_tree, mesh):
    flat_s = flatten_with_path(spec_tree)
    flat_t = flatten_with_path(shape_tree)
    for path, spec in flat_s.items():
        leaf = flat_t[path]
        parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for dim, ax in zip(leaf.shape, parts):
            if ax is None:
                continue
            assert dim % shd.axis_size(mesh, ax) == 0, \
                f"{path}: {leaf.shape} not divisible by {ax}"


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
def test_param_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    params = _params_template(cfg, "w3", "train")
    specs = shd.param_specs(cfg, params, mesh, fsdp=True)
    _check_divisibility(specs, params, mesh)


@pytest.mark.parametrize("arch", ["qwen3-32b", "mixtral-8x22b", "mamba2-2.7b",
                                  "zamba2-1.2b"])
def test_serve_specs_divisible(arch):
    cfg = get_config(arch)
    for kind in ("prefill", "decode"):
        params = _params_template(cfg, "w3", kind)
        specs = shd.param_specs(cfg, params, SINGLE)
        _check_divisibility(specs, params, SINGLE)


def test_gqa_kv_replicated_when_not_divisible():
    cfg = get_config("qwen3-32b")            # kv=8 < model=16
    params = _params_template(cfg, "float", "train")
    specs = shd.param_specs(cfg, params, SINGLE)
    wk = flatten_with_path(specs)["layers/attn/wk/w"]
    assert all(a != "model" for a in wk)     # replicated over model
    wq = flatten_with_path(specs)["layers/attn/wq/w"]
    assert "model" in tuple(wq)


def test_mha_kv_sharded_when_divisible():
    cfg = get_config("stablelm-3b")          # kv=32 % 16 == 0
    params = _params_template(cfg, "float", "train")
    specs = shd.param_specs(cfg, params, SINGLE)
    wk = flatten_with_path(specs)["layers/attn/wk/w"]
    assert "model" in tuple(wk)


def test_moe_expert_parallel_vs_tensor_parallel():
    # phi3.5: 16 experts % 16 == 0 -> EP on the expert dim
    cfg = get_config("phi3.5-moe-42b-a6.6b")
    params = _params_template(cfg, "float", "train")
    up = flatten_with_path(shd.param_specs(cfg, params, SINGLE))["layers/moe/up/w"]
    assert tuple(up)[1] == "model"           # (L, E, d, f): E sharded
    # mixtral: 8 experts (not divisible) -> TP inside experts
    cfg = get_config("mixtral-8x22b")
    params = _params_template(cfg, "float", "train")
    up = flatten_with_path(shd.param_specs(cfg, params, SINGLE))["layers/moe/up/w"]
    assert tuple(up)[1] is None and "model" in tuple(up)


def test_fsdp_adds_data_axis():
    cfg = get_config("qwen3-32b")
    params = _params_template(cfg, "float", "train")
    up_nofsdp = flatten_with_path(
        shd.param_specs(cfg, params, SINGLE, fsdp=False))["layers/mlp/up/w"]
    up_fsdp = flatten_with_path(
        shd.param_specs(cfg, params, SINGLE, fsdp=True))["layers/mlp/up/w"]
    assert "data" not in tuple(up_nofsdp)
    assert "data" in tuple(up_fsdp) and "model" in tuple(up_fsdp)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_cover_all_shapes(arch):
    cfg = get_config(arch)
    for s in LM_SHAPES:
        if s.name == "long_500k" and not cfg.sub_quadratic:
            continue
        specs = input_specs(cfg, s)
        assert "tokens" in specs
        assert specs["tokens"].shape[0] == s.global_batch
        if s.kind == "decode":
            assert specs["tokens"].shape == (s.global_batch, 1)
        if cfg.frontend and s.kind != "decode":
            assert "frontend_embeds" in specs


def test_state_specs_cover_optimizer():
    cfg = get_config("qwen2-1.5b")
    st = _state_template(cfg, TrainConfig(), "w3")
    specs = shd.state_specs(cfg, st, SINGLE, fsdp=True)
    assert "opt" in specs and "m" in specs["opt"]
    _check_divisibility(specs["params"], st["params"], SINGLE)
    _check_divisibility(specs["opt"]["m"], st["opt"]["m"], SINGLE)


def test_constrain_noop_outside_context():
    import jax.numpy as jnp
    from repro.distributed.context import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, "act") is x
