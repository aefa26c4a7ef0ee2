"""Sharding rules: parameter PartitionSpecs by path + activation-constraint
tables, for the production meshes (DESIGN §8).

Axes: ``data`` (+ ``pod`` when multi-pod) = data parallel; ``model`` = tensor
parallel (Megatron pattern), expert parallel (MoE, when E % model == 0), and
sequence sharding for decode KV caches.

All rules are **divisibility-guarded**: a dim is only sharded if the axis size
divides it; otherwise the next candidate (or replication) applies. That is
what lets a single rule set serve 10 architectures (GQA kv=2/8/32, MoE E=8/16,
vocab 92553, SSD heads 80, ...) on a 16-way model axis without per-arch
special cases.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.treeutil import map_with_path

__all__ = ["dp_axes", "param_specs", "state_specs", "batch_specs",
           "activation_rules", "cache_specs", "tree_shardings", "axis_size"]


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, (tuple, list)):
        return int(np.prod([axis_size(mesh, n) for n in name]))
    return mesh.shape[name] if name in mesh.axis_names else 1


def _guarded(shape: Sequence[int], mesh: Mesh,
             candidates: Sequence[Tuple[int, Any]]) -> P:
    """First candidate (dim, axis) whose axis size divides shape[dim] wins.
    ``dim`` may be negative (counted from the end) — rules are written
    against the *logical* weight, so stacked leading layer dims (L,) or
    (G, attn_every) don't change them."""
    spec = [None] * len(shape)
    for dim, axis in candidates:
        d = dim % len(shape)
        if shape[d] % axis_size(mesh, axis) == 0 and spec[d] is None:
            spec[d] = axis
            return P(*spec)
    return P(*spec)


def param_specs(cfg: ModelConfig, params_tree: Any, mesh: Mesh,
                fsdp: bool = False) -> Any:
    """PartitionSpec tree mirroring ``params_tree`` (works on ShapeDtypeStruct
    templates from jax.eval_shape — ``launch/steps.py``).

    ``fsdp=True`` additionally shards every >=2D weight over the ``data``
    axis on a free dim (ZeRO-3 semantics: XLA all-gathers per layer in
    fwd/bwd, reduce-scatters grads). Mandatory for the >=8B trains — fp32
    master + Adam moments replicated across 16 data rows do not fit 16GB."""
    kv_shardable = (cfg.num_kv_heads and
                    cfg.num_kv_heads % axis_size(mesh, "model") == 0)
    ep = cfg.num_experts and cfg.num_experts % axis_size(mesh, "model") == 0

    def rule(path: str, leaf) -> P:
        s = leaf.shape
        p = path.lower()
        if len(s) == 0:
            return P()
        # ---- embeddings / head -------------------------------------------------
        if p.endswith("embed/w"):
            return _guarded(s, mesh, [(0, "model"), (1, "model")])
        if "head/w" in p:
            return _guarded(s, mesh, [(-1, "model"), (-2, "model")])
        # ---- attention ---------------------------------------------------------
        if "attn/wq/w" in p or "attn/wq/b" in p:
            return _guarded(s, mesh, [(-1, "model")])
        if "attn/wk/" in p or "attn/wv/" in p:
            if kv_shardable:
                return _guarded(s, mesh, [(-1, "model")])
            return P(*([None] * len(s)))          # replicate small GQA kv
        if "attn/wo/w" in p:
            return _guarded(s, mesh, [(-2, "model")])
        # ---- MoE ---------------------------------------------------------------
        if "moe/router" in p:
            return P(*([None] * len(s)))
        if "moe/up/w" in p or "moe/gate/w" in p:    # (.., E, d, f)
            cand = [(-3, "model"), (-1, "model")] if ep else [(-1, "model")]
            return _guarded(s, mesh, cand)
        if "moe/down/w" in p:                       # (.., E, f, d)
            cand = [(-3, "model"), (-2, "model")] if ep else [(-2, "model")]
            return _guarded(s, mesh, cand)
        # ---- dense MLP -----------------------------------------------------------
        if "mlp/up/w" in p or "mlp/gate/w" in p:
            return _guarded(s, mesh, [(-1, "model")])
        if "mlp/down/w" in p:
            return _guarded(s, mesh, [(-2, "model")])
        # ---- mamba2 ----------------------------------------------------------------
        if "in_proj/w" in p:
            return _guarded(s, mesh, [(-1, "model")])
        if "/wz/w" in p or "/wx/w" in p:          # split projections (H-split)
            return _guarded(s, mesh, [(-1, "model")])
        if "/wbc/" in p or "/wdt/" in p:          # tiny: replicate
            return P(*([None] * len(s)))
        if "out_proj/w" in p:
            return _guarded(s, mesh, [(-2, "model")])
        if "conv_bc" in p:
            return P(*([None] * len(s)))
        if "conv_x" in p or "conv_w" in p or "conv_b" in p:
            return _guarded(s, mesh, [(-1, "model")])
        # ---- everything else (norms, biases, ssm dynamics, deltas) -----------------
        return P(*([None] * len(s)))

    def add_fsdp(spec: P, leaf) -> P:
        s = leaf.shape
        if len(s) < 2 or "data" not in mesh.axis_names:
            return spec
        parts = list(spec) + [None] * (len(s) - len(spec))
        if "data" in parts:
            return spec
        # prefer the matrix dim not already model-sharded, innermost first
        for d in (-2, -1, -3):
            d2 = d % len(s)
            if d2 < len(s) - 2 and len(s) == 2:
                continue
            if parts[d2] is None and s[d2] % axis_size(mesh, "data") == 0:
                parts[d2] = "data"
                return P(*parts)
        return spec

    def rule_dispatch(path, leaf):
        # quantized-serve leaves: {"q"| "qp", "delta"} follow the weight rule
        if path.endswith("/q") or path.endswith("/qp"):
            spec = rule(path[: path.rfind("/")] + "/w", leaf)
        elif path.endswith("/delta"):
            return P(*([None] * len(leaf.shape)))
        else:
            spec = rule(path, leaf)
        if fsdp and (path.endswith("/w") or path.endswith("/q")):
            spec = add_fsdp(spec, leaf)
        return spec

    return map_with_path(rule_dispatch, params_tree)


def state_specs(cfg: ModelConfig, state_tree: Any, mesh: Mesh,
                fsdp: bool = False) -> Any:
    """Train-state specs: params + optimizer moments (same layout) + scalars."""
    pspecs = param_specs(cfg, state_tree["params"], mesh, fsdp=fsdp)
    out = {"params": pspecs, "step": P()}
    if "opt" in state_tree:
        opt = {}
        for k, v in state_tree["opt"].items():
            if k == "count":
                opt[k] = P()
            else:   # moments mirror the param layout exactly
                opt[k] = param_specs(cfg, v, mesh, fsdp=fsdp)
        out["opt"] = opt
    if "deltas" in state_tree:
        out["deltas"] = map_with_path(
            lambda p, l: P(*([None] * len(l.shape))), state_tree["deltas"])
    return out


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                batch_tree: Any) -> Any:
    dp = dp_axes(mesh)
    shardable = shape.global_batch % axis_size(mesh, dp) == 0

    def rule(path, leaf):
        spec = [None] * len(leaf.shape)
        if shardable and len(leaf.shape) >= 1:
            spec[0] = dp
        return P(*spec)

    return map_with_path(rule, batch_tree)


def activation_rules(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> Dict:
    """Constraint table for distributed.context.sharding_rules."""
    dp = dp_axes(mesh)
    bs = shape.global_batch % axis_size(mesh, dp) == 0
    b = dp if bs else None
    ep = cfg.num_experts and cfg.num_experts % axis_size(mesh, "model") == 0
    vs = cfg.vocab_size % axis_size(mesh, "model") == 0
    return {
        "act": P(b, None, None),
        "dec_act": P(b, None, None),
        "logits": P(b, None, "model" if vs else None),
        "moe_dispatch": P(b, None, "model" if ep else None, None),
        "moe_buffer": P(b, "model" if ep else None, None, None),
    }


def cache_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                cache_tree: Any) -> Any:
    """KV-cache / SSM-state specs for serving.

    Transformer cache leaves: (L, B, S, KV*D) — batch over dp when it
    divides, **sequence over model** (the only way a 1.1TB 32k x 128 cache
    fits per-device HBM; softmax over the sharded axis becomes an XLA
    all-reduce pair, see DESIGN §8). Hybrid kv: (n_apps, B, S, KV*D).
    SSM states: (L, B, H, P, N) — heads over model.
    """
    dp = dp_axes(mesh)
    bs = shape.global_batch % axis_size(mesh, dp) == 0
    b = dp if bs else None

    def rule(path, leaf):
        s = leaf.shape
        if path.endswith("len") or len(s) <= 1:
            return P(*([None] * len(s)))
        if path.endswith("_scale"):                      # int8 kv per-token scales
            spec = [None] * len(s)
            spec[-2] = b                                 # (L, B, S)
            if s[-1] % axis_size(mesh, "model") == 0:
                spec[-1] = "model"
            return P(*spec)
        if path in ("k", "v") or path.endswith("/k") or path.endswith("/v"):
            spec = [None] * len(s)
            spec[1] = b                                  # batch
            if not bs and s[2] % axis_size(mesh, "data") == 0:
                spec[2] = ("data", "model") if s[2] % axis_size(
                    mesh, ("data", "model")) == 0 else "data"
            elif s[2] % axis_size(mesh, "model") == 0:
                spec[2] = "model"                        # sequence over model
            return P(*spec)
        if "/ssm" in path:                               # (L.., B, H, P, N)
            spec = [None] * len(s)
            spec[-4] = b
            if s[-3] % axis_size(mesh, "model") == 0:
                spec[-3] = "model"
            return P(*spec)
        if "/conv" in path:                              # (L.., B, W-1, C)
            spec = [None] * len(s)
            spec[-3] = b
            if s[-1] % axis_size(mesh, "model") == 0:
                spec[-1] = "model"
            return P(*spec)
        return P(*([None] * len(s)))

    return map_with_path(rule, cache_tree)


def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    return map_with_path(
        lambda p, s: NamedSharding(mesh, s) if s is not None else None,
        spec_tree)
