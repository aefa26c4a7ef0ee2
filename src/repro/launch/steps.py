"""Shape-only templates for one (arch x shape) cell: ``input_specs`` gives
ShapeDtypeStruct stand-ins for the model inputs, ``_params_template`` the
parameters in a weight form (float, int8 levels for prefill, packed 3-bit
containers for decode) and ``_state_template`` the training state. All go
through ``jax.eval_shape``, so nothing is allocated on any device.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import optim as optim_lib
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8, QuantPolicy
from repro.models import get_model
from repro.models.frontends import frontend_embed_shape, text_len

__all__ = ["input_specs"]

PARAM_DTYPE = jnp.float32  # master weights
COMPUTE_DTYPE = jnp.bfloat16


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b = shape.global_batch
    if shape.kind == "decode":
        return {"tokens": _sds((b, 1), jnp.int32)}
    st = text_len(cfg, shape.seq_len)
    out = {"tokens": _sds((b, st), jnp.int32)}
    if shape.kind == "train":
        out["labels"] = _sds((b, st), jnp.int32)
    if cfg.frontend is not None:
        out["frontend_embeds"] = _sds(frontend_embed_shape(cfg, b),
                                      COMPUTE_DTYPE)
    return out


def _policy(quant: str) -> QuantPolicy:
    return FLOAT if quant == "float" else W3A8


def _params_template(cfg: ModelConfig, quant: str, kind: str):
    mod = get_model(cfg)

    def make(key):
        p = mod.init(key, cfg, dtype=PARAM_DTYPE)
        if kind == "train" or quant == "float":
            return p
        pol = _policy(quant)
        if kind == "prefill" or quant == "w3levels":
            return quant_dense.export_levels(p, pol)
        return quant_dense.export_container(p, pol)

    return jax.eval_shape(make, jax.random.PRNGKey(0))


def _state_template(cfg: ModelConfig, tcfg: TrainConfig, quant: str):
    params = _params_template(cfg, quant, "train")
    opt = optim_lib.make(tcfg.optimizer)

    def make(p):
        st = {"params": p, "opt": opt.init(p),
              "step": jnp.zeros((), jnp.int32)}
        if quant != "float":
            st["deltas"] = quant_dense.fit_deltas_stacked(p, _policy(quant))
        return st

    return jax.eval_shape(make, params)
