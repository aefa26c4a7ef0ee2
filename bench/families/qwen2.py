"""The Qwen2 family on the program's side: the program's ModelConfig for a
configuration file whose ``family`` is ``qwen2``, its sizes, and its
serve-form weights made from the seed (``bench/weights.py``). Its plain
reference is ``bench/reference/qwen2.py``."""
from __future__ import annotations

import dataclasses

from bench import weights

FORMS = ("qp",)            # weight forms this family's weights are made in


def shapes(model: dict) -> weights.Shapes:
    return weights.Shapes.of(model)


def program_config(conf: dict):
    """The program's ModelConfig: its arch's config with every size the
    configuration file states."""
    from repro.configs import get_config
    m = conf["model"]
    base = get_config(conf["arch"])
    if base.family != "dense" or not base.qkv_bias:
        raise ValueError(f"{conf['arch']} is not a Qwen2-style dense model")
    return dataclasses.replace(
        base, num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        d_ff=m["intermediate_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        vocab_size=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]))


def serve_params(seed: int, conf: dict):
    """The whole model in the ``qp`` serve layout, made on the default
    device in one jitted call from the seed."""
    return weights.serve_params(seed, shapes(conf["model"]))
