"""Mixed-length traffic on qwen2-1.5b reduced to 4 layers, d_model 128 and
a 512-token vocabulary: the serving shape that the overload, crash-recovery
and speculation gates (test_resilience, test_durability, test_engine_spec)
run on."""
import dataclasses
import functools

import jax

from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8
from repro.models import get_model

W3 = dataclasses.replace(W3A8, act_bits=None)
# prompt lengths cycle over both small admission buckets (<= 8, 9..16)
LENGTHS = (3, 8, 5, 12, 4, 16, 7, 9)


@functools.lru_cache(maxsize=None)
def model(form: str):
    """(cfg, params, policy) in weight form ``w`` (float) or ``qp`` (packed
    3-bit containers, weight-only quantization)."""
    cfg = reduced(get_config("qwen2-1.5b"), layers=4, d_model=128, vocab=512)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    if form == "w":
        return cfg, params, FLOAT
    return cfg, quant_dense.export_container(params, W3), W3


def prompts(n: int):
    return [[(i * 7 + j) % 50 + 1 for j in range(LENGTHS[i % len(LENGTHS)])]
            for i in range(n)]
