"""Seeded serve-form weights, made on the device, and the same weights for the
reference.

The benchmark, not the program, makes the weights: 3-bit levels in -3..3 and
8-bit levels in -127..127 drawn from random bytes, per-output-channel step
sizes (``delta``), QKV biases and norm scales, all from ``--seed``. The
program gets them in its packed serve form (``qp``: ten 3-bit fields per
int32 word, packed along K); the reference regenerates the same levels from
the same keys and dequantizes them itself, one layer at a time. No float32
copy of a whole model exists at any point.

Every leaf is keyed ``fold_in(fold_in(root, LEAF_ID[name]), layer)``, so a
leaf's levels do not depend on how many other leaves or layers are built.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

LEAF_ID = {"embed": 1, "head": 2, "final_norm": 3, "ln1": 4, "ln2": 5,
           "wq": 10, "wk": 11, "wv": 12, "wo": 13, "gate": 14, "up": 15,
           "down": 16}
FIELDS = 10            # 3-bit fields per int32 word
# byte thresholds that map a uniform byte to the L2-optimal 3-bit levels of
# a Gaussian (step 0.586 sigma): P(0) = 58/256, P(+-1) = 50/256,
# P(+-2) = 31/256, P(+-3) = 18/256
_L3_EDGES = (18, 49, 99, 157, 207, 238)
_L3_STD = math.sqrt(672 / 256)            # std of those levels
_L8_STD = 255 / math.sqrt(24)             # std of (u1 + u2 - 255) / 2


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes a Qwen2 block needs, read from a configuration file."""
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool

    @classmethod
    def of(cls, model: dict) -> "Shapes":
        return cls(layers=model["num_hidden_layers"], d=model["hidden_size"],
                   ff=model["intermediate_size"],
                   heads=model["num_attention_heads"],
                   kv_heads=model["num_key_value_heads"],
                   head_dim=model["hidden_size"]
                   // model["num_attention_heads"],
                   vocab=model["vocab_size"],
                   tied=bool(model["tie_word_embeddings"]))

    def matrices(self):
        """(name, K, N, has_bias) of the seven 3-bit matrices of a layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return (("wq", self.d, q, True), ("wk", self.d, kv, True),
                ("wv", self.d, kv, True), ("wo", q, self.d, False),
                ("gate", self.d, self.ff, False), ("up", self.d, self.ff, False),
                ("down", self.ff, self.d, False))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 32 bits at a time."""
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    sign, seed = (1, -seed) if seed < 0 else (0, seed)
    key = jax.random.fold_in(key, sign)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return key


def _leaf_key(root, name: str, index):
    return jax.random.fold_in(jax.random.fold_in(root, LEAF_ID[name]), index)


def levels3(key, shape) -> jax.Array:
    """int8 levels in -3..3 with the shares of a quantized Gaussian."""
    u = jax.random.bits(key, shape, jnp.uint8)
    q = jnp.zeros(shape, jnp.int8) - 3
    for edge in _L3_EDGES:
        q = q + (u >= edge).astype(jnp.int8)
    return q


def levels8(key, shape) -> jax.Array:
    """int8 levels in -127..127, triangular around 0."""
    u = jax.random.bits(key, (2,) + tuple(shape), jnp.uint8).astype(jnp.int16)
    return jnp.clip((u[0] + u[1] - 255) // 2, -127, 127).astype(jnp.int8)


def _delta(key, n: int, std: float) -> jax.Array:
    """Per-channel step sizes around ``std``, spread by +-25%."""
    return std * (0.75 + 0.5 * jax.random.uniform(key, (n,), jnp.float32))


def matrix(root, name: str, layer, k: int, n: int, bias: bool):
    """One 3-bit matrix of one layer: (levels (K, N) int8, delta (N,) f32,
    bias (N,) f32 or None). The effective weight has std 1/sqrt(3K), the
    statistics of the program's own uniform initialisation."""
    key = _leaf_key(root, name, layer)
    q = levels3(jax.random.fold_in(key, 0), (k, n))
    d = _delta(jax.random.fold_in(key, 1), n,
               1.0 / (math.sqrt(3 * k) * _L3_STD))
    b = (0.1 * jax.random.normal(jax.random.fold_in(key, 2), (n,),
                                 jnp.float32) if bias else None)
    return q, d, b


def norm_scale(root, name: str, index, d: int) -> jax.Array:
    return 1.0 + 0.1 * jax.random.normal(_leaf_key(root, name, index), (d,),
                                         jnp.float32)


def _table(root, name: str, rows: int, cols: int, std: float):
    """An 8-bit table (rows, cols), made in 64 row blocks or fewer so that
    no temporary holds more than a block, and its per-column deltas."""
    nb = math.gcd(rows, 64)
    q = jax.lax.map(lambda i: levels8(_leaf_key(root, name, i),
                                      (rows // nb, cols)), jnp.arange(nb))
    d = _delta(_leaf_key(root, name, 1 << 30), cols, std / _L8_STD)
    return q.reshape(rows, cols), d


def embed_table(root, s: Shapes):
    """Embedding levels (V, D) int8 and deltas (D,); entries of std 0.02."""
    return _table(root, "embed", s.vocab, s.d, 0.02)


def head_table(root, s: Shapes):
    """Untied head levels (D, V) int8 and deltas (V,); entries of std
    1/sqrt(3D), the statistics of the program's uniform initialisation."""
    return _table(root, "head", s.d, s.vocab, 1.0 / math.sqrt(3 * s.d))


def pack3(q: jax.Array) -> jax.Array:
    """(K, N) levels in -4..3 -> (ceil(K/10), N) int32: word j of column n
    holds rows 10j..10j+9, row 10j+i in bits 3i..3i+2 (two's complement),
    K zero-padded to a multiple of ten."""
    k, n = q.shape
    kp = -(-k // FIELDS)
    q = jnp.pad(q, ((0, kp * FIELDS - k), (0, 0))).astype(jnp.int32) & 7
    shifts = (jnp.arange(FIELDS, dtype=jnp.int32) * 3)[None, :, None]
    return jnp.sum(q.reshape(kp, FIELDS, n) << shifts, axis=1,
                   dtype=jnp.int32)


@partial(jax.jit, static_argnames=("s",))
def _serve_params(root, s: Shapes):
    layers_idx = jnp.arange(s.layers)
    layer: dict = {"attn": {}, "mlp": {}}
    for name, k, n, bias in s.matrices():
        def one(l, name=name, k=k, n=n, bias=bias):
            q, d, b = matrix(root, name, l, k, n, bias)
            out = {"qp": pack3(q), "delta": d.reshape(1, n)}
            if bias:
                out["b"] = b
            return out
        group = "attn" if name.startswith("w") else "mlp"
        layer[group][name] = jax.lax.map(one, layers_idx)
    for ln in ("ln1", "ln2"):
        layer[ln] = {"scale": jax.lax.map(
            lambda l, ln=ln: norm_scale(root, ln, l, s.d), layers_idx)}
    q, d = embed_table(root, s)
    params = {"embed": {"q": q, "delta": d.reshape(1, s.d)},
              "layers": layer,
              "final_norm": {"scale": norm_scale(root, "final_norm", 0, s.d)}}
    if not s.tied:
        q, d = head_table(root, s)
        params["head"] = {"q": q, "delta": d.reshape(1, s.vocab)}
    return params


def serve_params(seed: int, s: Shapes):
    """The whole model in the program's ``qp`` serve layout, made on the
    default device in one jitted call from the seed."""
    return _serve_params(seed_key(seed), s)

