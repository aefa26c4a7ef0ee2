"""Plain float32 reference of a Qwen2 decoder, for the benchmark's check.

Written from the architecture (Qwen2 technical report, arXiv:2407.10671, and
the published ``config.json``), not from the program: token embedding;
per layer RMSNorm, Q/K/V projections with bias, rotary embedding
(rotate-half, ``rope_theta``), grouped-query causal attention scaled by
1/sqrt(head_dim), output projection, residual, RMSNorm, SwiGLU MLP,
residual; a final RMSNorm; a readout through the embedding table when it is
tied, or through a head. Everything runs in float32 under
``default_matmul_precision("highest")``, a few requests at a time and
attention in blocks of queries, so that it fits one chip at 4k prompts.

The weights are the benchmark's (``bench.weights``), regenerated from the
seed one layer at a time and dequantized here. The serving policy's
quantization is reproduced as the policy defines it: 3-bit and 8-bit weight
levels times per-output-channel deltas, and the 8-bit dynamic activation
grid on the MLP's inner activation, whose absmax scale is taken per row of
each batched call. In the serving engine a prompt is prefilled as one row
right-padded with token 0 to its admission bucket, so the prompt's scale
runs over those padded positions (which see only the real prompt), and each
later token is decoded alone, so it has a scale of its own.

``score`` teacher-forces each request's served tokens through one pass (the
padded prompt, then the served tokens at positions ``P, P+1, ...``) and
returns, for every served token, the reference's best logit, its logit for
the served token, and its own first choice. ``precision="fp8"`` is the
control: the same pass with every activation rounded to float8_e4m3fn, one
step below the bfloat16 the configuration serves in. ``precision="kv8"``
rounds only the cached keys and values to int8 with a per-token absmax
scale, as the program's ``kv_bits=8`` path does.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

PRECISIONS = ("f32", "fp8", "kv8")
# requests through the layers at once, and queries per attention block:
# at 4 x 4223 positions a block's float32 scores are 4 x 12 x 512 x 4223 x
# 4 B = 0.42 GB, where all queries at once would be 3.4 GB a layer
ROWS_PER_PASS = 4
Q_BLOCK = 512


def _round(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _kv_round(x, precision):
    """x (B, S, KV, D): keys or values as the cache holds them."""
    if precision == "kv8":
        amax = jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True)
        scale = jnp.maximum(amax, 1e-6) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return _round(x, precision)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (B, S, H, D), pos (B, S): rotate-half rotary embedding."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[..., None].astype(jnp.float32) * inv          # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _act8(h, region_a, row_a):
    """8-bit dynamic activation grid: positions in the padded prompt
    (``row_a``, (B, S) bool) share their row's absmax; every other position
    (``region_a`` false) has its own."""
    a = jnp.abs(h)
    per_pos = jnp.max(a, -1)                                   # (B, S)
    per_row = jnp.max(jnp.where(row_a, per_pos, 0.0), -1, keepdims=True)
    amax = jnp.where(region_a, per_row, per_pos)[..., None]
    scale = jnp.maximum(amax / 127.0, 1e-12)
    return jnp.clip(jnp.round(h / scale), -127, 127) * scale


def _attend(qg, k, v, mask, r):
    """Causal grouped-query attention of the queries ``qg`` (B, Q, KV, G, D)
    over every key and value (B, S, KV, D); ``mask`` (B, Q, S)."""
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, k)
    sc = jnp.where(mask[:, None, None], sc, -jnp.inf)
    p = r(jax.nn.softmax(sc, -1))
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


def _attention(qg, k, v, mask, r, q_block):
    """``_attend`` over blocks of ``q_block`` queries, one block at a time,
    so that the float32 scores held at once are (B, KV, G, q_block, S) and
    not (B, KV, G, S, S); each query's arithmetic is the same either way.
    ``q_block`` None: all queries in one block."""
    b, s = qg.shape[:2]
    if q_block is None or q_block >= s:
        return _attend(qg, k, v, mask, r)
    nb = -(-s // q_block)
    pad = nb * q_block - s
    # padded queries see every key, so that no softmax is over nothing
    qp = jnp.pad(qg, ((0, 0), (0, pad)) + ((0, 0),) * 3)
    mp = jnp.pad(mask, ((0, 0), (0, pad), (0, 0)), constant_values=True)
    qb = jnp.swapaxes(qp.reshape(b, nb, q_block, *qg.shape[2:]), 0, 1)
    mb = jnp.swapaxes(mp.reshape(b, nb, q_block, mask.shape[-1]), 0, 1)
    ob = jax.lax.map(lambda a: _attend(a[0], k, v, a[1], r), (qb, mb))
    return jnp.swapaxes(ob, 0, 1).reshape(b, nb * q_block,
                                          *ob.shape[3:])[:, :s]


@partial(jax.jit, static_argnames=("dims", "precision", "q_block"))
def _layer(x, lw, pos, mask, region_a, row_a, *, dims, precision,
           q_block=None):
    heads, kv_heads, hd, eps, theta = dims
    b, s, _ = x.shape
    r = partial(_round, precision=precision)
    hn = r(_rmsnorm(x, lw["ln1"], eps))
    q = r(hn @ lw["wq"] + lw["bq"]).reshape(b, s, heads, hd)
    k = r(hn @ lw["wk"] + lw["bk"]).reshape(b, s, kv_heads, hd)
    v = r(hn @ lw["wv"] + lw["bv"]).reshape(b, s, kv_heads, hd)
    q, k = r(_rope(q, pos, theta)), _rope(k, pos, theta)
    k, v = _kv_round(k, precision), _kv_round(v, precision)
    g = heads // kv_heads
    qg = q.reshape(b, s, kv_heads, g, hd) / np.sqrt(hd)
    o = r(_attention(qg, k, v, mask, r, q_block).reshape(b, s, heads * hd))
    x = r(x + r(o @ lw["wo"]))
    hn = r(_rmsnorm(x, lw["ln2"], eps))
    h = r(jax.nn.silu(r(hn @ lw["gate"])) * r(hn @ lw["up"]))
    h = _act8(h, region_a, row_a)
    return r(x + r(h @ lw["down"]))


@partial(jax.jit, static_argnames=("s",))
def _layer_weights(root, layer, s: W.Shapes) -> dict:
    """Dequantized float32 weights of one layer, from the seed."""
    lw = {}
    for name, k, n, bias in s.matrices():
        q, d, bb = W.matrix(root, name, layer, k, n, bias)
        lw[name] = q.astype(jnp.float32) * d
        if bias:
            lw["b" + name[1]] = bb
    lw["ln1"] = W.norm_scale(root, "ln1", layer, s.d)
    lw["ln2"] = W.norm_scale(root, "ln2", layer, s.d)
    return lw


class Reference:
    """The reference for one configuration file's ``model`` group and one
    seed."""

    def __init__(self, model: dict, seed: int):
        self.s = W.Shapes.of(model)
        self.eps = float(model["rms_norm_eps"])
        self.theta = float(model["rope_theta"])
        self.root = W.seed_key(seed)

    @staticmethod
    def _layout(reqs, shape=None):
        """Batch layout of the teacher-forced pass. ``reqs``: (prompt,
        served tokens, admission bucket) per request. Region A holds each
        padded prompt (width ``amax``), region B the served tokens but the
        last, at positions P, P+1, ... ``shape`` (rows, amax, bmax) pads
        the batch to fixed sizes, so that one compilation serves every
        run of a cell."""
        n, amax, bmax = _shape(reqs, shape)
        reqs = list(reqs) + [([1], [1], 8)] * (n - len(reqs))
        width = amax + bmax
        toks = np.zeros((n, width), np.int32)
        pos = np.zeros((n, width), np.int32)
        region_a = np.zeros((n, width), bool)
        region_a[:, :amax] = True
        row_a = np.zeros((n, width), bool)
        mask = np.zeros((n, width, width), bool)
        score_at = np.zeros((n, bmax + 1), np.int32)
        for i, (prompt, out, bk) in enumerate(reqs):
            p, t = len(prompt), len(out) - 1
            toks[i, :p] = prompt
            toks[i, amax:amax + t] = out[:-1]
            pos[i, :amax] = np.arange(amax)
            pos[i, amax:] = p + np.arange(bmax)
            row_a[i, :bk] = True
            qa = np.arange(amax)
            mask[i, :amax, :p] = np.arange(p)[None, :] <= qa[:, None]
            mask[i, amax:, :p] = True
            mask[i, amax:, amax:] = np.tril(np.ones((bmax, bmax), bool))
            score_at[i, 0] = p - 1
            score_at[i, 1:] = amax + np.arange(bmax)
        return toks, pos, mask, region_a, row_a, score_at

    def hidden(self, reqs, precision: str = "f32", shape=None,
               rows: int = ROWS_PER_PASS,
               q_block: int = Q_BLOCK) -> jax.Array:
        """Final-normed hidden states at every scored position,
        (rows, bmax + 1, D) float32. The batch goes through the layers
        ``rows`` requests at a time, and attention ``q_block`` queries at a
        time (None: all at once), so that what is held at once fits beside
        nothing else on one chip at the cell's longest prompts."""
        assert precision in PRECISIONS, precision
        layout = self._layout(reqs, shape)
        n = layout[0].shape[0]
        return jnp.concatenate([
            self._hidden_rows([a[i:i + rows] for a in layout], precision,
                              q_block) for i in range(0, n, rows)])

    def _hidden_rows(self, layout, precision, q_block) -> jax.Array:
        s = self.s
        toks, pos, mask, region_a, row_a, score_at = layout
        r = partial(_round, precision=precision)
        with jax.default_matmul_precision("highest"):
            eq, ed = W.embed_table(self.root, s)
            x = r(eq[jnp.asarray(toks)].astype(jnp.float32) * ed)
            del eq
            dims = (s.heads, s.kv_heads, s.head_dim, self.eps, self.theta)
            args = tuple(jnp.asarray(a) for a in (pos, mask, region_a, row_a))
            for layer in range(s.layers):
                x = _layer(x, _layer_weights(self.root, layer, s), *args,
                           dims=dims, precision=precision, q_block=q_block)
            x = jnp.take_along_axis(x, jnp.asarray(score_at)[..., None], 1)
            fn = W.norm_scale(self.root, "final_norm", 0, s.d)
            return r(_rmsnorm(x, fn, self.eps))

    def _readout_blocks(self):
        """Yield (first vocabulary id, (D, Vb) float32 readout block)."""
        s = self.s
        if s.tied:
            q, d = W.embed_table(self.root, s)              # (V, D), (D,)
        else:
            q, d = W.head_table(self.root, s)               # (D, V), (V,)
        nb = np.gcd(s.vocab, 64)
        vb = s.vocab // nb
        for i in range(nb):
            lo = i * vb
            if s.tied:
                yield lo, (q[lo:lo + vb].astype(jnp.float32) * d).T
            else:
                yield lo, q[:, lo:lo + vb].astype(jnp.float32) * d[lo:lo + vb]

    def logits(self, reqs, precision: str = "f32") -> jax.Array:
        """Whole logits at every scored position (requests, bmax + 1, V):
        for tests at small vocabularies."""
        h = self.hidden(reqs, precision)
        with jax.default_matmul_precision("highest"):
            return jnp.concatenate([h @ blk for _, blk in
                                    self._readout_blocks()], -1)

    def readout(self, h: jax.Array, tokens: jax.Array):
        """Over the whole vocabulary, in blocks: per scored position the
        best logit, the logit of ``tokens`` and the first choice (lowest id
        among ties)."""
        flat = h.reshape(-1, h.shape[-1])
        tok = tokens.reshape(-1)
        best = jnp.full(tok.shape, -jnp.inf)
        arg = jnp.zeros(tok.shape, jnp.int32)
        at = jnp.zeros(tok.shape)
        with jax.default_matmul_precision("highest"):
            for lo, blk in self._readout_blocks():
                best, arg, at = _readout_step(flat, blk, lo, tok, best, arg,
                                              at)
        shape = tokens.shape
        return best.reshape(shape), at.reshape(shape), arg.reshape(shape)


@jax.jit
def _readout_step(h, blk, lo, tok, best, arg, at):
    logits = h @ blk                                         # (N, Vb)
    m = jnp.max(logits, -1)
    a = jnp.argmax(logits, -1).astype(jnp.int32) + lo
    inside = (tok >= lo) & (tok < lo + blk.shape[1])
    hit = jnp.take_along_axis(
        logits, jnp.clip(tok - lo, 0, blk.shape[1] - 1)[:, None], 1)[:, 0]
    arg = jnp.where(m > best, a, arg)
    return (jnp.maximum(best, m), arg, jnp.where(inside, hit, at))


def _shape(reqs, shape=None) -> Tuple[int, int, int]:
    """(rows, amax, bmax) of the layout: ``shape`` where given, else the
    least that holds ``reqs``."""
    need = (len(reqs), max(bk for _, _, bk in reqs),
            max(max(len(o) - 1, 1) for _, o, _ in reqs))
    if shape is None:
        return need
    assert all(a >= b for a, b in zip(shape, need)), (shape, need)
    return tuple(shape)


def served_targets(reqs, shape=None) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens (rows, bmax + 1), valid mask) of the served tokens at the
    scored positions of :meth:`Reference._layout`."""
    n, _, bmax = _shape(reqs, shape)
    tok = np.zeros((n, bmax + 1), np.int32)
    valid = np.zeros_like(tok, bool)
    for i, (_, out, _) in enumerate(reqs):
        tok[i, :len(out)] = out
        valid[i, :len(out)] = True
    return tok, valid


def gaps(model: dict, seed: int, reqs: Sequence[Tuple[List[int], List[int],
                                                      int]],
         controls: Sequence[str] = (), shape=None) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, and the same for each control precision's own first
    choices on the same prompts and tokens. Returns {"served": float,
    "<control>": float, "tokens": n}. ``shape``: see ``Reference._layout``."""
    ref = Reference(model, seed)
    tok, valid = served_targets(reqs, shape)
    tok, valid_j = jnp.asarray(tok), jnp.asarray(valid)
    h = ref.hidden(reqs, shape=shape)
    best, at, _ = ref.readout(h, tok)
    out = {"tokens": int(valid.sum()),
           "served": float(jnp.max(jnp.where(valid_j, best - at, 0.0)))}
    for c in controls:
        hc = ref.hidden(reqs, c, shape)
        _, _, choice = ref.readout(hc, tok)
        del hc
        _, at_c, _ = ref.readout(h, choice)
        out[c] = float(jnp.max(jnp.where(valid_j, best - at_c, 0.0)))
    return out
