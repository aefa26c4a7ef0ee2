"""Decoder-only transformer backbone (dense / audio / vlm / moe families).

Features per assigned-arch requirements: GQA (num_kv_heads < num_heads),
qk_norm (qwen3), QKV bias (qwen2/2.5), sliding-window attention (mixtral),
RoPE, tied embeddings, MoE FFN (phi3.5/mixtral), frontend-embedding prefix
([audio]/[vlm] stubs). Layers run under ``jax.lax.scan`` with stacked params
(compile once per layer — mandatory at 64L/512-device lowering scale) and
optional remat.

Every projection goes through ``quant_dense`` so the paper's W3A8 policy
applies: wq/wk/wv/wo + FFN are role 'hidden' (3-bit), embed role 'embed',
LM head role 'output' (8-bit, the paper's sensitive-layer rule).

``prefill`` and ``decode_step`` name their parts with ``jax.named_scope``
(``model.embed``, ``model.attn_qkv``, ``model.kv_write``,
``model.attention``, ``model.attn_out``, ``model.mlp``,
``model.final_norm``, ``model.readout``): the names reach the HLO ops'
metadata only, so a profiler trace can say which part a device op serves.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant_dense
from repro.core.precision import QuantPolicy
from repro.distributed.context import constrain
from repro.models import moe as moe_mod
from repro.models.attention import (decode_attention, prefill_attention,
                                    resolve_attn_mode, verify_attention)
from repro.models.layers import (apply_rope, embed_init, embed_lookup,
                                 head_rmsnorm, logits_readout, mlp_apply,
                                 mlp_init, rmsnorm, rmsnorm_init, rope_freqs)

__all__ = ["init", "forward", "init_cache", "prefill", "decode_step",
           "verify_step", "rollback_cache", "spec_state_snapshot",
           "insert_prefill", "insert_prefill_many"]


# --- init -----------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": quant_dense.init(ks[0], d, h * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": quant_dense.init(ks[1], d, kv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": quant_dense.init(ks[2], d, kv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": quant_dense.init(ks[3], h * hd, d, bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def _layer_init(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    p = {"ln1": rmsnorm_init(cfg.d_model), "ln2": rmsnorm_init(cfg.d_model),
         "attn": _attn_init(ks[0], cfg, dtype)}
    if cfg.family == "moe":
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)
    layers = jax.vmap(lambda k: _layer_init(k, cfg, dtype))(layer_keys)
    params = {"embed": embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
              "layers": layers, "final_norm": rmsnorm_init(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["head"] = quant_dense.init(ks[2], cfg.d_model, cfg.vocab_size,
                                          bias=False, dtype=dtype)
    return params


# --- attention block --------------------------------------------------------------

def _dget(deltas, *names):
    node = deltas
    for n in names:
        if node is None:
            return None
        node = node.get(n)
    return node


def _qkv(lp, h, cfg: ModelConfig, policy, deltas, positions, inv_freq,
         mm: str = "auto"):
    b, s, _ = h.shape
    hd = cfg.head_dim
    q = quant_dense.apply(lp["attn"]["wq"], h, policy=policy, role="hidden",
                          delta=_dget(deltas, "attn", "wq", "w"), mode=mm)
    k = quant_dense.apply(lp["attn"]["wk"], h, policy=policy, role="hidden",
                          delta=_dget(deltas, "attn", "wk", "w"), mode=mm)
    v = quant_dense.apply(lp["attn"]["wv"], h, policy=policy, role="hidden",
                          delta=_dget(deltas, "attn", "wv", "w"), mode=mm)
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(lp["attn"]["q_norm"]["scale"], q, cfg.norm_eps)
        k = head_rmsnorm(lp["attn"]["k_norm"]["scale"], k, cfg.norm_eps)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    return q, k, v


def _attn_out(lp, o, cfg, policy, deltas, b, s, mm: str = "auto"):
    o = o.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return quant_dense.apply(lp["attn"]["wo"], o, policy=policy, role="hidden",
                             delta=_dget(deltas, "attn", "wo", "w"), mode=mm)


def _ffn(lp, h, cfg: ModelConfig, policy, deltas, mm: str = "auto"):
    """Returns (out, aux_loss)."""
    if cfg.family == "moe":
        return moe_mod.moe_apply(lp["moe"], h, cfg, policy=policy,
                                 deltas=_dget(deltas, "moe"), matmul_mode=mm)
    out = mlp_apply(lp["mlp"], h, act=cfg.mlp_act, policy=policy,
                    deltas=_dget(deltas, "mlp"), matmul_mode=mm)
    return out, jnp.zeros((), jnp.float32)


def _layer_forward(lp, ld, h, cfg: ModelConfig, policy, positions, inv_freq,
                   attn_chunk: int, mm: str = "auto", attn_mode: str = "ref",
                   lengths=None):
    """``attn_mode``/``lengths`` select the prefill-attention path: 'kernel'
    is the blocked Pallas kernel with the per-row bucketed-prefill mask
    (j <= t AND j < lengths[row]); 'ref' (the training default) the chunked
    / SWA scans, causal-only."""
    b, s, _ = h.shape
    with jax.named_scope("model.attn_qkv"):
        hn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
        q, k, v = _qkv(lp, hn, cfg, policy, ld, positions, inv_freq, mm)
    with jax.named_scope("model.attention"):
        o = prefill_attention(q, k, v, lengths=lengths,
                              window=cfg.sliding_window or 0, mode=attn_mode,
                              chunk=min(attn_chunk, s))
    with jax.named_scope("model.attn_out"):
        h = h + _attn_out(lp, o, cfg, policy, ld, b, s, mm)
        h = constrain(h, "act")
    with jax.named_scope("model.mlp"):
        hn = rmsnorm(lp["ln2"], h, cfg.norm_eps)
        f, aux = _ffn(lp, hn, cfg, policy, ld, mm)
        h = constrain(h + f, "act")
    # K/V in the cache's stored layout, (B, S, KV*D)
    return h, aux, (k.reshape(b, s, -1), v.reshape(b, s, -1))


# --- full forward (train) ----------------------------------------------------------

def _embed_input(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                 policy, deltas, dtype):
    """Token embeddings, with frontend prefix for [audio]/[vlm] stubs."""
    h = embed_lookup(params["embed"], batch["tokens"], policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    if cfg.frontend is not None and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(dtype)
        h = jnp.concatenate([fe, h], axis=1)
    return h


def forward(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: ModelConfig, *, policy: QuantPolicy,
            deltas: Optional[Dict] = None, dtype=jnp.bfloat16,
            remat: str = "layer", attn_chunk: int = 1024,
            matmul_mode: str = "auto",
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training/eval forward. Returns (logits (B,S,V) fp32, aux_loss)."""
    h = _embed_input(params, batch, cfg, policy, deltas, dtype)
    h = constrain(h, "act")
    s = h.shape[1]
    positions = jnp.arange(s)[None, :]
    inv_freq = rope_freqs(cfg.head_dim, cfg.rope_theta)

    def body(carry, xs):
        hh, aux = carry
        lp, ld = xs
        hh, a, _ = _layer_forward(lp, ld, hh, cfg, policy, positions, inv_freq,
                                  attn_chunk, matmul_mode)
        return (hh, aux + a), None

    if remat != "none":
        body = jax.checkpoint(body, prevent_cse=False)
    ld = deltas.get("layers") if deltas else None
    (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                               (params["layers"], ld))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _logits(params, h, cfg, policy, deltas, matmul_mode)
    return logits, aux


def _logits(params, h, cfg, policy, deltas, mm: str = "auto"):
    return logits_readout(params, h, cfg, policy=policy,
                          embed_delta=_dget(deltas, "embed", "w"),
                          head_delta=_dget(deltas, "head", "w"),
                          matmul_mode=mm)


# --- serving: prefill + decode ------------------------------------------------------

def cache_len_for(cfg: ModelConfig, max_len: int) -> int:
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               quantized: bool = False):
    """KV cache, stacked over layers in the layout the attention kernels
    read: (L, B, S, KV*D), KV head j in lane block j. ``quantized``: int8
    entries + per-(layer,batch,position) fp32 scales (L, B, S) — the
    paper's on-chip-quantization principle applied to the decode cache,
    which dominates decode HBM traffic at long context (beyond-paper, §Perf
    H-kv8). Scales factor exactly through attention."""
    s = cache_len_for(cfg, max_len)
    shape = (cfg.num_layers, batch, s, cfg.num_kv_heads * cfg.head_dim)
    if quantized:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros((cfg.num_layers, batch, s), jnp.float32),
                "v_scale": jnp.zeros((cfg.num_layers, batch, s), jnp.float32),
                "len": jnp.zeros((), jnp.int32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.zeros((), jnp.int32)}


def _quantize_kv(x: jnp.ndarray):
    """(..., KV*D) -> (int8 values, (...) scales). Per-token absmax over
    every head of the token."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_names(cache):
    return ("k", "v") + (("k_scale", "v_scale") if "k_scale" in cache else ())


def _kv_write(kv, idx, k, v):
    """Write new K/V rows into the cache leaves ``kv`` at ``idx``, a tuple
    of index arrays that broadcast to the rows' leading dims; k/v are
    (..., KV, D). Quantized first for an int8 cache. Only the new rows are
    written: a carried cache is updated in place."""
    k = k.reshape(k.shape[:-2] + (-1,))
    v = v.reshape(v.shape[:-2] + (-1,))
    out = dict(kv)
    if "k_scale" in kv:
        k, ksc = _quantize_kv(k)
        v, vsc = _quantize_kv(v)
        out["k_scale"] = kv["k_scale"].at[idx].set(ksc)
        out["v_scale"] = kv["v_scale"].at[idx].set(vsc)
    out["k"] = kv["k"].at[idx].set(k.astype(kv["k"].dtype))
    out["v"] = kv["v"].at[idx].set(v.astype(kv["v"].dtype))
    return out


def prefill(params, batch, cfg: ModelConfig, *, policy: QuantPolicy,
            deltas: Optional[Dict] = None, dtype=jnp.bfloat16,
            attn_chunk: int = 1024, max_len: Optional[int] = None,
            quantize_cache: bool = False,
            lengths: Optional[jnp.ndarray] = None,
            matmul_mode: str = "auto", attn_mode: str = "auto"):
    """Run the prompt, build the KV cache. Returns (last_logits, cache).

    ``lengths`` (B,) enables right-padded multi-request prefill: row ``i``
    holds a prompt of true length ``lengths[i]`` left-aligned in the padded
    (B, S) token array. Causal attention means valid positions never see the
    padding; the returned logits are gathered at each row's last REAL token
    and ``cache["len"]`` is the per-row true length, so decode overwrites /
    masks the junk K/V at padded positions. Requires S <= cache length (the
    sliding-window ring-roll path is per-row-ambiguous under padding).

    ``attn_mode`` ("auto" | "kernel" | "ref") picks the prompt
    self-attention implementation — the blocked online-softmax Pallas
    kernel (``kernels.attn_prefill``: no (B, ..., S, S) score tensor in
    HBM, per-row length masking) or the chunked/SWA reference scans (see
    :func:`repro.models.attention.prefill_attention`).
    """
    attn_mode = resolve_attn_mode(attn_mode)
    with jax.named_scope("model.embed"):
        h = _embed_input(params, batch, cfg, policy, deltas, dtype)
    s = h.shape[1]
    max_len = max_len or s
    cs = cache_len_for(cfg, max_len)
    if lengths is not None and s > cs:
        raise ValueError(f"padded prefill length {s} exceeds cache length "
                         f"{cs}; per-row ring alignment is undefined")
    positions = jnp.arange(s)[None, :]
    inv_freq = rope_freqs(cfg.head_dim, cfg.rope_theta)

    def body(hh, xs):
        lp, ld = xs
        hh, _, (k, v) = _layer_forward(lp, ld, hh, cfg, policy, positions,
                                       inv_freq, attn_chunk, matmul_mode,
                                       attn_mode, lengths)
        # keep last `cs` positions (ring-start for SWA, whole seq otherwise)
        with jax.named_scope("model.kv_write"):
            return hh, (k[:, -cs:], v[:, -cs:])

    ld = deltas.get("layers") if deltas else None
    h, (ks, vs) = jax.lax.scan(body, h, (params["layers"], ld))
    with jax.named_scope("model.final_norm"):
        if lengths is not None:
            lengths = jnp.asarray(lengths, jnp.int32)
            h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
        else:
            h = h[:, -1:]
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    with jax.named_scope("model.readout"):
        logits = _logits(params, h, cfg, policy, deltas, matmul_mode)
    with jax.named_scope("model.kv_write"):
        if cs > ks.shape[2]:
            padw = ((0, 0), (0, 0), (0, cs - ks.shape[2]), (0, 0))
            ks = jnp.pad(ks, padw)
            vs = jnp.pad(vs, padw)
        elif cfg.sliding_window and s >= cs and s % cs:
            # ring-buffer invariant: token t lives at slot t % cs. The slice
            # put token s-cs+i at slot i; roll by s % cs so it sits at
            # (s+i) % cs.
            ks = jnp.roll(ks, s % cs, axis=2)
            vs = jnp.roll(vs, s % cs, axis=2)
        clen = jnp.asarray(s, jnp.int32) if lengths is None else lengths
        if quantize_cache:
            qk, sk = _quantize_kv(ks)
            qv, sv = _quantize_kv(vs)
            cache = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv,
                     "len": clen}
        else:
            cache = {"k": ks, "v": vs, "len": clen}
    return logits, cache


def _layer_loop(body, h, params, deltas, cache):
    """Scan ``body((h, kv), (layer params, layer deltas, layer index))``
    over the layers with the stacked K/V leaves in the carry, so each layer
    writes its new rows into the one cache in place and the attention
    kernels read it by layer index. Returns (h, kv)."""
    kv = {n: cache[n] for n in _kv_names(cache)}
    ld = deltas.get("layers") if deltas else None
    layers = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
    (h, kv), _ = jax.lax.scan(body, (h, kv), (params["layers"], ld, layers))
    return h, kv


def decode_step(params, cache, tokens: jnp.ndarray, cfg: ModelConfig, *,
                policy: QuantPolicy, deltas: Optional[Dict] = None,
                dtype=jnp.bfloat16, matmul_mode: str = "auto",
                attn_mode: str = "auto"):
    """One token for the whole batch. tokens: (B, 1) int32.

    Returns (logits (B,1,V), new_cache). The KV cache is a ring buffer for
    SWA archs (bounded window) and an append buffer otherwise; rope uses the
    absolute position so ring overwrites stay correct.

    ``cache["len"]`` may be a scalar (uniform batch, e.g. ``generate``) or a
    (B,) vector of per-row lengths (slot-major continuous batching: every row
    is an independent request at its own position).

    ``attn_mode`` ("auto" | "kernel" | "ref") picks the decode-attention
    implementation — the fused Pallas ``kernels.attn_decode`` kernel or the
    einsum reference (see :func:`repro.models.attention.decode_attention`);
    it reads the int8 cache (``k_scale`` present) either way.

    The stacked cache rides the layer loop's carry: layer ``l`` writes only
    its B new rows, at ``[l, rows, slot]``, and the kernel reads layer ``l``
    by index, so with the cache donated the tick updates it in place and
    never slices, relays out or writes back a layer.
    """
    b = tokens.shape[0]
    pos = jnp.broadcast_to(cache["len"], (b,)).astype(jnp.int32)   # (B,)
    with jax.named_scope("model.embed"):
        h = embed_lookup(params["embed"], tokens, policy=policy,
                         delta=_dget(deltas, "embed", "w"), dtype=dtype)
        h = constrain(h, "dec_act")
    inv_freq = rope_freqs(cfg.head_dim, cfg.rope_theta)
    positions = pos[:, None]                                       # (B, 1)
    cs = cache["k"].shape[2]
    slot = jnp.mod(pos, cs) if cfg.sliding_window else pos
    rows = jnp.arange(b)
    valid = jnp.minimum(pos + 1, cs)

    def body(carry, xs):
        hh, kv = carry
        lp, ld, layer = xs
        with jax.named_scope("model.attn_qkv"):
            hn = rmsnorm(lp["ln1"], hh, cfg.norm_eps)
            q, k, v = _qkv(lp, hn, cfg, policy, ld, positions, inv_freq,
                           matmul_mode)
        with jax.named_scope("model.kv_write"):
            kv = _kv_write(kv, (layer, rows, slot), k[:, 0], v[:, 0])
        with jax.named_scope("model.attention"):
            o = decode_attention(q, kv["k"], kv["v"], valid,
                                 kv.get("k_scale"), kv.get("v_scale"),
                                 layer=layer, mode=attn_mode)
        with jax.named_scope("model.attn_out"):
            hh = hh + _attn_out(lp, o, cfg, policy, ld, b, 1, matmul_mode)
        with jax.named_scope("model.mlp"):
            hn = rmsnorm(lp["ln2"], hh, cfg.norm_eps)
            f, _ = _ffn(lp, hn, cfg, policy, ld, matmul_mode)
            hh = hh + f
        return (hh, kv), None

    h, kv = _layer_loop(body, h, params, deltas, cache)
    new_cache = dict(kv, len=cache["len"] + 1)
    with jax.named_scope("model.final_norm"):
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    with jax.named_scope("model.readout"):
        logits = _logits(params, h, cfg, policy, deltas, matmul_mode)
    return logits, new_cache


def verify_step(params, cache, tokens: jnp.ndarray, cfg: ModelConfig, *,
                policy: QuantPolicy, deltas: Optional[Dict] = None,
                dtype=jnp.bfloat16, matmul_mode: str = "auto",
                attn_mode: str = "auto"):
    """Multi-token decode against the live cache — the speculative-decoding
    verify entry point. tokens: (B, T) int32, the T tokens to append
    (committed last token + T-1 draft tokens).

    Returns (logits (B, T, V), new_cache, trajectory=None): position ``t``'s
    logits are the distribution over the token FOLLOWING ``tokens[:, t]`` —
    exactly what ``decode_step`` would have produced after consuming
    ``tokens[:, :t+1]`` sequentially. K/V for all T positions are written
    into the cache (``len`` advances by T); rejected suffixes are undone with
    :func:`rollback_cache`. Attention uses the causal per-row masking of the
    bucketed-prefill path applied to the decode cache
    (:func:`repro.models.attention.verify_attention`); ``attn_mode``
    ("auto" | "kernel" | "ref") dispatches it between the blocked
    ``kernels.attn_prefill`` Pallas kernel (T = spec_k+1 query rows, no
    (B, ..., T, S) score tensor in HBM, per-row DMA skipping past the
    causal frontier) and the guarded masked-einsum reference. The trailing
    ``None`` is the rollback trajectory slot (only stateful families need
    one — see hybrid).
    """
    b, t = tokens.shape
    pos0 = jnp.broadcast_to(cache["len"], (b,)).astype(jnp.int32)  # (B,)
    h = embed_lookup(params["embed"], tokens, policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    h = constrain(h, "dec_act")
    inv_freq = rope_freqs(cfg.head_dim, cfg.rope_theta)
    positions = pos0[:, None] + jnp.arange(t)[None, :]             # (B, T)
    cs = cache["k"].shape[2]
    slot = jnp.mod(positions, cs) if cfg.sliding_window else positions
    rows = jnp.arange(b)[:, None]                                  # (B, 1)
    valid = jnp.minimum(positions + 1, cs)                         # (B, T)

    def body(carry, xs):
        hh, kv = carry
        lp, ld, layer = xs
        hn = rmsnorm(lp["ln1"], hh, cfg.norm_eps)
        q, k, v = _qkv(lp, hn, cfg, policy, ld, positions, inv_freq,
                       matmul_mode)
        kv = _kv_write(kv, (layer, rows, slot), k, v)
        o = verify_attention(q, kv["k"], kv["v"], valid, kv.get("k_scale"),
                             kv.get("v_scale"), layer=layer, mode=attn_mode)
        hh = hh + _attn_out(lp, o, cfg, policy, ld, b, t, matmul_mode)
        hn = rmsnorm(lp["ln2"], hh, cfg.norm_eps)
        f, _ = _ffn(lp, hn, cfg, policy, ld, matmul_mode)
        return (hh + f, kv), None

    h, kv = _layer_loop(body, h, params, deltas, cache)
    new_cache = dict(kv, len=cache["len"] + t)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _logits(params, h, cfg, policy, deltas, matmul_mode)
    return logits, new_cache, None


def _wipe_mask(tgt: jnp.ndarray, cur: jnp.ndarray, cs: int) -> jnp.ndarray:
    """(B, S) bool: cache slots holding positions in [tgt, cur) per row —
    the entries a rollback erases. Ring-aware: position ``p`` lives at slot
    ``p % cs``, so the wiped band is the cyclic interval starting at
    ``tgt % cs`` of width ``cur - tgt`` (rewinds never span more than the
    ring — the engine forbids speculating across a ring wrap)."""
    sidx = jnp.arange(cs)
    return (jnp.mod(sidx[None, :] - tgt[:, None], cs)
            < (cur - tgt)[:, None])


def spec_state_snapshot(cache):
    """The subtree a rollback must restore from per-step snapshots. The
    transformer-family cache is pure KV — a length rewind suffices — so
    there is nothing to snapshot."""
    return None


def rollback_cache(cache, slots, new_lens, trajectory=None):
    """Rewind rows ``slots`` (N,) of a slot-major cache to lengths
    ``new_lens`` (N,) — the speculative-decoding rejection primitive.

    Semantics: per selected row, ``len`` drops to ``new_lens`` (clamped to
    [0, current]; a zero-distance rewind is the identity) and the K/V
    entries + int8 per-token scales at the wiped positions are zeroed, so
    the rolled-back cache is exactly the cache that never saw the rejected
    tokens. Rows whose ``slots`` entry is out of range are dropped (the
    engine's padding convention); ``trajectory`` is accepted for signature
    parity (stateful families use it) and must be None here."""
    assert trajectory is None, "transformer-family cache has no state trajectory"
    b = cache["k"].shape[1]
    cur = jnp.broadcast_to(cache["len"], (b,)).astype(jnp.int32)
    tgt = cur.at[slots].set(jnp.asarray(new_lens, jnp.int32), mode="drop")
    tgt = jnp.clip(tgt, 0, cur)
    cs = cache["k"].shape[2]
    wipe = _wipe_mask(tgt, cur, cs)                                # (B, S)
    out = dict(cache)
    for name in ("k", "v"):
        out[name] = jnp.where(wipe[None, :, :, None], 0, cache[name])
    if "k_scale" in cache:
        for name in ("k_scale", "v_scale"):
            out[name] = jnp.where(wipe[None], 0, cache[name])
    out["len"] = tgt
    return out


def free_slots(cache, slots):
    """Zero rows ``slots`` (N,) of a slot-major cache and reset their
    ``len`` to 0 — the release primitive behind preemption, deadline
    cancellation and NaN quarantine. The freed rows are exactly the
    freshly-allocated state (so a later ``insert_prefill_many`` admission
    is indistinguishable from first use, and a quarantined row's
    non-finite K/V entries cannot linger). Entries with ``slots[i] >=
    batch`` are dropped (the engine's padding convention)."""
    out = dict(cache)
    for name in _kv_names(cache):          # leaves (L, slots, ...): axis 1
        out[name] = cache[name].at[:, slots].set(0, mode="drop")
    out["len"] = cache["len"].at[slots].set(0, mode="drop")
    return out


def insert_prefill(cache, slot, src):
    """Copy a single-request prefill cache (batch=1, same max_len) into row
    ``slot`` of a slot-major shared cache whose ``len`` is per-slot (slots,).

    ``slot`` may be a traced int32 scalar, so one jitted insert serves every
    slot without recompiling. Purely functional: returns the updated cache.
    """
    out = dict(cache)
    for name in ("k", "v"):
        out[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], src[name].astype(cache[name].dtype), slot, 1)
    if "k_scale" in cache:
        for name in ("k_scale", "v_scale"):
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], src[name], slot, 1)
    out["len"] = jax.lax.dynamic_update_slice(
        cache["len"], jnp.reshape(src["len"], (1,)).astype(cache["len"].dtype),
        (slot,))
    return out


def insert_prefill_many(cache, slot_map, src):
    """Scatter an N-row batched prefill cache into rows ``slot_map`` (N,) of
    a slot-major shared cache (per-slot ``len``). One jitted scatter admits
    every request at once; entries with ``slot_map[i] >= slots`` are dropped
    (JAX scatter OOB semantics) — the engine points padding rows there.
    """
    out = dict(cache)
    for name in _kv_names(cache):          # leaves (L, slots, ...): axis 1
        out[name] = cache[name].at[:, slot_map].set(
            src[name].astype(cache[name].dtype), mode="drop")
    out["len"] = cache["len"].at[slot_map].set(
        jnp.asarray(src["len"]).astype(cache["len"].dtype), mode="drop")
    return out
