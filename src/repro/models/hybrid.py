"""Zamba2-style hybrid: Mamba2 backbone + ONE shared attention block applied
every ``attn_every`` layers (arXiv:2411.15242).

Layout: ``num_layers = n_groups * attn_every + n_tail``. Each group = a scan
over ``attn_every`` mamba blocks followed by the shared transformer block
(same weights every application — closed over, not scanned). Decode keeps one
KV cache per application (n_groups caches) + per-layer mamba states.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant_dense
from repro.core.precision import QuantPolicy
from repro.distributed.context import constrain
from repro.models import mamba2, transformer
from repro.models.attention import decode_attention, verify_attention
from repro.models.layers import (embed_init, embed_lookup, logits_readout,
                                 rmsnorm, rmsnorm_init)

__all__ = ["init", "forward", "init_cache", "prefill", "decode_step",
           "verify_step", "rollback_cache", "spec_state_snapshot",
           "insert_prefill", "insert_prefill_many"]


def _unit_layer(kv):
    """One group's K/V leaves (B, S, KV*D) as the attention entries' stacked
    operands: a leading unit layer axis (a free view), read at layer 0.
    Returns (k, v, k_scale, v_scale), the scales None for a float cache."""
    return (kv["k"][None], kv["v"][None],
            kv["k_scale"][None] if "k_scale" in kv else None,
            kv["v_scale"][None] if "v_scale" in kv else None)


def _counts(cfg: ModelConfig) -> Tuple[int, int]:
    n_groups = cfg.num_layers // cfg.attn_every
    n_tail = cfg.num_layers % cfg.attn_every
    return n_groups, n_tail


def _dget(deltas, *names):
    node = deltas
    for n in names:
        if node is None:
            return None
        node = node.get(n)
    return node


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    n_groups, n_tail = _counts(cfg)
    ks = jax.random.split(key, 5)
    gkeys = jax.random.split(ks[0], n_groups * cfg.attn_every).reshape(
        n_groups, cfg.attn_every, 2)
    groups = jax.vmap(jax.vmap(lambda k: mamba2.block_init(k, cfg, dtype)))(gkeys)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
        "groups": groups,
        "shared": transformer._layer_init(ks[2], cfg, dtype),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if n_tail:
        tkeys = jax.random.split(ks[3], n_tail)
        params["tail"] = jax.vmap(lambda k: mamba2.block_init(k, cfg, dtype))(tkeys)
    if not cfg.tie_embeddings:
        params["head"] = quant_dense.init(ks[4], cfg.d_model, cfg.vocab_size,
                                          bias=False, dtype=dtype)
    return params


def _mamba_scan(stack, dstack, h, cfg, policy, chunk, remat: str,
                return_state: bool = False, lengths=None, mm: str = "auto"):
    def body(hh, xs):
        lp, ld = xs
        if return_state:
            out, st = mamba2.block_apply(lp, hh, cfg, policy=policy, deltas=ld,
                                         chunk=chunk, return_state=True,
                                         lengths=lengths, matmul_mode=mm)
            return out, st
        return mamba2.block_apply(lp, hh, cfg, policy=policy, deltas=ld,
                                  chunk=chunk, lengths=lengths,
                                  matmul_mode=mm), None

    if remat != "none":
        body = jax.checkpoint(body, prevent_cse=False)
    return jax.lax.scan(body, h, (stack, dstack))


def forward(params, batch, cfg: ModelConfig, *, policy: QuantPolicy,
            deltas: Optional[Dict] = None, dtype=jnp.bfloat16,
            remat: str = "layer", attn_chunk: int = 1024,
            chunk: int = mamba2.DEFAULT_CHUNK,
            matmul_mode: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    n_groups, n_tail = _counts(cfg)
    h = embed_lookup(params["embed"], batch["tokens"], policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    h = constrain(h, "act")
    s = h.shape[1]
    positions = jnp.arange(s)[None, :]
    inv_freq = transformer.rope_freqs(cfg.head_dim, cfg.rope_theta)
    shared, sdelta = params["shared"], _dget(deltas, "shared")

    def group_body(hh, xs):
        gp, gd = xs
        hh, _ = _mamba_scan(gp, gd, hh, cfg, policy, chunk, remat,
                            mm=matmul_mode)
        hh, _, _ = transformer._layer_forward(shared, sdelta, hh, cfg, policy,
                                              positions, inv_freq, attn_chunk,
                                              matmul_mode)
        return hh, None

    gd = _dget(deltas, "groups")
    h, _ = jax.lax.scan(group_body, h, (params["groups"], gd))
    if n_tail:
        h, _ = _mamba_scan(params["tail"], _dget(deltas, "tail"), h, cfg,
                           policy, chunk, remat, mm=matmul_mode)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return (_logits(params, h, cfg, policy, deltas, matmul_mode),
            jnp.zeros((), jnp.float32))


def _logits(params, h, cfg, policy, deltas, mm: str = "auto"):
    return logits_readout(params, h, cfg, policy=policy,
                          embed_delta=_dget(deltas, "embed", "w"),
                          head_delta=_dget(deltas, "head", "w"),
                          matmul_mode=mm)


# --- serving -----------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               quantized: bool = False):
    """Decode state: per-layer mamba states + one KV cache per shared-block
    application, (G, B, S, KV*D) in the transformer family's stored layout.
    ``quantized``: int8 KV entries + per-(group,batch,position) fp32 scales
    — the transformer family's §Perf H-kv8 cache, extended to the hybrid
    attention applications (half the KV bytes per slot)."""
    n_groups, n_tail = _counts(cfg)
    one = mamba2.block_state(cfg, batch)
    kv_shape = (n_groups, batch, max_len, cfg.num_kv_heads * cfg.head_dim)
    if quantized:
        kv = {"k": jnp.zeros(kv_shape, jnp.int8),
              "v": jnp.zeros(kv_shape, jnp.int8),
              "k_scale": jnp.zeros((n_groups, batch, max_len), jnp.float32),
              "v_scale": jnp.zeros((n_groups, batch, max_len), jnp.float32)}
    else:
        kv = {"k": jnp.zeros(kv_shape, dtype), "v": jnp.zeros(kv_shape, dtype)}
    state = {
        "groups": jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x, (n_groups, cfg.attn_every) + x.shape), one),
        "kv": kv,
        "len": jnp.zeros((), jnp.int32),
    }
    if n_tail:
        state["tail"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_tail,) + x.shape), one)
    return state


def prefill(params, batch, cfg: ModelConfig, *, policy: QuantPolicy,
            deltas=None, dtype=jnp.bfloat16, attn_chunk: int = 1024,
            max_len: Optional[int] = None, chunk: int = mamba2.DEFAULT_CHUNK,
            quantize_cache: bool = False,
            lengths: Optional[jnp.ndarray] = None,
            matmul_mode: str = "auto", attn_mode: str = "auto"):
    """``lengths`` (B,) enables right-padded multi-request prefill: mamba
    blocks mask the SSD recurrence / gather the true conv tail (see
    mamba2.block_apply), attention is causal so real positions never see the
    padding, and the junk K/V written at padded slots is masked out by decode
    (per-row ``len``) until overwritten. ``quantize_cache`` stores the KV
    cache as int8 + per-token scales (see :func:`init_cache`). ``attn_mode``
    dispatches the shared-block prompt attention between the blocked Pallas
    kernel and the chunked reference (see
    :func:`repro.models.attention.prefill_attention`)."""
    from repro.models.attention import resolve_attn_mode
    attn_mode = resolve_attn_mode(attn_mode)
    n_groups, n_tail = _counts(cfg)
    bsz, s = batch["tokens"].shape
    max_len = max_len or s
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
        if s > max_len:
            raise ValueError(f"padded prefill length {s} exceeds max_len "
                             f"{max_len}")
    h = embed_lookup(params["embed"], batch["tokens"], policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    positions = jnp.arange(s)[None, :]
    inv_freq = transformer.rope_freqs(cfg.head_dim, cfg.rope_theta)
    shared, sdelta = params["shared"], _dget(deltas, "shared")

    def group_body(hh, xs):
        gp, gd = xs
        hh, mstates = _mamba_scan(gp, gd, hh, cfg, policy, chunk, "none",
                                  return_state=True, lengths=lengths,
                                  mm=matmul_mode)
        hh, _, (k, v) = transformer._layer_forward(
            shared, sdelta, hh, cfg, policy, positions, inv_freq, attn_chunk,
            matmul_mode, attn_mode, lengths)
        return hh, (mstates, k, v)

    gd = _dget(deltas, "groups")
    h, (gstates, ks, vs) = jax.lax.scan(group_body, h, (params["groups"], gd))
    state = init_cache(cfg, bsz, max_len, dtype, quantized=quantize_cache)
    state["groups"] = gstates
    pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
    ks = jnp.pad(ks, pad)
    vs = jnp.pad(vs, pad)
    if quantize_cache:
        qk, sk = transformer._quantize_kv(ks)
        qv, sv = transformer._quantize_kv(vs)
        state["kv"] = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    else:
        state["kv"]["k"] = ks.astype(dtype)
        state["kv"]["v"] = vs.astype(dtype)
    if n_tail:
        h, tstates = _mamba_scan(params["tail"], _dget(deltas, "tail"), h, cfg,
                                 policy, chunk, "none", return_state=True,
                                 lengths=lengths, mm=matmul_mode)
        state["tail"] = tstates
    if lengths is not None:
        h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
        state["len"] = lengths
    else:
        h = h[:, -1:]
        state["len"] = jnp.asarray(s, jnp.int32)
    hln = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return _logits(params, hln, cfg, policy, deltas, matmul_mode), state


def decode_step(params, state, tokens: jnp.ndarray, cfg: ModelConfig, *,
                policy: QuantPolicy, deltas=None, dtype=jnp.bfloat16,
                matmul_mode: str = "auto", attn_mode: str = "auto"):
    """One token for the whole batch. ``state["len"]`` may be scalar (uniform
    batch) or (B,) per-row lengths (slot-major continuous batching).

    ``attn_mode`` picks the decode-attention implementation (fused Pallas
    kernel vs einsum reference — see
    :func:`repro.models.attention.decode_attention`); an int8 KV state
    (``k_scale`` present, from ``prefill(quantize_cache=True)``) is read
    directly with its per-token scales either way."""
    n_groups, n_tail = _counts(cfg)
    b = tokens.shape[0]
    pos = jnp.broadcast_to(state["len"], (b,)).astype(jnp.int32)   # (B,)
    h = embed_lookup(params["embed"], tokens, policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    inv_freq = transformer.rope_freqs(cfg.head_dim, cfg.rope_theta)
    positions = pos[:, None]                                       # (B, 1)
    rows = jnp.arange(b)
    shared, sdelta = params["shared"], _dget(deltas, "shared")

    def mamba_body(hh, xs):
        lp, ld, st = xs
        hh, st2 = mamba2.block_decode(lp, hh, st, cfg, policy=policy,
                                      deltas=ld, matmul_mode=matmul_mode)
        return hh, st2

    def group_body(hh, xs):
        gp, gd, gst, kv = xs
        hh, gst2 = jax.lax.scan(mamba_body, hh, (gp, gd, gst))
        hn = rmsnorm(shared["ln1"], hh, cfg.norm_eps)
        q, k, v = transformer._qkv(shared, hn, cfg, policy, sdelta, positions,
                                   inv_freq, matmul_mode)
        kv = transformer._kv_write(kv, (rows, pos), k[:, 0], v[:, 0])
        kc, vc, ks_, vs_ = _unit_layer(kv)
        o = decode_attention(q, kc, vc, pos + 1, ks_, vs_, mode=attn_mode)
        hh = hh + transformer._attn_out(shared, o, cfg, policy, sdelta, b, 1,
                                        matmul_mode)
        hn = rmsnorm(shared["ln2"], hh, cfg.norm_eps)
        f, _ = transformer._ffn(shared, hn, cfg, policy, sdelta, matmul_mode)
        return hh + f, (gst2, kv)

    gd = _dget(deltas, "groups")
    h, (gstates, new_kv) = jax.lax.scan(
        group_body, h, (params["groups"], gd, state["groups"], state["kv"]))
    new_state = dict(state)
    new_state["groups"] = gstates
    new_state["kv"] = new_kv
    if n_tail:
        h, tstates = jax.lax.scan(
            mamba_body, h, (params["tail"], _dget(deltas, "tail"), state["tail"]))
        new_state["tail"] = tstates
    new_state["len"] = state["len"] + 1
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return _logits(params, h, cfg, policy, deltas, matmul_mode), new_state


def _mamba_verify(lp, ld, h_bt, st0, cfg, policy, matmul_mode):
    """One mamba layer over T tokens via the EXACT decode recurrence
    (``block_decode`` scanned token-by-token, so verify states are bitwise
    the states sequential decode would carry). h_bt: (B, T, D). Returns
    (out (B, T, D), final_state, per-step state trajectory (T, ...))."""
    def step(st, h_t):
        h2, st2 = mamba2.block_decode(lp, h_t, st, cfg, policy=policy,
                                      deltas=ld, matmul_mode=matmul_mode)
        return st2, (h2[:, 0], st2)

    st_final, (hs, straj) = jax.lax.scan(
        step, st0, h_bt.transpose(1, 0, 2)[:, :, None, :])
    return hs.transpose(1, 0, 2), st_final, straj


def verify_step(params, state, tokens: jnp.ndarray, cfg: ModelConfig, *,
                policy: QuantPolicy, deltas=None, dtype=jnp.bfloat16,
                matmul_mode: str = "auto", attn_mode: str = "auto"):
    """Multi-token decode against the live state — the speculative verify
    entry point. tokens: (B, T). Returns (logits (B, T, V), new_state,
    trajectory).

    The mamba blocks advance by the exact per-token decode recurrence; the
    shared attention block appends T K/V entries per application and masks
    the draft positions causally against each other and the prefix
    (:func:`repro.models.attention.verify_attention` — the bucketed-prefill
    masking rule on the decode cache). Because SSM states cannot be rewound
    arithmetically, ``trajectory`` snapshots the {"groups"[, "tail"]} state
    subtree after each of the T tokens (leading axis T+1, entry ``j`` =
    state after consuming ``tokens[:, :j]``); :func:`rollback_cache` selects
    each row's accepted entry from it."""
    n_groups, n_tail = _counts(cfg)
    b, t = tokens.shape
    pos0 = jnp.broadcast_to(state["len"], (b,)).astype(jnp.int32)  # (B,)
    h = embed_lookup(params["embed"], tokens, policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    inv_freq = transformer.rope_freqs(cfg.head_dim, cfg.rope_theta)
    positions = pos0[:, None] + jnp.arange(t)[None, :]             # (B, T)
    rows = jnp.arange(b)[:, None]                                  # (B, 1)
    shared, sdelta = params["shared"], _dget(deltas, "shared")

    def mamba_body(hh, xs):
        lp, ld, st = xs
        out, st_final, straj = _mamba_verify(lp, ld, hh, st, cfg, policy,
                                             matmul_mode)
        return out, (st_final, straj)

    def group_body(hh, xs):
        gp, gd, gst, kv = xs
        hh, (gst2, gtraj) = jax.lax.scan(mamba_body, hh, (gp, gd, gst))
        hn = rmsnorm(shared["ln1"], hh, cfg.norm_eps)
        q, k, v = transformer._qkv(shared, hn, cfg, policy, sdelta, positions,
                                   inv_freq, matmul_mode)
        kv = transformer._kv_write(kv, (rows, positions), k, v)
        kc, vc, ks_, vs_ = _unit_layer(kv)
        o = verify_attention(q, kc, vc, positions + 1, ks_, vs_,
                             mode=attn_mode)
        hh = hh + transformer._attn_out(shared, o, cfg, policy, sdelta, b, t,
                                        matmul_mode)
        hn = rmsnorm(shared["ln2"], hh, cfg.norm_eps)
        f, _ = transformer._ffn(shared, hn, cfg, policy, sdelta, matmul_mode)
        return hh + f, (gst2, gtraj, kv)

    gd = _dget(deltas, "groups")
    h, (gstates, gtraj, new_kv) = jax.lax.scan(
        group_body, h, (params["groups"], gd, state["groups"], state["kv"]))
    new_state = dict(state)
    new_state["groups"] = gstates
    new_state["kv"] = new_kv
    # trajectory leaves carry the snapshot axis FIRST: entry j = state after
    # consuming j tokens (entry 0 = the pre-verify state)
    trajectory = {"groups": jax.tree_util.tree_map(
        lambda init, tr: jnp.concatenate([init[None],
                                          jnp.moveaxis(tr, 2, 0)]),
        state["groups"], gtraj)}
    if n_tail:
        h, (tstates, ttraj) = jax.lax.scan(
            mamba_body, h, (params["tail"], _dget(deltas, "tail"),
                            state["tail"]))
        new_state["tail"] = tstates
        trajectory["tail"] = jax.tree_util.tree_map(
            lambda init, tr: jnp.concatenate([init[None],
                                              jnp.moveaxis(tr, 1, 0)]),
            state["tail"], ttraj)
    new_state["len"] = state["len"] + t
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _logits(params, h, cfg, policy, deltas, matmul_mode)
    return logits, new_state, trajectory


def spec_state_snapshot(state):
    """The state subtree a rollback must restore from per-step snapshots:
    the mamba SSM/conv states ({"groups"[, "tail"]}). The KV part rewinds by
    length like the transformer family and needs no snapshot."""
    snap = {"groups": state["groups"]}
    if "tail" in state:
        snap["tail"] = state["tail"]
    return snap


def _select_state(traj_leaf, j, baxis: int):
    """Per-row snapshot select: traj_leaf (T+1, ...) with the batch axis at
    ``baxis``; j (B,) picks each row's snapshot index. Returns the leaf with
    the snapshot axis removed (batch back at ``baxis - 1``)."""
    moved = jnp.moveaxis(traj_leaf, baxis, 0)          # (B, T+1, ...)
    sel = jax.vmap(lambda tr, idx: tr[idx])(moved, j)  # (B, ...)
    return jnp.moveaxis(sel, 0, baxis - 1)


def rollback_cache(state, slots, new_lens, trajectory=None):
    """Rewind rows ``slots`` (N,) of a slot-major hybrid state to lengths
    ``new_lens`` (N,). KV entries + int8 scales at wiped positions are
    zeroed and ``len`` drops (clamped to [0, current]; zero-distance rewind
    and out-of-range ``slots`` entries are identities), exactly as in the
    transformer family. The mamba states are restored from ``trajectory``
    (from :func:`verify_step` or a draft-chain snapshot stack): row ``b``
    gets snapshot ``new_len[b] - (current_len[b] - T)`` — rows rewound to
    the full current length keep the final (= current) state. With
    ``trajectory=None`` the mamba states are left untouched, which is only
    sound if they never advanced past ``new_lens``."""
    b = state["kv"]["k"].shape[1]
    cur = jnp.broadcast_to(state["len"], (b,)).astype(jnp.int32)
    tgt = cur.at[slots].set(jnp.asarray(new_lens, jnp.int32), mode="drop")
    tgt = jnp.clip(tgt, 0, cur)
    s = state["kv"]["k"].shape[2]
    wipe = transformer._wipe_mask(tgt, cur, s)                     # (B, S)
    out = dict(state)
    kv = dict(state["kv"])
    for name in ("k", "v"):
        kv[name] = jnp.where(wipe[None, :, :, None], 0, kv[name])
    if "k_scale" in kv:
        for name in ("k_scale", "v_scale"):
            kv[name] = jnp.where(wipe[None], 0, kv[name])
    out["kv"] = kv
    if trajectory is not None:
        t_steps = jax.tree_util.tree_leaves(trajectory)[0].shape[0] - 1
        j = jnp.clip(tgt - (cur - t_steps), 0, t_steps)
        out["groups"] = jax.tree_util.tree_map(
            lambda tr: _select_state(tr, j, 3), trajectory["groups"])
        if "tail" in trajectory:
            out["tail"] = jax.tree_util.tree_map(
                lambda tr: _select_state(tr, j, 2), trajectory["tail"])
    out["len"] = tgt
    return out


def free_slots(state, slots):
    """Zero rows ``slots`` (N,) of a slot-major hybrid state — KV entries
    (+ int8 scales), mamba group/tail states, and ``len`` — back to the
    freshly-allocated state: the preemption/deadline/quarantine release
    primitive. Batch axes as in :func:`insert_prefill_many`; out-of-range
    entries are dropped (padding convention)."""
    out = dict(state)
    out["groups"] = jax.tree_util.tree_map(
        lambda x: x.at[:, :, slots].set(0, mode="drop"), state["groups"])
    out["kv"] = jax.tree_util.tree_map(
        lambda x: x.at[:, slots].set(0, mode="drop"), state["kv"])
    if "tail" in state:
        out["tail"] = jax.tree_util.tree_map(
            lambda x: x.at[:, slots].set(0, mode="drop"), state["tail"])
    out["len"] = state["len"].at[slots].set(0, mode="drop")
    return out


def insert_prefill(state, slot, src):
    """Copy a single-request prefill state (batch=1, same max_len) into row
    ``slot`` of a slot-major shared state whose ``len`` is per-slot (slots,).
    Batch axes: ``groups`` leaves (G, A, B, ...) -> 2; ``kv``/``tail`` -> 1.
    ``slot`` may be traced."""
    def ins(dst, s, axis):
        return jax.lax.dynamic_update_slice_in_dim(
            dst, s.astype(dst.dtype), slot, axis)

    out = dict(state)
    out["groups"] = jax.tree_util.tree_map(
        lambda dst, s: ins(dst, s, 2), state["groups"], src["groups"])
    out["kv"] = jax.tree_util.tree_map(
        lambda dst, s: ins(dst, s, 1), state["kv"], src["kv"])
    if "tail" in state:
        out["tail"] = jax.tree_util.tree_map(
            lambda dst, s: ins(dst, s, 1), state["tail"], src["tail"])
    out["len"] = jax.lax.dynamic_update_slice(
        state["len"], jnp.reshape(src["len"], (1,)).astype(state["len"].dtype),
        (slot,))
    return out


def insert_prefill_many(state, slot_map, src):
    """Scatter an N-row batched prefill state into rows ``slot_map`` (N,) of
    a slot-major shared state (per-slot ``len``). Batch axes as in
    :func:`insert_prefill`; ``slot_map[i] >= slots`` entries are dropped
    (padding rows)."""
    out = dict(state)
    out["groups"] = jax.tree_util.tree_map(
        lambda dst, s: dst.at[:, :, slot_map].set(s.astype(dst.dtype),
                                                  mode="drop"),
        state["groups"], src["groups"])
    out["kv"] = jax.tree_util.tree_map(
        lambda dst, s: dst.at[:, slot_map].set(s.astype(dst.dtype),
                                               mode="drop"),
        state["kv"], src["kv"])
    if "tail" in state:
        out["tail"] = jax.tree_util.tree_map(
            lambda dst, s: dst.at[:, slot_map].set(s.astype(dst.dtype),
                                                   mode="drop"),
            state["tail"], src["tail"])
    out["len"] = state["len"].at[slot_map].set(
        jnp.asarray(src["len"]).astype(state["len"].dtype), mode="drop")
    return out
