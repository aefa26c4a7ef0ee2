"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) backbone.

Train/prefill uses the chunked SSD algorithm: within a chunk the recurrence is
expanded into an attention-like masked matmul (quadratic in the chunk length
only); across chunks a ``lax.scan`` carries the (H, P, N) state — overall
O(L·Q) compute and O(L) memory, sub-quadratic in sequence length (this is why
the ssm family runs the ``long_500k`` shape). Decode is the pure recurrence:
one state update per token, no KV growth.

Quantization (DESIGN §Arch-applicability): in/out projections are role
'hidden' (W3 — >90% of params); SSM dynamics A_log/dt_bias/D/conv stay fp32
(role 'ssm'), the analogue of the paper's sensitive 8-bit output layer.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant_dense
from repro.core.precision import QuantPolicy
from repro.distributed.context import constrain
from repro.models.layers import (embed_init, embed_lookup, logits_readout,
                                 rmsnorm, rmsnorm_init)

__all__ = ["init", "forward", "init_state", "decode_step", "verify_step",
           "rollback_cache", "spec_state_snapshot", "insert_prefill",
           "insert_prefill_many", "block_init", "block_apply", "block_decode",
           "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 256


# --- parameter init ---------------------------------------------------------------

def block_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    d, di, ns, g, h = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                       cfg.ssm_ngroups, cfg.ssm_heads)
    conv_ch = di + 2 * g * ns
    in_dim = 2 * di + 2 * g * ns + h
    ks = jax.random.split(key, 7)
    p = {
        "norm": rmsnorm_init(d),
        "out_proj": quant_dense.init(ks[1], di, d, bias=False, dtype=dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "ssm_d": jnp.ones((h,), jnp.float32),
        "gate_norm": rmsnorm_init(di),
    }
    if cfg.ssm_split_proj:
        # shard-aligned component projections + per-component convs: the
        # fused in_proj's z|x|B|C|dt split points fall inside TP shards,
        # forcing GSPMD reshards every layer (§Perf H-split)
        p.update({
            "wz": quant_dense.init(ks[0], d, di, bias=False, dtype=dtype),
            "wx": quant_dense.init(ks[2], d, di, bias=False, dtype=dtype),
            "wbc": quant_dense.init(ks[3], d, 2 * g * ns, bias=False,
                                    dtype=dtype),
            "wdt": quant_dense.init(ks[4], d, h, bias=False, dtype=dtype),
            "conv_x_w": jax.random.normal(ks[5], (cfg.ssm_conv, di),
                                          dtype) * 0.1,
            "conv_x_b": jnp.zeros((di,), dtype),
            "conv_bc_w": jax.random.normal(ks[6], (cfg.ssm_conv, 2 * g * ns),
                                           dtype) * 0.1,
            "conv_bc_b": jnp.zeros((2 * g * ns,), dtype),
        })
    else:
        p.update({
            "in_proj": quant_dense.init(ks[0], d, in_dim, bias=False,
                                        dtype=dtype),
            "conv_w": jax.random.normal(ks[2], (cfg.ssm_conv, conv_ch),
                                        dtype) * 0.1,
            "conv_b": jnp.zeros((conv_ch,), dtype),
        })
    return p


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    lk = jax.random.split(ks[0], cfg.num_layers)
    layers = jax.vmap(lambda k: block_init(k, cfg, dtype))(lk)
    params = {"embed": embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
              "layers": layers, "final_norm": rmsnorm_init(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["head"] = quant_dense.init(ks[2], cfg.d_model, cfg.vocab_size,
                                          bias=False, dtype=dtype)
    return params


# --- projections -------------------------------------------------------------------

def _dget(deltas, *names):
    node = deltas
    for n in names:
        if node is None:
            return None
        node = node.get(n)
    return node


def _split_proj(zxbcdt, cfg: ModelConfig):
    di, ns, g, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_heads
    z, x, bc, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + 2 * g * ns], axis=-1)
    return z, x, bc, dt


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv1d. x (B,L,C), w (W,C). Returns (y, new_state).

    ``state``: (B, W-1, C) trailing context (decode carries it)."""
    wlen = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (wlen - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(wlen))
    new_state = xp[:, -(wlen - 1):] if wlen > 1 else None
    return jax.nn.silu(y + b), new_state


# --- chunked SSD core ---------------------------------------------------------------

def _ssd_chunked(x, b_mat, c_mat, dt, a_log, chunk: int, bf16: bool = False):
    """SSD over the full sequence.

    x  (B, L, H, P) head values;   b_mat/c_mat (B, L, G, N) shared per group;
    dt (B, L, H) positive step;    a_log (H,) => a = -exp(a_log).
    Returns y (B, L, H, P). fp32 internals; ``bf16`` keeps the big einsum
    operands (x, B, C, decay matrix) in bfloat16 — the decay recurrences /
    cumsum/exp stay fp32 (beyond-paper §Perf H-ssd-bf16).
    """
    bsz, l, h, p = x.shape
    g = b_mat.shape[2]
    n = b_mat.shape[3]
    rep = h // g
    q = min(chunk, l)
    nchunks = -(-l // q)
    pad = nchunks * q - l
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    a = -jnp.exp(a_log.astype(jnp.float32))                    # (H,) negative
    dta = dt.astype(jnp.float32) * a                           # (B, L', H) = log decay
    op_dtype = jnp.bfloat16 if bf16 else jnp.float32
    xw = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
          ).astype(op_dtype)                                   # dt-weighted input

    def rs(t, extra):  # (B, L', ...) -> (nchunks, B, q, ...)
        return t.reshape(bsz, nchunks, q, *extra).transpose(1, 0, 2, *range(3, 3 + len(extra)))

    xs = (rs(xw, (h, p)), rs(b_mat.astype(op_dtype), (g, n)),
          rs(c_mat.astype(op_dtype), (g, n)), rs(dta, (h,)))

    def body(state, xs_c):
        xc, bc, cc, dac = xs_c                                  # per-chunk slices
        lcum = jnp.cumsum(dac, axis=1)                          # (B,q,H) inclusive
        ltot = lcum[:, -1]                                      # (B,H)
        # broadcast B/C groups to heads
        bh = jnp.repeat(bc, rep, axis=2)                        # (B,q,H,N)
        ch = jnp.repeat(cc, rep, axis=2)
        # --- intra-chunk (attention-like) ---
        # att[i,j] = (C_i . B_j) * exp(lcum_i - lcum_j) for j <= i
        scores = jnp.einsum("bihn,bjhn->bhij", ch, bh,
                            preferred_element_type=jnp.float32)
        decay = lcum[:, :, None, :] - lcum[:, None, :, :]       # (B,i,j,H)
        causal = jnp.tril(jnp.ones((xc.shape[1], xc.shape[1]), bool))
        # mask the EXPONENT: exp() of masked (future) entries overflows, and
        # where(mask, inf, 0) backprops inf*0 = NaN
        decay = jnp.where(causal[None, :, :, None], decay, -jnp.inf)
        w = jnp.exp(decay).astype(op_dtype)
        y_intra = jnp.einsum("bhij,bijh,bjhp->bihp", scores.astype(op_dtype),
                             w, xc, preferred_element_type=jnp.float32)
        # --- inter-chunk: contribution of carried state ---
        y_inter = jnp.einsum("bihn,bhpn->bihp", ch.astype(jnp.float32),
                             state) * jnp.exp(lcum)[..., None]
        # --- state update ---
        carry_w = jnp.exp(ltot[:, None, :] - lcum)              # (B,q,H)
        new_state = state * jnp.exp(ltot)[..., None, None] + jnp.einsum(
            "bjhn,bjh,bjhp->bhpn", bh.astype(jnp.float32), carry_w,
            xc.astype(jnp.float32))
        return new_state, y_intra + y_inter

    s0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    s_final, ys = jax.lax.scan(body, s0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, nchunks * q, h, p)
    return y[:, :l], s_final


def block_apply(lp, h_in: jnp.ndarray, cfg: ModelConfig, *, policy: QuantPolicy,
                deltas: Optional[Dict] = None, chunk: int = DEFAULT_CHUNK,
                return_state: bool = False,
                lengths: Optional[jnp.ndarray] = None,
                matmul_mode: str = "auto"):
    """Full Mamba2 block (pre-norm residual).

    With ``return_state`` returns (out, {"ssm", "conv"}) — the exact decode
    state after the sequence (prefill→decode continuation).

    ``lengths`` (B,) marks right-padded rows: dt is zeroed at padding
    positions, which makes the SSD recurrence an exact identity there
    (decay ``exp(0·a)=1``, input weight ``dt·x=0``) — so the carried SSM
    state equals the state after each row's last REAL token. The conv state
    is gathered from each row's true trailing window for the same reason.
    The causal conv itself needs no masking: position ``i < len`` only sees
    inputs ``<= i``, all real."""
    bsz, l, _ = h_in.shape
    hn = rmsnorm(lp["norm"], h_in, cfg.norm_eps)
    di = cfg.d_inner
    gn = cfg.ssm_ngroups * cfg.ssm_state
    if cfg.ssm_split_proj:
        z = quant_dense.apply(lp["wz"], hn, policy=policy, role="hidden",
                              delta=_dget(deltas, "wz", "w"), mode=matmul_mode)
        x0 = quant_dense.apply(lp["wx"], hn, policy=policy, role="hidden",
                               delta=_dget(deltas, "wx", "w"),
                               mode=matmul_mode)
        bc0 = quant_dense.apply(lp["wbc"], hn, policy=policy, role="hidden",
                                delta=_dget(deltas, "wbc", "w"),
                                mode=matmul_mode)
        dt = quant_dense.apply(lp["wdt"], hn, policy=policy, role="hidden",
                               delta=_dget(deltas, "wdt", "w"),
                               mode=matmul_mode)
        xbc_pre = jnp.concatenate([x0, bc0], axis=-1)
        x, _ = _causal_conv(x0, lp["conv_x_w"], lp["conv_x_b"])
        bc, _ = _causal_conv(bc0, lp["conv_bc_w"], lp["conv_bc_b"])
        b_mat, c_mat = jnp.split(bc, [gn], axis=-1)
    else:
        zxbcdt = quant_dense.apply(lp["in_proj"], hn, policy=policy,
                                   role="hidden",
                                   delta=_dget(deltas, "in_proj", "w"),
                                   mode=matmul_mode)
        z, x, bc, dt = _split_proj(zxbcdt, cfg)
        xbc_pre = jnp.concatenate([x, bc], axis=-1)
        xbc, _ = _causal_conv(xbc_pre, lp["conv_w"], lp["conv_b"])
        x, b_mat, c_mat = jnp.split(xbc, [di, di + gn], axis=-1)
    x = x.reshape(bsz, l, cfg.ssm_heads, cfg.ssm_headdim)
    b_mat = b_mat.reshape(bsz, l, cfg.ssm_ngroups, cfg.ssm_state)
    c_mat = c_mat.reshape(bsz, l, cfg.ssm_ngroups, cfg.ssm_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    if lengths is not None:
        valid = jnp.arange(l)[None, :] < lengths[:, None]           # (B, L)
        dt = dt * valid[..., None]          # identity recurrence at padding
    y, s_final = _ssd_chunked(x, b_mat, c_mat, dt, lp["a_log"], chunk,
                              bf16=cfg.ssm_bf16)
    y = y + x.astype(jnp.float32) * lp["ssm_d"][:, None]        # D skip
    y = y.reshape(bsz, l, di).astype(h_in.dtype)
    y = rmsnorm(lp["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = quant_dense.apply(lp["out_proj"], y, policy=policy, role="hidden",
                            delta=_dget(deltas, "out_proj", "w"),
                            mode=matmul_mode)
    out = constrain(h_in + out, "act")
    if return_state:
        wlen = cfg.ssm_conv
        if lengths is not None:
            # per-row trailing window [len-(W-1), len): positions < 0 are
            # the initial zero conv state (short prompts)
            idx = lengths[:, None] - (wlen - 1) + jnp.arange(wlen - 1)[None]
            tail = jnp.take_along_axis(xbc_pre.astype(jnp.float32),
                                       jnp.maximum(idx, 0)[:, :, None], axis=1)
            tail = jnp.where((idx >= 0)[:, :, None], tail, 0.0)
        else:
            pad = max(wlen - 1 - l, 0)
            tail = xbc_pre[:, -(wlen - 1):].astype(jnp.float32)
            if pad:
                tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        return out, {"ssm": s_final, "conv": tail}
    return out


# --- decode (pure recurrence) ---------------------------------------------------------

def block_state(cfg: ModelConfig, batch: int):
    conv_ch = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return {
        "ssm": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state),
                         jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), jnp.float32),
    }


def block_decode(lp, h_in: jnp.ndarray, state: Dict, cfg: ModelConfig, *,
                 policy: QuantPolicy, deltas: Optional[Dict] = None,
                 matmul_mode: str = "auto"):
    """One-token step. h_in (B,1,d). Returns (h_out, new_state)."""
    bsz = h_in.shape[0]
    hn = rmsnorm(lp["norm"], h_in, cfg.norm_eps)
    di = cfg.d_inner
    gn = cfg.ssm_ngroups * cfg.ssm_state
    if cfg.ssm_split_proj:
        z = quant_dense.apply(lp["wz"], hn, policy=policy, role="hidden",
                              delta=_dget(deltas, "wz", "w"), mode=matmul_mode)
        x0 = quant_dense.apply(lp["wx"], hn, policy=policy, role="hidden",
                               delta=_dget(deltas, "wx", "w"),
                               mode=matmul_mode)
        bc0 = quant_dense.apply(lp["wbc"], hn, policy=policy, role="hidden",
                                delta=_dget(deltas, "wbc", "w"),
                                mode=matmul_mode)
        dt = quant_dense.apply(lp["wdt"], hn, policy=policy, role="hidden",
                               delta=_dget(deltas, "wdt", "w"),
                               mode=matmul_mode)
        cs_x, cs_bc = jnp.split(state["conv"], [di], axis=-1)
        x, cx = _causal_conv(x0, lp["conv_x_w"], lp["conv_x_b"], cs_x)
        bc, cbc = _causal_conv(bc0, lp["conv_bc_w"], lp["conv_bc_b"], cs_bc)
        conv_state = jnp.concatenate([cx, cbc], axis=-1)
        b_mat, c_mat = jnp.split(bc, [gn], axis=-1)
    else:
        zxbcdt = quant_dense.apply(lp["in_proj"], hn, policy=policy,
                                   role="hidden",
                                   delta=_dget(deltas, "in_proj", "w"),
                                   mode=matmul_mode)
        z, x, bc, dt = _split_proj(zxbcdt, cfg)
        xbc, conv_state = _causal_conv(jnp.concatenate([x, bc], axis=-1),
                                       lp["conv_w"], lp["conv_b"],
                                       state["conv"])
        x, b_mat, c_mat = jnp.split(xbc, [di, di + gn], axis=-1)
    h, p, n, g = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state, cfg.ssm_ngroups
    x = x.reshape(bsz, h, p).astype(jnp.float32)
    rep = h // g
    b1 = jnp.repeat(b_mat.reshape(bsz, g, n), rep, axis=1).astype(jnp.float32)
    c1 = jnp.repeat(c_mat.reshape(bsz, g, n), rep, axis=1).astype(jnp.float32)
    dt1 = jax.nn.softplus(dt.reshape(bsz, h).astype(jnp.float32) + lp["dt_bias"])
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt1 * a)                                    # (B,H)
    # S <- decay*S + dt * B x^T ;  y = C . S + D*x
    s_new = state["ssm"] * decay[..., None, None] + jnp.einsum(
        "bh,bhn,bhp->bhpn", dt1, b1, x)
    y = jnp.einsum("bhn,bhpn->bhp", c1, s_new) + lp["ssm_d"][:, None] * x
    y = y.reshape(bsz, 1, di).astype(h_in.dtype)
    y = rmsnorm(lp["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = quant_dense.apply(lp["out_proj"], y, policy=policy, role="hidden",
                            delta=_dget(deltas, "out_proj", "w"),
                            mode=matmul_mode)
    # keep the carried state's canonical fp32 dtype (block_state): the conv
    # tail comes back in the activation dtype, and a bf16 drift would make
    # every decode re-trace — and break scan-carried decode chains
    # (speculative drafting) outright
    conv_state = conv_state.astype(state["conv"].dtype)
    return h_in + out, {"ssm": s_new, "conv": conv_state}


# --- whole-model wrappers ---------------------------------------------------------------

def forward(params, batch, cfg: ModelConfig, *, policy: QuantPolicy,
            deltas: Optional[Dict] = None, dtype=jnp.bfloat16,
            remat: str = "layer", attn_chunk: int = 0,
            chunk: int = DEFAULT_CHUNK,
            matmul_mode: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = embed_lookup(params["embed"], batch["tokens"], policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    h = constrain(h, "act")

    def body(hh, xs):
        lp, ld = xs
        return block_apply(lp, hh, cfg, policy=policy, deltas=ld, chunk=chunk,
                           matmul_mode=matmul_mode), None

    if remat != "none":
        body = jax.checkpoint(body, prevent_cse=False)
    ld = deltas.get("layers") if deltas else None
    h, _ = jax.lax.scan(body, h, (params["layers"], ld))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return (_logits(params, h, cfg, policy, deltas, matmul_mode),
            jnp.zeros((), jnp.float32))


def _logits(params, h, cfg, policy, deltas, mm: str = "auto"):
    return logits_readout(params, h, cfg, policy=policy,
                          embed_delta=_dget(deltas, "embed", "w"),
                          head_delta=_dget(deltas, "head", "w"),
                          matmul_mode=mm)


def init_state(cfg: ModelConfig, batch: int, max_len: int = 0, dtype=jnp.bfloat16):
    """Decode state for all layers (stacked). max_len unused (O(1) state)."""
    one = block_state(cfg, batch)
    return {"layers": jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), one),
        "len": jnp.zeros((), jnp.int32)}


def prefill(params, batch, cfg: ModelConfig, *, policy: QuantPolicy,
            deltas=None, dtype=jnp.bfloat16, attn_chunk: int = 0,
            max_len: Optional[int] = None, chunk: int = DEFAULT_CHUNK,
            lengths: Optional[jnp.ndarray] = None,
            matmul_mode: str = "auto"):
    """Prompt pass returning final logits + exact decode-ready state.

    ``lengths`` (B,) enables right-padded multi-request prefill: the SSD
    recurrence is masked so each row's state stops at its true length,
    logits come from each row's last real token, and ``len`` is per-row."""
    h = embed_lookup(params["embed"], batch["tokens"], policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)
    bsz, l = batch["tokens"].shape
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)

    def body(hh, xs):
        lp, ld = xs
        out, st = block_apply(lp, hh, cfg, policy=policy, deltas=ld,
                              chunk=chunk, return_state=True, lengths=lengths,
                              matmul_mode=matmul_mode)
        return out, st

    ld = deltas.get("layers") if deltas else None
    h, states = jax.lax.scan(body, h, (params["layers"], ld))
    if lengths is not None:
        h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    else:
        h = h[:, -1:]
    hln = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _logits(params, hln, cfg, policy, deltas, matmul_mode)
    clen = jnp.asarray(l, jnp.int32) if lengths is None else lengths
    return logits, {"layers": states, "len": clen}


def decode_step(params, state, tokens: jnp.ndarray, cfg: ModelConfig, *,
                policy: QuantPolicy, deltas=None, dtype=jnp.bfloat16,
                matmul_mode: str = "auto"):
    h = embed_lookup(params["embed"], tokens, policy=policy,
                     delta=_dget(deltas, "embed", "w"), dtype=dtype)

    def body(hh, xs):
        lp, ld, st = xs
        hh, st2 = block_decode(lp, hh, st, cfg, policy=policy, deltas=ld,
                               matmul_mode=matmul_mode)
        return hh, st2

    ld = deltas.get("layers") if deltas else None
    h, new_layers = jax.lax.scan(body, h, (params["layers"], ld, state["layers"]))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _logits(params, h, cfg, policy, deltas, matmul_mode)
    return logits, {"layers": new_layers, "len": state["len"] + 1}


_NO_SPEC = ("family 'ssm' does not support speculative decoding: the SSD "
            "recurrence folds every token into one fixed-size state, so a "
            "rejected draft suffix cannot be rewound (no KV length to "
            "rewind, and snapshotting every per-layer state per draft "
            "token would defeat the O(1)-state point of the family)")


def verify_step(params, state, tokens, cfg, **kw):
    """Speculative verify is structurally unavailable for the pure-SSM
    family — reject loudly instead of silently corrupting the state."""
    raise ValueError(_NO_SPEC)


def spec_state_snapshot(state):
    raise ValueError(_NO_SPEC)


def rollback_cache(state, slots, new_lens, trajectory=None):
    raise ValueError(_NO_SPEC)


def free_slots(state, slots):
    """Zero rows ``slots`` (N,) of a slot-major state (conv + SSM states)
    and reset their ``len`` — the preemption/deadline/quarantine release
    primitive. The SSD state is a running fold, so zeroing IS the fresh
    state; out-of-range entries are dropped (padding convention)."""
    layers = jax.tree_util.tree_map(
        lambda x: x.at[:, slots].set(0, mode="drop"), state["layers"])
    ln = state["len"].at[slots].set(0, mode="drop")
    return {"layers": layers, "len": ln}


def insert_prefill(state, slot, src):
    """Copy a single-request prefill state (batch=1) into row ``slot`` of a
    slot-major shared state whose ``len`` is per-slot (slots,). ``slot`` may
    be traced. Every layer leaf is (L, B, ...): batch axis 1."""
    layers = jax.tree_util.tree_map(
        lambda dst, s: jax.lax.dynamic_update_slice_in_dim(
            dst, s.astype(dst.dtype), slot, 1),
        state["layers"], src["layers"])
    ln = jax.lax.dynamic_update_slice(
        state["len"], jnp.reshape(src["len"], (1,)).astype(state["len"].dtype),
        (slot,))
    return {"layers": layers, "len": ln}


def insert_prefill_many(state, slot_map, src):
    """Scatter an N-row batched prefill state into rows ``slot_map`` (N,) of
    a slot-major shared state (per-slot ``len``). Entries with
    ``slot_map[i] >= slots`` are dropped (padding rows)."""
    layers = jax.tree_util.tree_map(
        lambda dst, s: dst.at[:, slot_map].set(s.astype(dst.dtype),
                                               mode="drop"),
        state["layers"], src["layers"])
    ln = state["len"].at[slot_map].set(
        jnp.asarray(src["len"]).astype(state["len"].dtype), mode="drop")
    return {"layers": layers, "len": ln}
