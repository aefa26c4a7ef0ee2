"""Blocked online-softmax prefill/verify attention (kernels/attn_prefill,
interpret mode): parity against its pure-jnp oracle (ref.py) and the
production chunked/einsum paths across blocking edge cases (T/S not
divisible by the block sizes, mixed row lengths, single-row buckets, bf16 +
int8 KV, SWA windows), the empty-row guard regression
(verify_attention/chunked_attention), the jaxpr-asserted absence of the
quadratic (T, S) score tensor in kernel-mode prefill and verify graphs, and
engine-level token parity of kernel-mode prefill+verify vs ref under
staggered admission."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import check_no_quadratic_scores
from repro.configs import get_config, reduced
from repro.core.precision import FLOAT, W3A8
from repro.kernels.attn_prefill.ops import attn_prefill
from repro.kernels.attn_prefill.ref import attn_prefill_ref
from repro.models import api as model_api
from repro.models import get_model, transformer
from repro.models.attention import (chunked_attention, prefill_attention,
                                    sliding_window_attention,
                                    verify_attention)
from repro.models.transformer import _quantize_kv
from repro.serving.engine import ServingEngine, generate

W3 = dataclasses.replace(W3A8, act_bits=None)


def _case(seed, b, t, s, h, kv, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    return q, k, v


def _stored(x):
    """(B, S, KV, D) -> the cache's stored layout, one layer: (1, B, S, KV*D)."""
    return x.reshape(x.shape[0], x.shape[1], -1)[None]


def _quantized(x):
    """(B, S, KV, D) -> int8 (B, S, KV, D) and its (B, S) scales."""
    q, sc = _quantize_kv(x.reshape(x.shape[0], x.shape[1], -1))
    return q.reshape(x.shape), sc


def _oracle(q, k, v, hi, lo=None, k_scale=None, v_scale=None):
    """attn_prefill_ref through the same GQA/pre-scale plumbing as ops.py."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    qg = (q * (d ** -0.5)).reshape(b, t, kv, h // kv, d)
    lo = jnp.zeros((b, t), jnp.int32) if lo is None else lo
    return attn_prefill_ref(qg, k, v, lo, hi, k_scale,
                            v_scale).reshape(b, t, h, d)


def _prefill_hi(lens, t):
    pos = jnp.arange(t, dtype=jnp.int32)
    return jnp.minimum(pos[None, :] + 1, jnp.asarray(lens, jnp.int32)[:, None])


# --- kernel vs oracle: blocking edge cases ----------------------------------------

@pytest.mark.parametrize("h,kv", [(8, 2), (4, 4), (4, 1), (4, 2)])
@pytest.mark.parametrize("b,t,bt,bs", [
    pytest.param(3, 50, 16, 32, id="16-32"),
    pytest.param(3, 50, 8, 24, id="8-24"),
    pytest.param(3, 50, 7, 13, id="7-13"),
    pytest.param(3, 50, 128, 128, id="128-128"),
    pytest.param(2, 24, None, None, id="B2-T24")])
def test_kernel_matches_oracle_blocking(h, kv, b, t, bt, bs):
    """Mixed per-row lengths (incl. 1 and full) under the bucketed-prefill
    rule; bt/bs sweep covers T and S not divisible by the requested block
    sizes (queries are padded, keys blocked by a divisor of S); the last
    case is a two-row admission at the kernel's own block sizes."""
    d = 16
    q, k, v = _case(0, b, t, t, h, kv, d)
    lens = [t, 17, 1][:b]
    hi = _prefill_hi(lens, t)
    blocks = {} if bt is None else {"bt": bt, "bs": bs}
    out = attn_prefill(q, _stored(k), _stored(v), hi, **blocks,
                       interpret=True)
    ref = _oracle(q, k, v, hi)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    # every REAL query position also matches the production chunked path
    # (causal-only masking: j <= t < len already implies j < len there)
    chunked = chunked_attention(q, k, v, causal=True, chunk=32)
    for row, ln in enumerate(lens):
        np.testing.assert_allclose(np.asarray(out[row, :ln]),
                                   np.asarray(chunked[row, :ln]), atol=2e-5)


def test_kernel_single_row_bucket():
    """B=1 admission bucket, T=S=33 not divisible by either block size."""
    q, k, v = _case(1, 1, 33, 33, 4, 2, 8)
    hi = _prefill_hi([33], 33)
    out = attn_prefill(q, _stored(k), _stored(v), hi, bt=8, bs=16,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(q, k, v, hi)),
                               atol=2e-6)


def test_kernel_bf16():
    q, k, v = _case(2, 2, 40, 40, 8, 2, 16, jnp.bfloat16)
    hi = _prefill_hi([40, 23], 40)
    out = attn_prefill(q, _stored(k), _stored(v), hi, bt=16, bs=16,
                       interpret=True)
    ref = _oracle(q, k, v, hi)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


def test_kernel_int8_kv_with_scales():
    """int8 K/V + per-token scales read directly: the fused dequant epilogue
    must factor the scales exactly where the ref einsum does."""
    b, t = 3, 41
    q, k, v = _case(3, b, t, t, 8, 2, 16)
    kq, ksc = _quantized(k)
    vq, vsc = _quantized(v)
    hi = _prefill_hi([41, 9, 28], t)
    out = attn_prefill(q, _stored(kq), _stored(vq), hi, k_scale=ksc[None],
                       v_scale=vsc[None], bt=16, bs=16, interpret=True)
    ref = _oracle(q, kq, vq, hi, k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    # and the int8 path stays close to the float attention it encodes
    full = _oracle(q, k, v, hi)
    assert float(jnp.max(jnp.abs(out - full))) < 0.1


def test_kernel_swa_window():
    """lo bounds = sliding window: kernel == sliding_window_attention at
    full length (no row padding), == oracle with the lo/hi mask."""
    b, t, w = 2, 40, 8
    q, k, v = _case(4, b, t, t, 4, 2, 8)
    pos = jnp.arange(t, dtype=jnp.int32)
    hi = jnp.broadcast_to(pos[None, :] + 1, (b, t))
    lo = jnp.broadcast_to(jnp.maximum(pos - (w - 1), 0)[None], (b, t))
    out = attn_prefill(q, _stored(k), _stored(v), hi, lo=lo, bt=16, bs=16,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_oracle(q, k, v, hi, lo=lo)),
                               atol=2e-6)
    swa = sliding_window_attention(q, k, v, window=w, chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(swa), atol=2e-5)


# --- dispatch entry points --------------------------------------------------------

def test_prefill_attention_dispatch():
    """prefill_attention(mode=...) kernel path == ref path at every real
    query position, for plain and SWA masking."""
    b, t = 3, 36
    q, k, v = _case(5, b, t, t, 4, 2, 8)
    lens = jnp.asarray([36, 12, 5], jnp.int32)
    out_k = prefill_attention(q, k, v, lengths=lens, mode="kernel",
                              interpret=True)
    out_r = prefill_attention(q, k, v, lengths=lens, mode="ref", chunk=16)
    for row, ln in enumerate([36, 12, 5]):
        np.testing.assert_allclose(np.asarray(out_k[row, :ln]),
                                   np.asarray(out_r[row, :ln]), atol=2e-5)
    sw_k = prefill_attention(q, k, v, window=8, mode="kernel", interpret=True)
    sw_r = prefill_attention(q, k, v, window=8, mode="ref", chunk=16)
    np.testing.assert_allclose(np.asarray(sw_k), np.asarray(sw_r), atol=2e-5)


@pytest.mark.parametrize("s,starts,h", [
    pytest.param(50, (4, 0, 47), 8, id="B3-S50"),
    pytest.param(48, (0, 12, 24, 45), 4, id="B4-S48")])
def test_verify_attention_dispatch(s, starts, h):
    """verify_attention(mode='kernel') — the T-row specialization over the
    live cache — matches the guarded-einsum ref, float and int8 cache.
    Row i verifies T=3 drafts at positions starts[i] .. starts[i] + 2."""
    b, t = len(starts), 3
    q, _, _ = _case(6, b, t, s, h, 2, 16)
    _, kc, vc = _case(7, b, t, s, h, 2, 16)
    valid = (jnp.asarray(starts, jnp.int32)[:, None]
             + jnp.arange(1, t + 1, dtype=jnp.int32))
    kc, vc = _stored(kc), _stored(vc)
    out_k = verify_attention(q, kc, vc, valid, mode="kernel", interpret=True)
    out_r = verify_attention(q, kc, vc, valid, mode="ref")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5)
    kq, ksc = _quantize_kv(kc)
    vq, vsc = _quantize_kv(vc)
    out_k8 = verify_attention(q, kq, vq, valid, ksc, vsc, mode="kernel",
                              interpret=True)
    out_r8 = verify_attention(q, kq, vq, valid, ksc, vsc, mode="ref")
    np.testing.assert_allclose(np.asarray(out_k8), np.asarray(out_r8),
                               atol=2e-5)


# --- empty-row guard regression ---------------------------------------------------

def test_verify_attention_empty_row_guard():
    """A zero-valid-length row (all-false mask — engine padding) must yield
    zeros from BOTH paths, never NaN or the uniform average over v."""
    b, t, s = 2, 3, 32
    q, kc, vc = _case(8, b, t, s, 4, 2, 8)
    valid = jnp.asarray([[0, 0, 0], [4, 5, 6]], jnp.int32)
    for mode in ("ref", "kernel"):
        out = verify_attention(q, _stored(kc), _stored(vc), valid, mode=mode,
                               interpret=True)
        assert not np.any(np.isnan(np.asarray(out))), mode
        np.testing.assert_array_equal(np.asarray(out[0]), 0.0, err_msg=mode)
        assert float(jnp.max(jnp.abs(out[1]))) > 0, mode


def test_chunked_attention_empty_row_guard():
    """q_offset < 0 makes query 0's causal mask all-false across every
    chunk: the scan's online softmax must emit zeros for it, not the
    uniform v average (and never NaN)."""
    q, k, v = _case(9, 2, 4, 8, 4, 2, 8)
    out = chunked_attention(q, k, v, causal=True, chunk=4, q_offset=-1)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[:, 0]), 0.0)
    assert float(jnp.max(jnp.abs(out[:, 1:]))) > 0


# --- the tentpole invariant: no (T, S) score tensor in kernel-mode graphs ---------
# (the jaxpr walking lives in repro.analysis now — the shared pass keeps
# this test's exact strictness: any float tensor with trailing (T, S) dims
# outside pallas_call, rank >= 2, or a missing pallas_call, is a violation)

def _graph_cfg():
    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=32, vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_prefill_graph_has_no_quadratic_score_tensor():
    """Jitted kernel-mode prefill contains NO float (..., T, T) score tensor
    outside the pallas_call — the quadratic-HBM intermediate is gone."""
    cfg, params = _graph_cfg()
    t = 48
    toks = jnp.zeros((2, t), jnp.int32)
    lens = jnp.asarray([48, 20], jnp.int32)

    def run(mode):
        fn = lambda tk: transformer.prefill(
            params, {"tokens": tk}, cfg, policy=FLOAT, dtype=jnp.float32,
            lengths=lens, max_len=64, attn_mode=mode)
        return jax.make_jaxpr(fn)(toks)

    viols = check_no_quadratic_scores(run("kernel"), t, t,
                                      require_pallas=True)
    assert not viols, "; ".join(str(v) for v in viols)
    # detector sanity: the ref chunked path DOES build (B, KV, G, T, chunk)
    # tiles with chunk == T here, so the same check must trip on it
    assert check_no_quadratic_scores(run("ref"), t, t), \
        "detector lost its ref signal"


def test_verify_graph_has_no_score_tensor():
    """Jitted kernel-mode verify_step contains NO float (..., T, S) score
    tensor outside the pallas_call (T = spec_k+1, S = the decode cache)."""
    cfg, params = _graph_cfg()
    t, s = 3, 40
    cache = model_api.init_cache(cfg, 2, s, jnp.float32, per_slot_len=True)
    cache["len"] = jnp.asarray([7, 11], jnp.int32)
    toks = jnp.zeros((2, t), jnp.int32)

    def run(mode):
        fn = lambda c, tk: transformer.verify_step(
            params, c, tk, cfg, policy=FLOAT, dtype=jnp.float32,
            attn_mode=mode)
        return jax.make_jaxpr(fn)(cache, toks)

    viols = check_no_quadratic_scores(run("kernel"), t, s,
                                      require_pallas=True)
    assert not viols, "; ".join(str(v) for v in viols)
    assert check_no_quadratic_scores(run("ref"), t, s), \
        "detector lost its ref signal"


# --- engine-level token parity ----------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_engine_kernel_prefill_verify_matches_ref(family):
    """attn_mode='kernel' (blocked Pallas prefill + verify + fused decode,
    interpret mode on CPU) is token-identical to attn_mode='ref' through
    the speculative engine under staggered bucketed admission."""
    arch = "zamba2-1.2b" if family == "hybrid" else "qwen2-1.5b"
    layers = 4 if family == "hybrid" else 2
    cfg = reduced(get_config(arch), layers=layers, d_model=32, vocab=64)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    prompts = {4: [1, 2, 3, 4], 9: [5, 4, 3, 2, 1, 2, 3, 4, 5]}

    def solo(prompt, mode):
        out = generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                       policy=FLOAT, max_new_tokens=4, dtype=jnp.float32,
                       attn_mode=mode, spec_k=2)
        return [int(x) for x in np.asarray(out[0, len(prompt):])]

    ref = {n: solo(p, "ref") for n, p in prompts.items()}
    assert {n: solo(p, "kernel") for n, p in prompts.items()} == ref

    eng = ServingEngine(params, cfg, policy=FLOAT, slots=3, max_len=32,
                        dtype=jnp.float32, attn_mode="kernel", spec_k=2)
    for n in (4, 9, 4):                     # two buckets, batched admission
        eng.submit(prompts[n], max_new=4)
    eng.step(); eng.step()                  # first wave mid-decode...
    eng.submit(prompts[9], max_new=4)       # ...late wave rides along
    done = eng.run_all()
    assert len(done) == 4 and all(r.done for r in done)
    for r in done:
        assert r.out == ref[len(r.prompt)], (family, r.out)
