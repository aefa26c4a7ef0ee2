"""Fused Pallas decode-attention kernel (kernels/attn_decode, interpret
mode): parity against its pure-jnp oracle (ref.py) and the production
einsum path (models.attention.decode_attention), bf16-class and int8
caches, per-row valid lengths, blocking edge cases, and the
``attn_mode`` dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attn_decode.ops import attn_decode
from repro.kernels.attn_decode.ref import attn_decode_ref
from repro.models.attention import (decode_attention, resolve_attn_mode,
                                    ATTN_MODES)
from repro.models.transformer import _quantize_kv


def _case(seed, b, s, h, kv, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kc = jax.random.normal(ks[1], (b, s, kv, d))
    vc = jax.random.normal(ks[2], (b, s, kv, d))
    return q, kc, vc


def _stored(x):
    """(B, S, KV, D) -> the cache's stored layout, one layer: (1, B, S, KV*D)."""
    return x.reshape(x.shape[0], x.shape[1], -1)[None]


def _quantized(x):
    """(B, S, KV, D) -> int8 (B, S, KV, D) and its (B, S) scales."""
    q, sc = _quantize_kv(x.reshape(x.shape[0], x.shape[1], -1))
    return q.reshape(x.shape), sc


@pytest.mark.parametrize("h,kv", [(8, 8), (8, 2), (4, 1)])
@pytest.mark.parametrize("b,s,bm,bs", [
    pytest.param(5, 100, 8, 128, id="8-128"),
    pytest.param(5, 100, 2, 32, id="2-32"),
    pytest.param(5, 100, 3, 17, id="3-17"),
    pytest.param(8, 96, None, None, id="slots8-S96")])
def test_kernel_matches_ref_and_einsum(h, kv, b, s, bm, bs):
    """Mixed per-row lengths (incl. 1 and full): kernel == ref == einsum.
    bm/bs sweep covers B and S not divisible by the requested block sizes
    (the kernel blocks by divisors: the cache is read in place); the last
    case is an 8-slot tick at the kernel's own block sizes."""
    d = 16
    q, kc, vc = _case(0, b, s, h, kv, d)
    lens = jnp.minimum(jnp.asarray([1, 7, 64, 100, 33, 96, 50, 12][:b],
                                   jnp.int32), s)
    blocks = {} if bm is None else {"bm": bm, "bs": bs}
    out = attn_decode(q, _stored(kc), _stored(vc), lens, **blocks,
                      interpret=True)
    ref = attn_decode_ref(q, kc, vc, lens)
    ein = decode_attention(q, _stored(kc), _stored(vc), lens, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ein), atol=2e-5)


def test_kernel_scalar_cache_len():
    q, kc, vc = _case(1, 4, 64, 8, 2, 16)
    out = attn_decode(q, _stored(kc), _stored(vc), 42, interpret=True)
    ein = decode_attention(q, _stored(kc), _stored(vc), 42, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ein), atol=2e-5)


def test_kernel_int8_cache_with_scales():
    """int8 K/V + per-token scales read directly: the fused dequant
    epilogue must factor the scales exactly where decode_attention does."""
    b, s = 5, 80
    q, kc, vc = _case(2, b, s, 8, 2, 16)
    kq, ksc = _quantized(kc)
    vq, vsc = _quantized(vc)
    lens = jnp.asarray([1, 80, 13, 37, 64], jnp.int32)
    out = attn_decode(q, _stored(kq), _stored(vq), lens, ksc[None],
                      vsc[None], bm=2, bs=32, interpret=True)
    ref = attn_decode_ref(q, kq, vq, lens, ksc, vsc)
    ein = decode_attention(q, _stored(kq), _stored(vq), lens, ksc[None],
                           vsc[None], mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ein), atol=2e-5)
    # and the int8 path is actually close to the float attention it encodes
    full = decode_attention(q, _stored(kc), _stored(vc), lens, mode="ref")
    assert float(jnp.max(jnp.abs(out - full))) < 0.1


def test_kernel_bf16_cache():
    q, kc, vc = _case(3, 4, 64, 8, 2, 16)
    kc16, vc16 = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
    lens = jnp.asarray([5, 64, 17, 50], jnp.int32)
    out = attn_decode(q, _stored(kc16), _stored(vc16), lens, interpret=True)
    ein = decode_attention(q, _stored(kc16), _stored(vc16), lens, mode="ref")
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ein, np.float32), atol=2e-2)


def test_kernel_ring_permutation_invariance():
    """Ring-buffer storage order must not change the kernel's output
    (mirrors the einsum-path test in test_attention.py)."""
    b, l, h, kv, d = 1, 16, 4, 4, 8
    q, kc, vc = _case(4, b, l, h, kv, d)
    out1 = attn_decode(q, _stored(kc), _stored(vc), jnp.full((b,), l),
                       interpret=True)
    perm = jax.random.permutation(jax.random.PRNGKey(9), l)
    out2 = attn_decode(q, _stored(kc[:, perm]), _stored(vc[:, perm]),
                       jnp.full((b,), l), interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-5)


def test_zero_length_rows_are_zero():
    """cache_len == 0 rows (engine padding) produce zeros, not NaN or the
    uniform v average — both kernel and ref guard the empty softmax."""
    q, kc, vc = _case(5, 3, 32, 4, 2, 8)
    lens = jnp.asarray([0, 16, 0], jnp.int32)
    out = attn_decode(q, _stored(kc), _stored(vc), lens, interpret=True)
    ref = attn_decode_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)
    assert float(jnp.max(jnp.abs(out[1]))) > 0


def test_attn_mode_dispatch():
    """decode_attention(mode=...) mirrors quant_dense.serve_apply: 'kernel'
    routes to the Pallas kernel, 'ref' to the einsum path, 'auto' resolves
    by backend, junk raises."""
    assert resolve_attn_mode("auto") in ("kernel", "ref")
    assert resolve_attn_mode("kernel") == "kernel"
    assert resolve_attn_mode("ref") == "ref"
    with pytest.raises(ValueError):
        resolve_attn_mode("einsum")
    assert "auto" in ATTN_MODES
    q, kc, vc = _case(6, 2, 40, 8, 2, 16)
    lens = jnp.asarray([11, 40], jnp.int32)
    out_k = decode_attention(q, _stored(kc), _stored(vc), lens,
                             mode="kernel", interpret=True)
    out_r = decode_attention(q, _stored(kc), _stored(vc), lens, mode="ref")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5)
