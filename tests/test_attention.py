"""Chunked / sliding-window / decode attention vs a naive reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (chunked_attention, decode_attention,
                                    sliding_window_attention)


def ref_attn(q, k, v, causal=True, window=0):
    b, lq, h, d = q.shape
    lkv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / d ** 0.5
    qp, kp = jnp.arange(lq)[:, None], jnp.arange(lkv)[None, :]
    mask = jnp.ones((lq, lkv), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


def _stored(x):
    """(B, S, KV, D) -> the cache's stored layout, one layer: (1, B, S, KV*D)."""
    return x.reshape(x.shape[0], x.shape[1], -1)[None]


def _qkv(seed, b, l, h, kv, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, l, h, d)),
            jax.random.normal(ks[1], (b, l, kv, d)),
            jax.random.normal(ks[2], (b, l, kv, d)))


@pytest.mark.parametrize("l,chunk", [(64, 64), (64, 16), (70, 32), (5, 8)])
@pytest.mark.parametrize("h,kv", [(8, 8), (8, 2), (4, 1)])
def test_chunked_matches_ref(l, chunk, h, kv):
    q, k, v = _qkv(0, 2, l, h, kv, 16)
    out = chunked_attention(q, k, v, causal=True, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_attn(q, k, v)),
                               atol=2e-5)


@pytest.mark.parametrize("window,chunk", [(8, 16), (24, 16), (128, 32)])
def test_sliding_window_matches_ref(window, chunk):
    q, k, v = _qkv(1, 2, 70, 8, 2, 16)
    out = sliding_window_attention(q, k, v, window=window, chunk=chunk)
    ref = ref_attn(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_matches_last_position():
    b, l, h, kv, d = 2, 40, 8, 2, 16
    q, k, v = _qkv(2, b, l, h, kv, d)
    s = 64
    kc = jnp.zeros((b, s, kv, d)).at[:, :l].set(k)
    vc = jnp.zeros((b, s, kv, d)).at[:, :l].set(v)
    out = decode_attention(q[:, -1:], _stored(kc), _stored(vc),
                           jnp.full((b,), l))
    ref = ref_attn(q, k, v)[:, -1:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_ring_permutation_invariance():
    """Ring-buffer storage order must not change decode attention."""
    b, l, h, kv, d = 1, 16, 4, 4, 8
    q, k, v = _qkv(3, b, l, h, kv, d)
    out1 = decode_attention(q[:, -1:], _stored(k), _stored(v),
                            jnp.full((b,), l))
    perm = jax.random.permutation(jax.random.PRNGKey(9), l)
    out2 = decode_attention(q[:, -1:], _stored(k[:, perm]), _stored(v[:, perm]),
                            jnp.full((b,), l))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-5)
