"""Per-kernel allclose vs pure-jnp oracles (interpret=True on CPU), with
hypothesis shape/dtype sweeps as required for every Pallas kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:     # optional dev dep; see requirements-dev.txt
    from _hypothesis_stub import given, settings, st

from repro.core.packing import pack_matrix
from repro.kernels.qmatmul.ops import qmatmul
from repro.kernels.qmatmul.ref import qmatmul_ref
from repro.kernels.qmatvec.ops import qmatvec
from repro.kernels.qmatvec.ref import qmatvec_ref
from repro.kernels.sigmoid_pw.kernel import sigmoid_pw_pallas
from repro.kernels.sigmoid_pw.ref import sigmoid_pw


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestQMatmul:
    # the last two: an 8-slot decode tick and 8 slots x a 16-token prefill
    @pytest.mark.parametrize("m,k,n", [(8, 32, 16), (128, 128, 128),
                                       (100, 1022, 10), (257, 513, 129),
                                       (8, 96, 128), (128, 96, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, m, k, n, dtype):
        kx, kw, kd = jax.random.split(jax.random.PRNGKey(0), 3)
        x = _rand(kx, (m, k), dtype)
        wq = jax.random.randint(kw, (k, n), -3, 4, jnp.int8)
        d = jnp.abs(_rand(kd, (n,), jnp.float32)) * 0.1 + 0.01
        out = qmatmul(x, wq, d, interpret=True)
        ref = qmatmul_ref(x, wq, d)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(out, jnp.float32),
                                   np.asarray(ref, jnp.float32),
                                   rtol=tol, atol=tol)

    def test_batched_leading_dims(self):
        x = _rand(jax.random.PRNGKey(0), (2, 3, 64), jnp.float32)
        wq = jax.random.randint(jax.random.PRNGKey(1), (64, 32), -3, 4, jnp.int8)
        d = jnp.ones((32,), jnp.float32) * 0.1
        out = qmatmul(x, wq, d, interpret=True)
        assert out.shape == (2, 3, 32)
        ref = qmatmul_ref(x.reshape(-1, 64), wq, d).reshape(2, 3, 32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 70), st.integers(1, 150), st.integers(1, 70),
           st.integers(0, 2**31 - 1))
    def test_shape_sweep_property(self, m, k, n, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = _rand(ks[0], (m, k), jnp.float32)
        wq = jax.random.randint(ks[1], (k, n), -3, 4, jnp.int8)
        d = jnp.abs(_rand(ks[2], (n,), jnp.float32)) * 0.1 + 0.01
        out = qmatmul(x, wq, d, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(qmatmul_ref(x, wq, d)),
                                   rtol=1e-4, atol=1e-4)


class TestQMatvec:
    # (M, K, N, bias, out_dtype): decode (M 1, 16) and bucketed prefill
    # (16 x 64) rows; KP 154 in one K block (K 1536), partial last blocks
    # (K 1537, 2570, 3001), K not a multiple of 10; N 256 and 8960; an
    # 8-slot decode tick and 8 slots x a 16-token prefill at K 96
    @pytest.mark.parametrize("m,k,n,bias,out_dtype", [
        pytest.param(1, 1022, 1022, False, None, id="1-1022-1022"),
        pytest.param(8, 100, 64, False, None, id="8-100-64"),
        pytest.param(128, 640, 256, False, None, id="128-640-256"),
        (1, 1536, 256, True, jnp.float32), (16, 1536, 8960, True, None),
        (16, 1537, 256, False, jnp.bfloat16), (1024, 1536, 256, True, None),
        (16, 2570, 256, True, None), (1, 3001, 256, False, None),
        (16, 8960, 256, True, jnp.float32), (1, 8960, 8960, False, None),
        (16, 95, 8960, False, None), (8, 96, 128, True, None),
        (128, 96, 128, True, None)])
    def test_vs_ref(self, m, k, n, bias, out_dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = _rand(ks[0], (m, k), jnp.float32)
        q = jax.random.randint(ks[1], (k, n), -4, 4, jnp.int8)
        wp = pack_matrix(q, 3)
        d = jnp.abs(_rand(ks[2], (n,), jnp.float32)) * 0.1 + 0.01
        b = _rand(ks[3], (n,), jnp.float32) if bias else None
        out = qmatvec(x, wp, d, k=k, bias=b, out_dtype=out_dtype,
                      interpret=True)
        ref = qmatvec_ref(x, wp, d, k, bias=b, out_dtype=out_dtype)
        assert out.shape == (m, n) and out.dtype == ref.dtype
        tol = 2e-2 if out.dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(out, jnp.float32),
                                   np.asarray(ref, jnp.float32),
                                   rtol=tol, atol=tol)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 16), st.integers(1, 200), st.integers(1, 64),
           st.integers(0, 2**31 - 1))
    def test_shape_sweep_property(self, b, k, n, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = _rand(ks[0], (b, k), jnp.float32)
        q = jax.random.randint(ks[1], (k, n), -3, 4, jnp.int8)
        wp = pack_matrix(q, 3)
        d = jnp.abs(_rand(ks[2], (n,), jnp.float32)) * 0.1 + 0.01
        out = qmatvec(x, wp, d, k=k, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(qmatvec_ref(x, wp, d, k)),
                                   rtol=1e-4, atol=1e-4)

    def test_packed_traffic_is_3p2_bits(self):
        k, n = 1000, 64
        q = jnp.zeros((k, n), jnp.int8)
        wp = pack_matrix(q, 3)
        assert wp.nbytes * 8 / (k * n) == pytest.approx(3.2, rel=0.01)


class TestFusedBias:
    """Batched decode/prefill shapes with the bias fused into the kernel
    epilogue, checked against the dequantized ``effective_weight`` oracle
    (the serve-path correctness bar)."""

    def _oracle(self, x, leaf):
        from repro.core import quant_dense
        from repro.core.precision import W3A8
        w = quant_dense.effective_weight(leaf, W3A8, "hidden", k=x.shape[-1])
        return x @ w.astype(x.dtype) + leaf["b"]

    @pytest.mark.parametrize("b", [2, 8, 128])      # decode + prefill shapes
    def test_qmatvec_batched_with_bias_vs_effective_weight(self, b):
        k, n = 100, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        x = _rand(ks[0], (b, k), jnp.float32)
        q = jax.random.randint(ks[1], (k, n), -3, 4, jnp.int8)
        d = jnp.abs(_rand(ks[2], (n,), jnp.float32)) * 0.1 + 0.01
        bias = _rand(ks[3], (n,), jnp.float32)
        leaf = {"qp": pack_matrix(q, 3), "delta": d.reshape(1, n), "b": bias}
        out = qmatvec(x, leaf["qp"], d, k=k, bias=bias, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._oracle(x, leaf)),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("b", [8, 128])
    def test_qmatmul_levels_with_bias_vs_effective_weight(self, b):
        k, n = 100, 64
        ks = jax.random.split(jax.random.PRNGKey(4), 4)
        x = _rand(ks[0], (b, k), jnp.float32)
        q = jax.random.randint(ks[1], (k, n), -3, 4, jnp.int8)
        d = jnp.abs(_rand(ks[2], (n,), jnp.float32)) * 0.1 + 0.01
        bias = _rand(ks[3], (n,), jnp.float32)
        leaf = {"q": q, "delta": d.reshape(1, n), "b": bias}
        out = qmatmul(x, q, d, bias=bias, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._oracle(x, leaf)),
                                   rtol=1e-4, atol=1e-4)


class TestSigmoidPW:
    def test_vs_ref_and_exact(self):
        x = jnp.linspace(-8, 8, 1000)
        out = sigmoid_pw_pallas(x, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(sigmoid_pw(x)),
                                   atol=1e-6)
        # PLAN approximation error bound vs exact sigmoid
        err = float(jnp.max(jnp.abs(out - jax.nn.sigmoid(x))))
        assert err < 0.0190

    @pytest.mark.parametrize("shape", [(7,), (3, 5), (2, 3, 129)])
    def test_shapes(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) * 4
        out = sigmoid_pw_pallas(x, interpret=True)
        assert out.shape == shape

    def test_symmetry(self):
        x = jnp.linspace(0.0, 6.0, 100)
        lo = sigmoid_pw_pallas(-x, interpret=True)
        hi = sigmoid_pw_pallas(x, interpret=True)
        np.testing.assert_allclose(np.asarray(lo + hi), 1.0, atol=1e-6)
