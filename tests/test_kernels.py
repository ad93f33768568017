"""Per-kernel shape/dtype sweeps: Pallas kernels vs ref.py oracles.

Runs in interpret mode (CPU container); the kernel bodies execute exactly
as they would on TPU up to compiler scheduling.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bcq
from repro.kernels.lut_gemm import ops as lut_ops, ref as lut_ref
from repro.kernels.bcq_matmul import ops as mxu_ops, ref as mxu_ref


def _case(m, n, b, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    W = jnp.array(rng.normal(size=(m, n)).astype(np.float32))
    x = jnp.array(rng.normal(size=(b, n)).astype(np.float32), dtype=dtype)
    return W, x


SHAPES = [
    # (M, N, B) — aligned and deliberately ragged cases
    (128, 512, 8),
    (64, 128, 1),
    (96, 200, 5),
    (256, 384, 3),
    (33, 130, 2),
    (64, 2048, 3),      # two 1024-weight chunks: the in-kernel chunk loop
]


class TestLutGemmKernel:
    @pytest.mark.parametrize("m,n,b", SHAPES)
    @pytest.mark.parametrize("bits", [1, 2, 4])
    def test_matches_dense_oracle(self, m, n, b, bits):
        W, x = _case(m, n, b, seed=m + n + bits)
        wq = bcq.from_uniform(W, bits=bits, group_size=64)
        want = lut_ref.dense_ref(x, wq)
        got = lut_ops.lut_gemm(x, wq, interpret=True)
        scale = float(jnp.abs(want).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(want) / scale, atol=2e-5)

    @pytest.mark.parametrize("read_mode", ["onehot", "select"])
    def test_read_modes_agree(self, read_mode):
        W, x = _case(128, 256, 4, seed=11)
        wq = bcq.quantize(W, bits=3, group_size=128, iters=2)
        want = lut_ref.dense_ref(x, wq)
        got = lut_ops.lut_gemm(x, wq, read_mode=read_mode, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-4)

    @pytest.mark.parametrize("half_lut", [True, False])
    def test_half_lut_equivalence(self, half_lut):
        """hFFLUT decode must be bit-identical math to the full table."""
        W, x = _case(64, 128, 2, seed=3)
        wq = bcq.from_uniform(W, bits=4, group_size=64)
        got = lut_ops.lut_gemm(x, wq, half_lut=half_lut, interpret=True)
        want = lut_ref.dense_ref(x, wq)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-4)

    @pytest.mark.parametrize("mu", [2, 4])
    def test_mu_values(self, mu):
        W, x = _case(64, 256, 2, seed=mu)
        wq = bcq.from_uniform(W, bits=2, group_size=64)
        got = lut_ops.lut_gemm(x, wq, mu=mu, interpret=True)
        want = lut_ref.dense_ref(x, wq)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        W, x = _case(64, 128, 2, seed=5, dtype=dtype)
        wq = bcq.from_uniform(W, bits=4, group_size=64)
        got = lut_ops.lut_gemm(x, wq, interpret=True)
        want = lut_ref.dense_ref(x, wq)
        assert got.dtype == dtype
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol * 10)

    def test_lut_ref_matches_dense_ref(self):
        """The lut_ref oracle itself must agree with dense dequant."""
        W, x = _case(96, 200, 5, seed=0)
        wq = bcq.from_uniform(W, bits=4, group_size=64)
        a = lut_ref.lut_ref(x, wq, mu=4, half_lut=True)
        b = lut_ref.dense_ref(x, wq)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=2e-4)

    def test_3d_batch(self):
        W, _ = _case(64, 128, 1, seed=8)
        x = jnp.array(np.random.default_rng(8).normal(size=(2, 3, 128)).astype(np.float32))
        wq = bcq.from_uniform(W, bits=4, group_size=64)
        got = lut_ops.lut_gemm(x, wq, interpret=True)
        assert got.shape == (2, 3, 64)
        want = lut_ref.dense_ref(x, wq)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-4)


class TestBcqMatmulKernel:
    @pytest.mark.parametrize("m,n,b", SHAPES)
    @pytest.mark.parametrize("bits", [2, 4])
    def test_matches_oracle(self, m, n, b, bits):
        W, x = _case(m, n, b, seed=m * 2 + bits)
        wq = bcq.from_uniform(W, bits=bits, group_size=64)
        want = mxu_ref.bcq_matmul_ref(x, wq)
        got = mxu_ops.bcq_matmul(x, wq, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_agrees_with_lut_kernel(self):
        """Both kernels execute the same BCQ math."""
        W, x = _case(128, 512, 8, seed=21)
        wq = bcq.quantize(W, bits=3, group_size=128, iters=2)
        a = mxu_ops.bcq_matmul(x, wq, interpret=True)
        b = lut_ops.lut_gemm(x, wq, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=2e-4)
