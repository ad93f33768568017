"""Plane-bundle layout invariants and the dedicated ternary datapath.

Covers: the spec/plane TERNARY_BITS constants agreeing, the ternary
Pallas kernel matching the gathered half-LUT oracle bit-exactly over
the kernel shape matrix (interpret mode), bundle storage-byte honesty
(ternary strictly smaller than generic 2-bit BCQ at equal shape), the
sub-2-bit mixed-precision plan lowering to per-layer ternary bundles,
and serve-level token-for-token equality of the fused ternary kernel
against the XLA fallback backend.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import plane
from repro.kernels.ternary_matmul import dense_ref, ternary_matmul, ternary_ref
from repro.quant import QuantSpec
from repro.quant.formats import quantize_ternary

RNG = np.random.default_rng


def _case(m, n, b, seed, dtype=jnp.float32):
    rng = RNG(seed)
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
]


def test_ternary_bits_constants_agree():
    """spec.py keeps its own literal to stay import-light; pin them."""
    from repro.quant.spec import TERNARY_BITS as spec_bits
    assert spec_bits == plane.TERNARY_BITS


class TestTernaryKernelExactness:
    """The kernel must be *bit-exact* against the gathered oracle on
    arithmetically exact inputs (pow2 alphas, integer activations):
    there the equality is independent of reduction order and fusion, so
    any mismatch means the in-kernel sign/mask -> (b1, b2) decode
    diverged.  Float inputs may differ by reduction-order ulps only."""

    @pytest.mark.parametrize("m,n,b", SHAPES)
    def test_matches_oracle_exactly(self, m, n, b):
        # pow2 alphas + integer activations make every partial product
        # an exact f32, so the equality is independent of reduction
        # order/fusion — any mismatch is a decode bug, not rounding
        rng = RNG(m + n)
        W = jnp.array(0.5 * rng.integers(-1, 2, size=(m, n)).astype(np.float32))
        x = jnp.array(rng.integers(-8, 9, size=(b, n)).astype(np.float32))
        wq = quantize_ternary(W, group_size=64)
        want = ternary_ref(x, wq)
        got = ternary_matmul(x, wq, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("m,n,b", SHAPES)
    def test_float_case_within_ulp(self, m, n, b):
        W, x = _case(m, n, b, seed=m + n)
        wq = quantize_ternary(W, group_size=64)
        want = np.asarray(ternary_ref(x, wq))
        got = np.asarray(ternary_matmul(x, wq, interpret=True))
        scale = np.abs(want).max() + 1e-6
        np.testing.assert_allclose(got / scale, want / scale, atol=1e-6)

    @pytest.mark.parametrize("read_mode", ["onehot", "select"])
    def test_read_modes_exact(self, read_mode):
        rng = RNG(7)
        m, n, b = 96, 256, 4
        W = jnp.array(0.5 * rng.integers(-1, 2, size=(m, n)).astype(np.float32))
        x = jnp.array(rng.integers(-8, 9, size=(b, n)).astype(np.float32))
        wq = quantize_ternary(W, group_size=64)
        want = ternary_ref(x, wq)
        got = ternary_matmul(x, wq, read_mode=read_mode, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_matches_dense_dequant(self):
        """And the oracle itself must match plain dequant @ x."""
        W, x = _case(64, 192, 3, seed=1)
        wq = quantize_ternary(W, group_size=64)
        a = np.asarray(ternary_ref(x, wq))
        d = np.asarray(dense_ref(x, wq))
        scale = np.abs(d).max() + 1e-6
        np.testing.assert_allclose(a / scale, d / scale, atol=1e-5)

    def test_integer_exact_case(self):
        """Pow2 alphas + integer activations: every partial product is
        an exact f32, so kernel == oracle == dense regardless of
        accumulation order."""
        rng = RNG(3)
        m, n, b = 64, 128, 4
        w = rng.integers(-1, 2, size=(m, n)).astype(np.float32)
        wq = quantize_ternary(jnp.array(0.5 * w), group_size=64)
        x = jnp.array(rng.integers(-8, 9, size=(b, n)).astype(np.float32))
        got = np.asarray(ternary_matmul(x, wq, interpret=True))
        dense = np.asarray(x) @ (0.5 * w).T
        assert np.array_equal(got, dense)

    def test_rejects_generic_bundles(self):
        from repro.core import bcq
        W, x = _case(32, 64, 2, seed=0)
        wq = bcq.quantize(W, bits=2, group_size=32, iters=1)
        with pytest.raises(ValueError, match="ternary"):
            ternary_matmul(x, wq, interpret=True)


class TestBundleBytes:
    def test_nbytes_counts_stored_arrays_only(self):
        W, _ = _case(48, 160, 1, seed=2)
        wq = quantize_ternary(W, group_size=32)
        want = (wq.packed.size * wq.packed.dtype.itemsize
                + wq.alpha.size * wq.alpha.dtype.itemsize)
        assert wq.z is None and wq.nbytes() == want

    def test_ternary_strictly_smaller_than_bcq2(self):
        """Same shape/groups, same 2 stored planes — the ternary layout
        must win on bytes (1 alpha row vs 2, no offset row)."""
        from repro.core import bcq
        W, _ = _case(48, 160, 1, seed=2)
        t = quantize_ternary(W, group_size=32)
        g = bcq.quantize(W, bits=2, group_size=32, iters=1)
        assert t.packed.shape == g.packed.shape
        assert t.nbytes() < g.nbytes()


class TestMixedPrecisionTernary:
    def test_sub2_plan_lowers_to_ternary_bundles(self):
        """A 1.58-bit average budget must produce at least one ternary
        bundle and charge the budget at the information rate."""
        from repro.quant import quantize_model

        rng = RNG(0)
        params = {f"l{i}": {"up": jnp.array(
            rng.normal(size=(24, 64)).astype(np.float32))} for i in range(3)}
        spec = QuantSpec(bits=1.58, group_size=32, iters=2)
        assert spec.bits == plane.TERNARY_BITS
        qparams, manifest = quantize_model(params, spec)
        kinds = [qparams[f"l{i}"]["up"].kind for i in range(3)]
        assert "ternary" in kinds
        fmts = {l["path"]: l["format"] for l in manifest.layers}
        for i, k in enumerate(kinds):
            assert fmts[f"l{i}/up"] == ("ternary" if k == "ternary" else "bcq")
        # parameter-weighted effective bits must respect the budget
        # (every candidate >= the ternary rate, so >= holds too)
        eff = [qparams[f"l{i}"]["up"].effective_bits for i in range(3)]
        avg = sum(eff) / len(eff)
        assert plane.TERNARY_BITS <= avg <= 2.0 + 1e-9


class TestServeTernary:
    def test_fused_and_fallback_serve_identical_tokens(self):
        """The backend is an execution detail: serving the same ternary
        checkpoint on ternary_pallas (interpret) and on the bcq_xla
        fallback must emit the same tokens for every request."""
        from repro.configs import get_reduced
        from repro.models import Model
        from repro.quant import quantize_model
        from repro.serve import Request, ServeEngine

        cfg = get_reduced("opt_6_7b").replace(
            remat=False, dtype="float32",
            quant=QuantSpec(format="ternary", backend="ternary_pallas"))
        model = Model(cfg)
        params = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v,
            model.init(jax.random.PRNGKey(0)))
        qparams, _ = quantize_model(params, cfg.quant, model.axes())

        rng = RNG(0)
        prompts = [rng.integers(0, cfg.vocab_size, (int(l),))
                   for l in (5, 9)]
        outs = {}
        for backend in ("ternary_pallas", "bcq_xla"):
            m = Model(cfg.replace(quant=cfg.quant.replace(backend=backend)))
            eng = ServeEngine(m, qparams, slots=2, cache_len=64,
                              prefill_buckets=(16,))
            done = eng.run([Request(uid=i, prompt=p, max_new_tokens=4)
                            for i, p in enumerate(prompts)])
            outs[backend] = {r.uid: list(r.out_tokens) for r in done}
        assert outs["ternary_pallas"] == outs["bcq_xla"]
