"""Tests for the repro.tune autotuning + dispatch subsystem.

Pins the three contracts the serving path relies on:

  * the JSON cache round-trips deterministically (same entries -> byte-
    identical file; reload -> identical configs),
  * dispatch falls back to the deterministic heuristic when tuning is
    disabled or the cache is cold,
  * every candidate the tuner can emit computes the same answer as the
    ``ref`` oracles in interpret mode — a config can change speed, never
    math.
"""
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro import tune as T
from repro.core import bcq
from repro.kernels.bcq_matmul import bcq_matmul, ref as bref
from repro.kernels.lut_gemm import lut_gemm, ref as lref
from repro.tune import dispatch as tdispatch


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test sees its own empty cache file and default tune mode."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune_cache.json"))
    monkeypatch.delenv("REPRO_TUNE", raising=False)
    T.reset_default_cache()
    yield
    T.reset_default_cache()


def _problem(m=32, n=128, b=4, bits=2, group_size=64, seed=0):
    rng = np.random.default_rng(seed)
    W = jnp.array(rng.normal(size=(m, n)).astype(np.float32))
    x = jnp.array(rng.normal(size=(b, n)).astype(np.float32))
    return x, bcq.from_uniform(W, bits=bits, group_size=group_size)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


class TestCache:
    def test_key_buckets_batch(self):
        kw = dict(m=64, n=128, dtype="float32", mu=4, group_size=64,
                  device="cpu")
        k5 = T.cache_key("lut_gemm", b=5, **kw)
        k7 = T.cache_key("lut_gemm", b=7, **kw)
        k9 = T.cache_key("lut_gemm", b=9, **kw)
        assert k5 == k7          # same pow2 bucket
        assert k5 != k9          # next bucket
        assert T.bucket_batch(1) == 8 and T.bucket_batch(9) == 16

    def test_key_separates_interpret_from_device(self):
        kw = dict(b=8, m=64, n=128, dtype="float32", mu=4, group_size=64)
        assert T.cache_key("lut_gemm", interpret=True, **kw) \
            != T.cache_key("lut_gemm", interpret=False, **kw)

    def test_roundtrip_deterministic(self, tmp_path):
        path = str(tmp_path / "c.json")
        cfg = T.KernelConfig(8, 64, 256, "select", False)
        c1 = T.TuneCache(path)
        c1.store("k1", cfg, time_s=1.0)
        c1.store("k0", T.KernelConfig(), time_s=2.0)
        c1.save()
        first = open(path, "rb").read()
        # reload -> identical configs; save again -> identical bytes
        c2 = T.TuneCache(path)
        assert c2.lookup("k1") == cfg
        assert c2.lookup("k0") == T.KernelConfig()
        assert c2.lookup("missing") is None
        c2.save()
        assert open(path, "rb").read() == first
        blob = json.loads(first)
        assert blob["version"] == T.cache.SCHEMA_VERSION

    def test_corrupt_cache_treated_as_cold(self, tmp_path):
        path = tmp_path / "c.json"
        path.write_text("{not json")
        c = T.TuneCache(str(path))
        assert len(c) == 0 and c.lookup("anything") is None


# ---------------------------------------------------------------------------
# dispatch: heuristic fallback + cache hits
# ---------------------------------------------------------------------------


class TestDispatch:
    KW = dict(b=4, m=32, n=128, dtype="float32", mu=4, group_size=64,
              interpret=True)

    def test_cold_cache_returns_heuristic(self):
        got = T.kernel_config("lut_gemm", **self.KW)
        want = T.heuristic_config("lut_gemm", b=4, m=32, n=128, mu=4,
                                  group_size=64)
        assert got == want

    def test_disabled_ignores_cache(self, monkeypatch):
        tuned = T.KernelConfig(8, 32, 128, "select", False)
        cache = T.default_cache()
        key = T.cache_key("lut_gemm", b=4, m=32, n=128, dtype="float32",
                          mu=4, group_size=64, interpret=True)
        cache.store(key, tuned)
        assert T.kernel_config("lut_gemm", **self.KW) == tuned
        monkeypatch.setenv("REPRO_TUNE", "off")
        assert T.kernel_config("lut_gemm", **self.KW) \
            == T.heuristic_config("lut_gemm", b=4, m=32, n=128, mu=4,
                                  group_size=64)

    def test_cached_entry_is_clamped_to_shape(self):
        # a stale entry tuned for a bigger shape must still launch legally
        cache = T.default_cache()
        key = T.cache_key("lut_gemm", b=4, m=32, n=128, dtype="float32",
                          mu=4, group_size=64, interpret=True)
        cache.store(key, T.KernelConfig(32, 256, 1024, "select", True))
        got = T.kernel_config("lut_gemm", **self.KW)
        assert got.block_m <= 32 and got.block_n <= 128
        assert got.read_mode == "select"

    def test_heuristic_is_deterministic_and_legal(self):
        for (b, m, n, g) in [(1, 33, 130, 32), (8, 128, 512, 128),
                             (64, 1024, 4096, 128), (5, 96, 200, 64)]:
            c1 = T.heuristic_config("lut_gemm", b=b, m=m, n=n, group_size=g)
            c2 = T.heuristic_config("lut_gemm", b=b, m=m, n=n, group_size=g)
            assert c1 == c2
            assert c1.block_n % g == 0 and c1.block_m % 8 == 0

    def test_ops_route_through_dispatch(self, monkeypatch):
        calls = []
        real = tdispatch.kernel_config

        def spy(kernel, **kw):
            calls.append(kernel)
            return real(kernel, **kw)

        monkeypatch.setattr(tdispatch, "kernel_config", spy)
        x, wq = _problem()
        want = lref.dense_ref(x, wq)
        got = lut_gemm(x, wq, interpret=True)
        got2 = bcq_matmul(x, wq, interpret=True)
        assert calls == ["lut_gemm", "bcq_matmul"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                                   rtol=1e-4, atol=2e-4)

    def test_explicit_args_bypass_dispatch(self, monkeypatch):
        def boom(*a, **kw):
            raise AssertionError("dispatch must not be consulted")

        monkeypatch.setattr(tdispatch, "kernel_config", boom)
        x, wq = _problem()
        got = lut_gemm(x, wq, half_lut=True, read_mode="onehot", block_b=8,
                       block_m=32, block_n=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(lref.dense_ref(x, wq)),
                                   rtol=1e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# candidate space: every emittable config computes the right answer
# ---------------------------------------------------------------------------


class TestCandidates:
    def test_heuristic_is_candidate_zero(self):
        cands = T.candidate_configs("lut_gemm", b=4, m=32, n=128, mu=4,
                                    group_size=64)
        assert cands[0] == T.heuristic_config("lut_gemm", b=4, m=32, n=128,
                                              mu=4, group_size=64)
        assert len(cands) == len(set(cands))          # deduped

    def test_every_lut_candidate_matches_ref(self):
        x, wq = _problem(m=32, n=128, b=4, bits=2, group_size=64)
        want = np.asarray(lref.lut_ref(x, wq, mu=4, out_dtype=jnp.float32))
        scale = np.abs(want).max() + 1e-6
        cands = T.candidate_configs("lut_gemm", b=4, m=32, n=128, mu=4,
                                    group_size=64)
        assert len(cands) >= 4                        # read modes x half_lut
        for cfg in cands:
            got = np.asarray(lut_gemm(x, wq, mu=4, interpret=True,
                                      out_dtype=jnp.float32,
                                      **cfg.to_kwargs("lut_gemm")))
            np.testing.assert_allclose(got / scale, want / scale, atol=1e-4,
                                       err_msg=f"config {cfg}")

    def test_every_bcq_candidate_matches_ref(self):
        x, wq = _problem(m=40, n=192, b=4, bits=3, group_size=32)
        want = np.asarray(bref.bcq_matmul_ref(x, wq, out_dtype=jnp.float32))
        scale = np.abs(want).max() + 1e-6
        cands = T.candidate_configs("bcq_matmul", b=4, m=40, n=192,
                                    group_size=32)
        for cfg in cands:
            got = np.asarray(bcq_matmul(x, wq, interpret=True,
                                        out_dtype=jnp.float32,
                                        **cfg.to_kwargs("bcq_matmul")))
            np.testing.assert_allclose(got / scale, want / scale, atol=1e-4,
                                       err_msg=f"config {cfg}")


# ---------------------------------------------------------------------------
# tuner end-to-end
# ---------------------------------------------------------------------------


class TestTuner:
    def test_tune_persists_winner_and_dispatch_serves_it(self):
        x, wq = _problem()
        cache = T.default_cache()
        res = T.tune("lut_gemm", x, wq, mu=4, reps=1, warmup=0, cache=cache,
                     interpret=True)
        cache.save()
        # winner is a real candidate and can't lose to the default
        cands = T.candidate_configs("lut_gemm", b=4, m=32, n=128, mu=4,
                                    group_size=64)
        assert res.best in cands
        assert res.best_time <= res.default_time
        assert res.speedup >= 1.0
        assert all(t.ok for t in res.timings)
        # a fresh process-view of the cache serves the tuned config
        T.reset_default_cache()
        got = T.kernel_config("lut_gemm", b=4, m=32, n=128, dtype="float32",
                              mu=4, group_size=64, interpret=True)
        assert got == res.best

    def test_tune_shape_synthesizes_and_buckets(self):
        res = T.tune_shape("bcq_matmul", b=5, m=16, n=64, bits=2,
                           group_size=32, reps=1, warmup=0, interpret=True)
        assert "|b8|" in res.key          # 5 buckets to 8
        assert res.best_time > 0

    def test_collect_bcq_specs_dedupes(self):
        from repro.quant.formats import quantize_ternary
        _, wq = _problem(m=16, n=64, group_size=32, bits=2)
        wt = quantize_ternary(wq.dequantize(), group_size=32)
        params = {"a": {"q": wq, "k": wq}, "b": [wq, wt],
                  "dense": jnp.ones((4,))}
        # same shape, different layout kind -> two distinct GEMM problems
        assert T.collect_bcq_specs(params) == [(16, 64, 2, 32, "bcq"),
                                               (16, 64, 2, 32, "ternary")]
