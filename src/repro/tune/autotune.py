"""Measurement-driven tuner for the Pallas kernels.

For one (kernel, activation, BCQWeight) problem the tuner enumerates the
clamped candidate space, runs every candidate once against the kernel's
reference oracle (``lut_gemm`` candidates must match ``ref.lut_ref``,
``bcq_matmul`` candidates ``ref.bcq_matmul_ref``) and only then times the
survivors with the median-of-k harness.  A config that crashes or
mis-computes is recorded but can never win.  Candidate 0 is always the
deterministic heuristic, so ``best_time <= default_time`` by
construction — tuning can only help.

Winners persist in the JSON :class:`~repro.tune.cache.TuneCache`;
``pretune_params`` walks a quantized params tree, collects the distinct
GEMM problems actually served, and tunes each once per batch bucket —
the warm-up path the serve engine and ``python -m repro.tune`` share.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bcq import BCQWeight, from_uniform

from . import cache as cache_mod
from .measure import measure
from .space import KernelConfig, candidate_configs


@dataclasses.dataclass
class Timing:
    config: KernelConfig
    seconds: float          # inf when invalid
    ok: bool
    error: str = ""


@dataclasses.dataclass
class TuneResult:
    kernel: str
    key: str
    best: KernelConfig
    best_time: float
    default_time: float
    timings: list

    @property
    def speedup(self) -> float:
        """Tuned-vs-heuristic speedup (>= 1.0 by construction)."""
        return self.default_time / max(self.best_time, 1e-12)


def _kernel_fns(kernel: str):
    """(op, oracle) for a kernel — lazy so importing repro.tune stays
    cheap and cycle-free (the op wrappers import repro.tune.dispatch)."""
    if kernel == "lut_gemm":
        from repro.kernels.lut_gemm import lut_gemm, ref
        return lut_gemm, ref.lut_ref
    if kernel == "bcq_matmul":
        from repro.kernels.bcq_matmul import bcq_matmul, ref
        return bcq_matmul, ref.bcq_matmul_ref
    if kernel == "ternary_matmul":
        from repro.kernels.ternary_matmul import ternary_matmul, ref
        return ternary_matmul, ref.ternary_ref
    raise ValueError(f"unknown kernel {kernel!r}")


def _default_interpret() -> bool:
    from repro.core import lut_gemm as core_lg
    return core_lg.interpret_mode()


def tune(kernel: str, x: jax.Array, w: BCQWeight, *, mu: int = 4,
         reps: int = 5, warmup: int = 2, max_candidates: int = 0,
         atol: float = 1e-3, interpret: Optional[bool] = None,
         cache: Optional[cache_mod.TuneCache] = None,
         verbose: bool = False) -> TuneResult:
    """Tune one problem; returns the winner (cached if ``cache`` given)."""
    interpret = _default_interpret() if interpret is None else interpret
    op, oracle = _kernel_fns(kernel)

    x2 = x.reshape(-1, x.shape[-1])
    b, m, nn = x2.shape[0], w.out_features, w.in_features
    # mu only affects the LUT-reading kernels; key it as 0 for bcq_matmul
    # so the cache key matches what the op wrapper's dispatch looks up.
    lut_like = kernel in ("lut_gemm", "ternary_matmul")
    key_mu = mu if lut_like else 0
    key = cache_mod.cache_key(kernel, b=b, m=m, n=nn, dtype=x2.dtype,
                              mu=key_mu, group_size=w.group_size,
                              interpret=interpret)
    cands = candidate_configs(kernel, b=b, m=m, n=nn, mu=mu,
                              group_size=w.group_size,
                              max_candidates=max_candidates)
    if lut_like:
        want = np.asarray(oracle(x2, w, mu=mu, out_dtype=jnp.float32))
    else:
        want = np.asarray(oracle(x2, w, out_dtype=jnp.float32))
    scale = float(np.abs(want).max()) + 1e-6

    timings = []
    for cfg in cands:
        kw = cfg.to_kwargs(kernel)
        if lut_like:
            kw["mu"] = mu
        run = lambda kw=kw: op(x2, w, interpret=interpret,
                               out_dtype=jnp.float32, **kw)
        try:
            got = np.asarray(jax.block_until_ready(run()))
            err = float(np.abs(got - want).max()) / scale
            if not np.isfinite(err) or err > atol:
                raise AssertionError(f"max rel err {err:.2e} > {atol:.0e}")
            secs = measure(run, n=reps, warmup=warmup)
            timings.append(Timing(cfg, secs, True))
        except Exception as e:                    # invalid launch: record, skip
            timings.append(Timing(cfg, float("inf"), False,
                                  f"{type(e).__name__}: {e}"))
        if verbose:
            t = timings[-1]
            state = f"{t.seconds * 1e3:9.3f} ms" if t.ok else f"INVALID ({t.error[:60]})"
            print(f"[tune] {kernel} {cfg.to_kwargs(kernel)} -> {state}")

    valid = [t for t in timings if t.ok]
    if not valid:
        raise RuntimeError(
            f"no valid config for {kernel} on b={b} m={m} n={nn} "
            f"(first error: {timings[0].error})")
    best = min(valid, key=lambda t: t.seconds)
    default_time = timings[0].seconds if timings[0].ok else best.seconds
    result = TuneResult(kernel=kernel, key=key, best=best.config,
                        best_time=best.seconds, default_time=default_time,
                        timings=timings)
    if cache is not None:
        cache.store(key, best.config, time_s=best.seconds,
                    default_time_s=default_time,
                    speedup=round(result.speedup, 4),
                    shape=[b, m, nn], n_candidates=len(cands))
    return result


# ---------------------------------------------------------------------------
# shape-level helpers (synthesize operands; used by CLI / serve pretune)
# ---------------------------------------------------------------------------


def tune_shape(kernel: str, *, b: int, m: int, n: int, bits: int = 4,
               group_size: int = 128, mu: int = 4, dtype=jnp.float32,
               seed: int = 0, **kw) -> TuneResult:
    """Tune a synthetic (b, m, n) problem — tuning depends on shapes and
    dtypes, not weight values, so RTN-quantized gaussian weights stand in
    for the real layer (ternary-quantized for the ternary kernel)."""
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(b, n)).astype(np.float32), dtype=dtype)
    if kernel == "ternary_matmul":
        from repro.quant.formats import quantize_ternary    # lazy: registry
        wq = quantize_ternary(W, group_size=group_size)
    else:
        wq = from_uniform(W, bits=bits, group_size=group_size)
    return tune(kernel, x, wq, mu=mu, **kw)


def collect_bcq_specs(params) -> list:
    """Distinct (out_features, in_features, bits, group_size, kind)
    across every plane-bundle leaf (scan-stacked leaves count once — the
    per-layer GEMM problem is identical)."""
    from repro.quant.ptq import _walk          # shared pytree walker
    specs = []
    for _, leaf in _walk(params):
        if isinstance(leaf, BCQWeight):
            spec = (leaf.out_features, leaf.in_features,
                    int(leaf.packed.shape[-3]), leaf.group_size, leaf.kind)
            if spec not in specs:
                specs.append(spec)
    return specs


def pretune_params(params, *, kernels: Sequence[str] = ("lut_gemm",),
                   batch_sizes: Sequence[int] = (1, 8), mu: int = 4,
                   dtype=jnp.float32, cache: Optional[cache_mod.TuneCache] = None,
                   save: bool = True, verbose: bool = False,
                   **kw) -> list:
    """Tune every distinct GEMM problem a quantized params tree serves.

    Returns the list of :class:`TuneResult`; persists winners into
    ``cache`` (the process default when None) and saves the JSON file.
    """
    cache = cache_mod.default_cache() if cache is None else cache
    specs = collect_bcq_specs(params)
    results = []
    done = set()
    for m, n, bits, group_size, kind in specs:
        # ternary layers serve through the dedicated kernel only; bcq
        # layers tune whatever the caller asked for
        use_kernels = ("ternary_matmul",) if kind == "ternary" else kernels
        for b in batch_sizes:
            for kernel in use_kernels:
                # batch sizes sharing a pow2 bucket share a cache key
                tag = (kernel, m, n, bits, group_size,
                       cache_mod.bucket_batch(b))
                if tag in done:
                    continue
                done.add(tag)
                res = tune_shape(kernel, b=b, m=m, n=n, bits=bits,
                                 group_size=group_size, mu=mu, dtype=dtype,
                                 cache=cache, verbose=verbose, **kw)
                results.append(res)
                if verbose:
                    print(f"[pretune] {res.key}: best {res.best_time*1e3:.3f} ms "
                          f"(x{res.speedup:.2f} vs default) {res.best.to_kwargs(kernel)}")
    if save and results:
        cache.save()
    return results
