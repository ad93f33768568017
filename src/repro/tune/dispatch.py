"""The single dispatch point that picks a launch config for a kernel call.

Every ``lut_gemm`` / ``bcq_matmul`` call whose caller did not pin the
geometry lands here.  Resolution order:

  1. tuned entry in the JSON cache (keyed per cache.cache_key), unless
     tuning is disabled;
  2. with ``REPRO_TUNE=auto`` and a real device (not interpret mode):
     tune on miss with the live operands, persist, return the winner;
  3. deterministic heuristic (seed defaults clamped to the shape).

``REPRO_TUNE`` modes: ``on`` (default — cache then heuristic), ``off`` /
``0`` (heuristic only; fully deterministic, no file IO), ``auto``
(tune-on-miss).  Config resolution is shape-driven and happens eagerly in
the op wrappers — shapes are static even under jit tracing, so dispatch
adds no traced ops.
"""
from __future__ import annotations

import os
from typing import Optional

from . import cache as cache_mod
from .space import KernelConfig, clamp_config, heuristic_config

_ENV_MODE = "REPRO_TUNE"


def tune_mode() -> str:
    mode = os.environ.get(_ENV_MODE, "on").strip().lower()
    if mode in ("off", "0", "heuristic", "disable", "disabled"):
        return "off"
    if mode == "auto":
        return "auto"
    return "on"


def kernel_unsupported_reason(kernel: str, *, m: int, n: int,
                              group_size: int, bits: Optional[int] = None,
                              **caps) -> Optional[str]:
    """Capability probe: ``None`` when the Pallas kernel can launch the
    problem, else the SPECIFIC cap that failed (so callers, traces and
    tests can assert *why* a launch negotiated down to the gathered
    path instead of collapsing every reason into one boolean).

    For the GEMM kernels (callers: the quant backend registry,
    :mod:`repro.quant.backends`) ``(m, n)`` are the weight dims.  The
    limits are the chip's, not the interpreter's: scale groups cover
    whole packed bytes (group_size % 8 == 0, reason ``"group_size"``);
    the bit-serial loop streams 1..8 planes (``"bits"``); the LUT
    kernels build tables for key widths mu in ``LUT_MUS`` (cap ``mu``,
    reason ``"mu"``); and every block spans the whole reduction dim, so
    the heuristic launch's double-buffered blocks must fit the kernels'
    scoped VMEM (reason ``"vmem"``).

    For the paged-attention family (``paged_attention`` decode,
    ``paged_prefill`` chunked prefill; caller: ``models.attention``'s
    routers) the dims are remapped — ``m`` is the total q-head count,
    ``n`` the per-sequence KV capacity, ``group_size`` the pool block
    size — and ``caps`` carries the variant axes:

      * ``n_kv_heads``  — q heads must group evenly over kv heads
        (reason ``"heads"``);
      * ``tp``          — model-axis shard count when the serve engine
        runs the kernel per-shard under ``shard_map``: both head counts
        must divide the mesh so every shard sees whole GQA groups
        (reason ``"tp"``);
      * ``kv_dtype``    — float AND int8 pools are covered (the int8
        kernels fold the per-slot scales in-kernel); anything else is
        reason ``"kv_dtype"``;
      * ``window``      — sliding-window masking still gathers (ring
        caches are not paged, so this is only reachable through direct
        op calls; reason ``"window"``);
      * ``latent``      — MLA absorbed decode is fused
        (``paged_attention``), but MLA *prefill* needs the
        decompressing ``kv_map_fn`` and stays gathered
        (``paged_prefill`` reason ``"latent"``).

    The GEMM kernels additionally accept a ``kind`` cap (the
    PlaneBundle layout kind): ``ternary_matmul`` only consumes
    ``kind="ternary"`` bundles, while ``lut_gemm``/``bcq_matmul`` read
    generic ``kind="bcq"`` planes (reason ``"kind"`` either way).

    Reasons: ``"unknown_kernel"``, ``"tp"``, ``"heads"``, ``"shape"``,
    ``"window"``, ``"kv_dtype"``, ``"latent"``, ``"group_size"``,
    ``"bits"``, ``"kind"``, ``"mu"``, ``"vmem"``.  Every non-None return
    is also recorded on the active trace (``record_kernel_unsupported``).
    """
    reason = _unsupported_reason(kernel, m=m, n=n, group_size=group_size,
                                 bits=bits, **caps)
    if reason is not None:
        from repro.obs.trace import record_kernel_unsupported
        record_kernel_unsupported(kernel, reason, m=m, n=n)
    return reason


def _unsupported_reason(kernel: str, *, m: int, n: int, group_size: int,
                        bits: Optional[int] = None,
                        **caps) -> Optional[str]:
    from .space import KERNELS, PAGED_KERNELS
    if kernel not in KERNELS:
        return "unknown_kernel"
    if kernel in PAGED_KERNELS:
        hkv = int(caps.get("n_kv_heads", m) or m)
        tp = int(caps.get("tp", 1) or 1)
        if tp < 1 or m % tp or hkv % tp:
            return "tp"
        m, hkv = m // tp, hkv // tp            # per-shard head counts
        if m < 1 or hkv < 1 or m % hkv:
            return "heads"
        if n < 1 or group_size < 1:
            return "shape"
        if caps.get("window", 0):
            return "window"
        latent = bool(caps.get("latent", False))
        if latent and kernel == "paged_prefill":
            return "latent"                    # kv_map_fn decompression
        dt = caps.get("kv_dtype")
        if dt is not None and not latent:
            import jax.numpy as jnp
            dt = jnp.dtype(dt)
            if not (jnp.issubdtype(dt, jnp.floating) or dt == jnp.int8):
                return "kv_dtype"
        return None
    if m < 1 or n < 1:
        return "shape"
    if group_size < 8 or group_size % 8:
        return "group_size"
    if bits is not None and not 1 <= bits <= 8:
        return "bits"
    kind = caps.get("kind")
    if kind is not None:
        if kernel == "ternary_matmul" and kind != "ternary":
            return "kind"
        if kernel != "ternary_matmul" and kind == "ternary":
            return "kind"
    from repro.kernels.lut_common import (LUT_MUS, VMEM_LIMIT,
                                          plane_vmem_bytes)
    from .space import LUT_KERNELS
    if kernel in LUT_KERNELS and caps.get("mu", 4) not in LUT_MUS:
        return "mu"
    cfg = heuristic_config(kernel, b=8, m=m, n=n, group_size=group_size)
    n_pad = -(-n // cfg.block_n) * cfg.block_n
    q = 2 if kernel == "ternary_matmul" else (bits or 8)
    need = plane_vmem_bytes(q=q, n_alpha=q, block_b=cfg.block_b,
                            block_m=cfg.block_m, n_bytes=n_pad // 8,
                            n_groups=n_pad // group_size,
                            chunk=min(cfg.block_n, n_pad) // 8)
    if need > VMEM_LIMIT:
        return "vmem"
    return None


def kernel_supports(kernel: str, *, m: int, n: int, group_size: int,
                    bits: Optional[int] = None, **caps) -> bool:
    """Boolean view of :func:`kernel_unsupported_reason` (True == the
    kernel can launch this problem)."""
    return kernel_unsupported_reason(kernel, m=m, n=n,
                                     group_size=group_size, bits=bits,
                                     **caps) is None


def kernel_config(kernel: str, *, b: int, m: int, n: int, dtype,
                  mu: int = 0, group_size: int = 128,
                  interpret: bool = False,
                  operands=None) -> KernelConfig:
    """Resolve the launch config for one (kernel, problem) point.

    b/m/n are the *logical* batch rows, out_features, in_features;
    ``operands=(x2, w)`` (2-D activations + BCQWeight) enables
    tune-on-miss under ``REPRO_TUNE=auto``.
    """
    from repro.obs.trace import record_kernel_config
    mode = tune_mode()
    if mode != "off":
        key = cache_mod.cache_key(kernel, b=b, m=m, n=n, dtype=dtype,
                                  mu=mu, group_size=group_size,
                                  interpret=interpret)
        hit = cache_mod.default_cache().lookup(key)
        if hit is not None:
            cfg = clamp_config(hit, kernel, b=b, m=m, n=n,
                               group_size=group_size)
            record_kernel_config(kernel, "cache", cfg, b=b, m=m, n=n)
            return cfg
        if mode == "auto" and not interpret and operands is not None:
            import jax
            if not any(isinstance(o, jax.core.Tracer) for o in operands):
                # concrete operands only — under jit tracing we fall through
                # to the heuristic (tune offline with `python -m repro.tune`)
                from . import autotune                # lazy: avoids cycle
                res = autotune.tune(kernel, *operands, mu=mu or 4,
                                    cache=cache_mod.default_cache(),
                                    interpret=interpret)
                cache_mod.default_cache().save()
                record_kernel_config(kernel, "tuned", res.best,
                                     b=b, m=m, n=n)
                return res.best
    cfg = heuristic_config(kernel, b=b, m=m, n=n, mu=mu or 4,
                           group_size=group_size)
    # traces show tuned-vs-fallback launch choices: "cache"/"tuned"
    # resolutions above vs this deterministic heuristic default
    record_kernel_config(kernel, "heuristic", cfg, b=b, m=m, n=n)
    return cfg
