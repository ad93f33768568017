"""Public jit'd wrapper for the FIGLUT Pallas kernel.

Handles arbitrary leading batch dims, pads (B, M, N) up to block multiples,
and dispatches to :func:`lut_gemm_tiled`.  The oracle for every path is
``ref.lut_ref`` / ``ref.dense_ref``.

Launch geometry (block sizes, read mode, hFFLUT) is no longer hard-coded:
any parameter left as ``None`` is resolved through
:func:`repro.tune.dispatch.kernel_config` — tuned JSON-cache entry if one
exists for this (batch-bucket, M, N, dtype, mu, group, device) point,
deterministic heuristic otherwise.  Explicit arguments always win, so
tests and the tuner itself can pin exact launches.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bcq import BCQWeight
from repro.core.plane import tile_operands
from repro.tune import dispatch as _dispatch
from . import lut_gemm as _k


def lut_gemm(x: jax.Array, w: BCQWeight, *, mu: int = 4,
             half_lut: Optional[bool] = None, read_mode: Optional[str] = None,
             block_b: Optional[int] = None, block_m: Optional[int] = None,
             block_n: Optional[int] = None, interpret: bool = False,
             out_dtype=None) -> jax.Array:
    """y = x @ dequant(w).T via the FIGLUT Pallas kernel.

    x: [..., in_features] -> [..., out_features].  FP32 accumulation.
    """
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    n_logical = x.shape[-1]
    if n_logical != w.in_features:
        raise ValueError(f"x last dim {n_logical} != in_features {w.in_features}")

    x2 = x.reshape(-1, n_logical)
    b = x2.shape[0]

    if None in (half_lut, read_mode, block_b, block_m, block_n):
        cfg = _dispatch.kernel_config(
            "lut_gemm", b=b, m=w.out_features, n=w.in_features,
            dtype=x2.dtype, mu=mu, group_size=w.group_size,
            interpret=interpret, operands=(x2, w))
        half_lut = cfg.half_lut if half_lut is None else half_lut
        read_mode = cfg.read_mode if read_mode is None else read_mode
        block_b = cfg.block_b if block_b is None else block_b
        block_m = cfg.block_m if block_m is None else block_m
        block_n = cfg.block_n if block_n is None else block_n

    xs, packed, alpha, z, b, m, block_m, block_n = tile_operands(
        x2, w, block_b=block_b, block_m=block_m, block_n=block_n)

    y = _k.lut_gemm_tiled(
        xs, packed, alpha, z, mu=mu, half_lut=half_lut,
        group_size=w.group_size, read_mode=read_mode, block_b=block_b,
        block_m=block_m, block_n=block_n, interpret=interpret)
    return y[:b, :m].reshape(*lead, m).astype(out_dtype)
