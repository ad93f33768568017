"""Public jit'd wrapper for the BCQ dequant-in-VMEM matmul kernel.

Block sizes left as ``None`` resolve through
:func:`repro.tune.dispatch.kernel_config` (tuned cache entry or the
deterministic heuristic); explicit arguments always win.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bcq import BCQWeight
from repro.core.plane import tile_operands
from repro.tune import dispatch as _dispatch
from . import bcq_matmul as _k


def bcq_matmul(x: jax.Array, w: BCQWeight, *, block_b: Optional[int] = None,
               block_m: Optional[int] = None, block_n: Optional[int] = None,
               interpret: bool = False, out_dtype=None) -> jax.Array:
    """y = x @ dequant(w).T via the TPU-native packed-weight kernel."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    n_logical = x.shape[-1]
    if n_logical != w.in_features:
        raise ValueError(f"x last dim {n_logical} != in_features {w.in_features}")

    x2 = x.reshape(-1, n_logical)
    b = x2.shape[0]

    if None in (block_b, block_m, block_n):
        cfg = _dispatch.kernel_config(
            "bcq_matmul", b=b, m=w.out_features, n=w.in_features,
            dtype=x2.dtype, mu=0, group_size=w.group_size,
            interpret=interpret, operands=(x2, w))
        block_b = cfg.block_b if block_b is None else block_b
        block_m = cfg.block_m if block_m is None else block_m
        block_n = cfg.block_n if block_n is None else block_n

    xs, packed, alpha, z, b, m, block_m, block_n = tile_operands(
        x2, w, block_b=block_b, block_m=block_m, block_n=block_n)

    y = _k.bcq_matmul_tiled(
        xs, packed, alpha, z, group_size=w.group_size, block_b=block_b,
        block_m=block_m, block_n=block_n, interpret=interpret)
    return y[:b, :m].reshape(*lead, m).astype(out_dtype)
