"""Public jit'd wrapper for the ternary fast-path kernel.

Accepts only ``kind="ternary"`` plane bundles (sign + mask planes, one
alpha row, no offset).  Launch geometry left as ``None`` resolves
through :func:`repro.tune.dispatch.kernel_config` under the
``"ternary_matmul"`` kernel name (tuned cache entry or the heuristic);
explicit arguments always win.  Tile padding is the shared
:func:`repro.core.plane.tile_operands` admission step — no layout math
lives here.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.plane import PlaneBundle, tile_operands
from repro.tune import dispatch as _dispatch
from . import ternary_matmul as _k


def ternary_matmul(x: jax.Array, w: PlaneBundle, *, mu: int = 4,
                   read_mode: Optional[str] = None,
                   block_b: Optional[int] = None,
                   block_m: Optional[int] = None,
                   block_n: Optional[int] = None, interpret: bool = False,
                   out_dtype=None) -> jax.Array:
    """y = x @ dequant(w).T via the dedicated ternary Pallas kernel.

    x: [..., in_features] -> [..., out_features].  FP32 accumulation.
    """
    if w.kind != "ternary":
        raise ValueError(
            f"ternary_matmul needs a kind='ternary' bundle, got {w.kind!r}; "
            "generic BCQ weights take the lut_gemm/bcq_matmul kernels")
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    n_logical = x.shape[-1]
    if n_logical != w.in_features:
        raise ValueError(f"x last dim {n_logical} != in_features {w.in_features}")

    x2 = x.reshape(-1, n_logical)
    b = x2.shape[0]

    if None in (read_mode, block_b, block_m, block_n):
        cfg = _dispatch.kernel_config(
            "ternary_matmul", b=b, m=w.out_features, n=w.in_features,
            dtype=x2.dtype, mu=mu, group_size=w.group_size,
            interpret=interpret, operands=(x2, w))
        read_mode = cfg.read_mode if read_mode is None else read_mode
        block_b = cfg.block_b if block_b is None else block_b
        block_m = cfg.block_m if block_m is None else block_m
        block_n = cfg.block_n if block_n is None else block_n

    xs, packed, alpha, _, b, m, block_m, block_n = tile_operands(
        x2, w, block_b=block_b, block_m=block_m, block_n=block_n)

    y = _k.ternary_matmul_tiled(
        xs, packed, alpha, mu=mu, group_size=w.group_size,
        read_mode=read_mode, block_b=block_b, block_m=block_m,
        block_n=block_n, interpret=interpret)
    return y[:b, :m].reshape(*lead, m).astype(out_dtype)
