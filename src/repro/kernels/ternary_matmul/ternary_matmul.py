"""Pallas TPU kernel for the dedicated ternary (1.58-bit) fast path.

The ternary bundle stores ONE sign plane + ONE nonzero-mask plane and a
single shared-magnitude alpha row (``core.plane.PlaneBundle`` with
``kind="ternary"``) — strictly fewer HBM bytes than the generic 2-plane
BCQ encoding it replaces.  This kernel exploits that ±α structure
in-kernel instead of riding the generic bit-serial path:

  1. **half-LUT build** (§III-D/E): one half-size activation table per
     mu-group, shared by both derived planes — the hFFLUT symmetry
     LUT[p] = -LUT[2^mu-1-p] means ternary pays ONE table for what the
     generic 2-bit path reads as two.
  2. **in-kernel sign decode** (the paper's sign-decoding unit): the
     BCQ planes b1 = sign | ~mask, b2 = sign & mask are derived with two
     bitwise byte ops from the stored (sign, mask) bytes — no second
     stored plane, no second alpha row (``lut_common.ternary_plane_bytes``).
  3. **single-alpha accumulate**: y += (a/2) * (V1 + V2) per alpha
     group; there is no offset term (ternary has none), so the z row,
     its DMA and its epilogue all disappear.

The bit slab layout and LUT reads are shared with ``lut_gemm``
(``kernels/lut_common.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lut_common import (ReadMode, build_lut, expand_groups,
                                      for_each_chunk, plane_call, read_lut,
                                      ternary_plane_bytes)


def _ternary_matmul_kernel(x_ref, packed_ref, alpha_ref, o_ref, *,
                           mu: int, group_size: int, read_mode: ReadMode,
                           chunk: int):
    n_bytes = packed_ref.shape[-1]
    gb = group_size // 8
    keys_per_byte = 8 // mu
    key_mask = (1 << mu) - 1

    def body(off, acc):
        cols = pl.ds(off, chunk)
        slabs = [x_ref[s, :, cols] for s in range(8)]
        # -- 1. one half-size LUT per key position for both planes -------
        tables = [build_lut(slabs, h, mu, True) for h in range(keys_per_byte)]
        # -- 2. sign decode: (sign, mask) bytes -> b1/b2 plane bytes -----
        b1, b2 = ternary_plane_bytes(packed_ref[0, :, cols].astype(jnp.int32),
                                     packed_ref[1, :, cols].astype(jnp.int32))
        # -- 3. single-alpha accumulate:  y += (a/2) (V1 + V2) ------------
        half_alpha = expand_groups(alpha_ref[0], off, chunk, gb) * 0.5
        for plane in (b1, b2):
            for h in range(keys_per_byte):
                acc = acc + read_lut(tables[h], (plane >> (mu * h)) & key_mask,
                                     half_alpha, mu, True, read_mode)
        return acc

    o_ref[...] = for_each_chunk(n_bytes, chunk, body,
                                jnp.zeros(o_ref.shape, jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("mu", "group_size", "read_mode", "block_b", "block_m",
                     "block_n", "interpret"),
)
def ternary_matmul_tiled(xs, packed, alpha, *, mu: int = 4,
                         group_size: int = 128, read_mode: ReadMode = "onehot",
                         block_b: int = 8, block_m: int = 128,
                         block_n: int = 1024, interpret: bool = False):
    """Raw tiled kernel call. All dims must already divide the blocks.

    xs: f32 [8, B, N//8] activation bit slabs; packed: uint8[2, M, N//8]
    (plane 0 = sign, plane 1 = mask); alpha: f32[1, M, N//group_size].
    Returns f32 [B, M] (FP32 accumulation).
    """
    _, b, nb = xs.shape
    q, m, _ = packed.shape
    chunk = block_n // 8
    assert q == 2, f"ternary bundle stores sign+mask planes, got {q}"
    assert alpha.shape[0] == 1, "ternary carries a single alpha row"
    assert nb % chunk == 0 and m % block_m == 0 and b % block_b == 0, (
        f"shapes ({b},{m},{nb * 8}) must divide blocks "
        f"({block_b},{block_m},{block_n})")
    assert block_n % group_size == 0 and group_size % 8 == 0
    assert 8 % mu == 0, f"mu={mu} must divide 8"
    kernel = functools.partial(
        _ternary_matmul_kernel, mu=mu, group_size=group_size,
        read_mode=read_mode, chunk=chunk)
    return plane_call(kernel, xs, packed, alpha, None, block_b=block_b,
                      block_m=block_m, interpret=interpret)
