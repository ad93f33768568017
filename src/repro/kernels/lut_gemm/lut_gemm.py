"""Pallas TPU kernel for FIGLUT's LUT-based FP-INT GEMM (paper §III).

Per (out-tile, batch-tile) grid cell, over byte chunks of the reduction
dim (``block_n`` weights per chunk):

  1. **LUT generation** (§III-E): the chunk's activation bit slabs are
     combined into the mu-group tables — entry p of a key position is
     the signed sum of its mu slabs.  With ``half_lut=True`` only the
     MSB=1 half of each table is built (hFFLUT, §III-D).
  2. **RAC** (§III-C): every output row's mu-bit weight pattern keys a
     read from the VMEM-resident table, either as a one-hot contraction
     per entry on the MXU (``onehot``) or as a 2^mu-way mux sweep on the
     VPU (``select``, the paper's multiplexer).  VMEM has no banking at
     the Pallas level, so the k = block_m concurrent readers are
     conflict-free by construction.
  3. **bit-serial accumulate** (§III-B): each plane's reads are weighted
     by its per-group alpha (expanded to the chunk's bytes) and summed
     in FP32; the offset term z * sum(x_group) (Eq. (3)) is folded in
     per chunk.

Storage streamed from HBM is the *packed* uint8 bit-planes — q/16 of the
bf16 dense bytes — which is the memory-roofline win on TPU.  Layout
choices that keep the body compilable are documented in
``kernels/lut_common.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lut_common import (ReadMode, build_lut, dot_nt,
                                      expand_groups, for_each_chunk,
                                      plane_call, read_lut)


def _lut_gemm_kernel(x_ref, packed_ref, alpha_ref, z_ref, o_ref, *,
                     mu: int, half_lut: bool, group_size: int,
                     read_mode: ReadMode, chunk: int):
    q, _, n_bytes = packed_ref.shape
    gb = group_size // 8
    keys_per_byte = 8 // mu
    key_mask = (1 << mu) - 1

    def body(off, acc):
        cols = pl.ds(off, chunk)
        slabs = [x_ref[s, :, cols] for s in range(8)]
        tables = [build_lut(slabs, h, mu, half_lut)
                  for h in range(keys_per_byte)]
        for i in range(q):
            byte = packed_ref[i, :, cols].astype(jnp.int32)
            a = expand_groups(alpha_ref[i], off, chunk, gb)
            for h in range(keys_per_byte):
                acc = acc + read_lut(tables[h], (byte >> (mu * h)) & key_mask,
                                     a, mu, half_lut, read_mode)
        # offset term: z[m, G(n)] * x[b, n] summed over the chunk's bytes
        xsum = functools.reduce(lambda u, v: u + v, slabs)
        return acc + dot_nt(xsum, expand_groups(z_ref[...], off, chunk, gb))

    o_ref[...] = for_each_chunk(n_bytes, chunk, body,
                                jnp.zeros(o_ref.shape, jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("mu", "half_lut", "group_size", "read_mode",
                     "block_b", "block_m", "block_n", "interpret"),
)
def lut_gemm_tiled(xs, packed, alpha, z, *, mu: int = 4,
                   half_lut: bool = True, group_size: int = 128,
                   read_mode: ReadMode = "onehot", block_b: int = 8,
                   block_m: int = 128, block_n: int = 1024,
                   interpret: bool = False):
    """Raw tiled kernel call. All dims must already divide the blocks.

    xs: f32 [8, B, N//8] activation bit slabs
    (``core.plane.bit_slabs``); packed: uint8[q, M, N//8];
    alpha: f32[q, M, N//group_size]; z: f32[M, N//group_size].
    ``block_n`` is the reduction chunk in weights.  Returns f32 [B, M].
    """
    _, b, nb = xs.shape
    _, m, _ = packed.shape
    chunk = block_n // 8
    assert nb % chunk == 0 and m % block_m == 0 and b % block_b == 0, (
        f"shapes ({b},{m},{nb * 8}) must divide blocks "
        f"({block_b},{block_m},{block_n})")
    assert block_n % group_size == 0 and group_size % 8 == 0
    assert 8 % mu == 0, f"mu={mu} must divide 8"
    kernel = functools.partial(
        _lut_gemm_kernel, mu=mu, half_lut=half_lut, group_size=group_size,
        read_mode=read_mode, chunk=chunk)
    return plane_call(kernel, xs, packed, alpha, z, block_b=block_b,
                      block_m=block_m, interpret=interpret)
