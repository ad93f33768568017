"""Beyond-paper TPU-native execution of BCQ weights: dequant-in-VMEM matmul.

FIGLUT's LUT read replaces an FP adder — a win for CMOS energy, but a TPU's
MXU performs a 128x128 systolic matmul at fixed cost whether operands are
+-1 or arbitrary bf16.  The *transferable* win of the BCQ format on TPU is
that weights live in HBM as packed uint8 bit-planes (q/16 of bf16 bytes):
LLM decode is memory-bound, so cutting weight bytes moves the memory-
roofline term directly.

This kernel streams packed planes HBM->VMEM and, per byte chunk of the
reduction dim, reconstructs the weight tile in VMEM one bit slab at a
time (shift/mask unpack + alpha-scaled accumulate + offset) and issues
one MXU matmul per slab against the matching activation slab.  Same math
as the LUT kernel, same compressed storage, MXU-native compute.  The bit
slab layout is described in ``kernels/lut_common.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lut_common import (dot_nt, expand_groups, for_each_chunk,
                                      plane_call, plane_pm1)


def _bcq_matmul_kernel(x_ref, packed_ref, alpha_ref, z_ref, o_ref, *,
                       group_size: int, chunk: int):
    q, _, n_bytes = packed_ref.shape
    gb = group_size // 8

    def body(off, acc):
        cols = pl.ds(off, chunk)
        planes = [packed_ref[i, :, cols].astype(jnp.int32) for i in range(q)]
        alphas = [expand_groups(alpha_ref[i], off, chunk, gb)
                  for i in range(q)]
        zc = expand_groups(z_ref[...], off, chunk, gb)
        for s in range(8):
            # dequantized slab s:  W = sum_i alpha_i * B_i + z
            w = alphas[0] * plane_pm1(planes[0], s)
            for i in range(1, q):
                w = w + alphas[i] * plane_pm1(planes[i], s)
            acc = acc + dot_nt(x_ref[s, :, cols], w + zc)
        return acc

    o_ref[...] = for_each_chunk(n_bytes, chunk, body,
                                jnp.zeros(o_ref.shape, jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "block_b", "block_m", "block_n",
                     "interpret"),
)
def bcq_matmul_tiled(xs, packed, alpha, z, *, group_size: int = 128,
                     block_b: int = 8, block_m: int = 128,
                     block_n: int = 1024, interpret: bool = False):
    """Raw tiled call; dims must divide blocks.  xs: f32 [8, B, N//8]
    activation bit slabs -> f32 [B, M].  ``block_n`` is the reduction
    chunk in weights."""
    _, b, nb = xs.shape
    _, m, _ = packed.shape
    chunk = block_n // 8
    assert nb % chunk == 0 and m % block_m == 0 and b % block_b == 0
    assert block_n % group_size == 0 and group_size % 8 == 0
    kernel = functools.partial(_bcq_matmul_kernel, group_size=group_size,
                               chunk=chunk)
    return plane_call(kernel, xs, packed, alpha, z, block_b=block_b,
                      block_m=block_m, interpret=interpret)
