"""Grouped BCQ matmul: rows sorted by expert against a bank of 3-D plane
bundles, reading only the planes of experts that got rows.

The rows of a mixture-of-experts layer arrive sorted by expert, each
expert's group padded to whole row tiles (``ops.expert_layout``).  The
grid walks the row tiles; three scalar-prefetched vectors say, per
tile, which expert's planes to copy (``tile_expert``), which row tile
to read and write (``tile_src``) and how many tiles hold rows
(``n_live``).  The ``index_map`` picks the expert's block from the
stored ``[E, q, N, K/8]`` planes, so consecutive tiles of one expert
keep its planes resident, and the steps past ``n_live`` repeat the last
live tile's blocks (nothing is copied) and skip compute.  An expert
without rows is never read.

Each live tile is the body of ``bcq_matmul``: per byte chunk of the
reduction dim and per bit slab, the weight tile is rebuilt in VMEM
(``sum_i alpha_i * B_i + z``, float32) and multiplied with the matching
activation slab on the MXU at full float32 precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lut_common import (VMEM_LIMIT, dot_nt, expand_groups,
                                      for_each_chunk, plane_pm1)


def _grouped_kernel(tile_expert, tile_src, n_live, x_ref, packed_ref,
                    alpha_ref, z_ref, o_ref, *, group_size: int, chunk: int):
    del tile_expert, tile_src
    q, _, n_bytes = packed_ref.shape
    gb = group_size // 8

    @pl.when(pl.program_id(0) < n_live[0])
    def _():
        def body(off, acc):
            cols = pl.ds(off, chunk)
            planes = [packed_ref[i, :, cols].astype(jnp.int32)
                      for i in range(q)]
            alphas = [expand_groups(alpha_ref[i], off, chunk, gb)
                      for i in range(q)]
            zc = expand_groups(z_ref[...], off, chunk, gb)
            for s in range(8):
                w = alphas[0] * plane_pm1(planes[0], s)
                for i in range(1, q):
                    w = w + alphas[i] * plane_pm1(planes[i], s)
                acc = acc + dot_nt(x_ref[s, :, cols], w + zc)
            return acc

        o_ref[...] = for_each_chunk(n_bytes, chunk, body,
                                    jnp.zeros(o_ref.shape, jnp.float32))


def reduction_chunk(n_bytes: int) -> int:
    """Bytes of the reduction dim one step of the chunk loop covers: 128
    lane-aligned bytes (1,024 weights) where they tile it, else all of
    it (an expert's 1,408-wide down projection is 176 bytes)."""
    return 128 if n_bytes % 128 == 0 else n_bytes


@functools.partial(jax.jit,
                   static_argnames=("group_size", "block_t", "interpret"))
def bcq_grouped_matmul_tiled(tile_expert, tile_src, n_live, xs, packed,
                             alpha, z, *, group_size: int, block_t: int,
                             interpret: bool = False):
    """Raw tiled call.  xs f32 [8, R, K/8] activation bit slabs of the
    expert-sorted, tile-padded rows (R a multiple of ``block_t``);
    packed uint8 [E, q, N, K/8]; alpha f32 [E, q, N, G]; z f32
    [E, N, G]; tile_expert / tile_src int32 [R / block_t]; n_live int32
    [1].  Returns f32 [R, N]; rows of tiles past ``n_live`` are left
    unwritten."""
    _, r, nb = xs.shape
    _, q, n, _ = packed.shape
    ng = alpha.shape[-1]
    assert r % block_t == 0 and block_t % 8 == 0
    assert group_size % 8 == 0 and nb % (group_size // 8) == 0
    kernel = functools.partial(_grouped_kernel, group_size=group_size,
                               chunk=reduction_chunk(nb))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r // block_t,),
        in_specs=[
            pl.BlockSpec((8, block_t, nb),
                         lambda t, te, ts, nl: (0, ts[t], 0)),
            pl.BlockSpec((None, q, n, nb),
                         lambda t, te, ts, nl: (te[t], 0, 0, 0)),
            pl.BlockSpec((None, q, n, ng),
                         lambda t, te, ts, nl: (te[t], 0, 0, 0)),
            pl.BlockSpec((None, n, ng),
                         lambda t, te, ts, nl: (te[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, n),
                               lambda t, te, ts, nl: (ts[t], 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, tile_src, n_live, xs, packed, alpha, z)
