"""Public wrapper of the grouped BCQ expert matmul, and the row layout it
reads.

:func:`expert_layout` sorts a mixture-of-experts layer's (token, k)
assignments by expert and gives every expert's rows whole tiles of
``block_t`` rows, with no capacity: an expert gets as many tiles as its
rows need.  Invalid assignments (padding tokens) get no row and no
tile.  :func:`bcq_grouped_matmul` then runs the kernel over those rows.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.plane import PlaneBundle, bit_slabs
from . import bcq_grouped_matmul as _k


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ExpertLayout:
    """Where each assignment's row sits in the tile-padded, expert-sorted
    row array, and what each row tile holds.

    dest:        int32 [A]  row of each assignment (``rows`` if invalid)
    tile_expert: int32 [n_tiles] expert whose planes a tile reads
    tile_src:    int32 [n_tiles] row tile it reads and writes
    n_live:      int32 [1]  tiles that hold rows
    load:        int32 [E]  valid rows per expert
    """
    dest: jax.Array
    tile_expert: jax.Array
    tile_src: jax.Array
    n_live: jax.Array
    load: jax.Array
    rows: int = dataclasses.field(metadata=dict(static=True))
    block_t: int = dataclasses.field(metadata=dict(static=True))


def row_tile(assignments: int, n_experts: int) -> int:
    """Rows per tile: about twice an expert's mean share of the
    assignments, in whole sublanes (8 rows) up to 128 -- 8 for a decode
    step's few rows per expert, ~96 for a 512-token prefill chunk."""
    mean2 = -(-2 * assignments // max(n_experts, 1))
    return int(min(128, max(8, -(-mean2 // 8) * 8)))


def expert_layout(experts: jax.Array, valid: jax.Array, n_experts: int,
                  block_t: int) -> ExpertLayout:
    """experts int32 [A] (each assignment's expert), valid bool [A]."""
    a = experts.shape[0]
    e = n_experts
    n_tiles = -(-(a + min(e, a) * (block_t - 1)) // block_t)
    rows = n_tiles * block_t
    key = jnp.where(valid, experts, e).astype(jnp.int32)
    load = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    tiles = -(-load // block_t)
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    n_live = tile_end[-1]
    # rank of each assignment within its expert, in a stable sort
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    first = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), load]))[sorted_key]
    rank = jnp.arange(a, dtype=jnp.int32) - first
    row_sorted = jnp.where(sorted_key < e,
                           tile_start[jnp.minimum(sorted_key, e - 1)]
                           * block_t + rank, rows)
    dest = jnp.zeros((a,), jnp.int32).at[order].set(row_sorted)
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    last = jnp.maximum(n_live - 1, 0)
    src = jnp.minimum(t, last)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, src, side="right"), e - 1
    ).astype(jnp.int32)
    return ExpertLayout(dest=dest, tile_expert=tile_expert,
                        tile_src=src.astype(jnp.int32),
                        n_live=n_live.reshape(1).astype(jnp.int32),
                        load=load, rows=rows, block_t=block_t)


def scatter_rows(x: jax.Array, layout: ExpertLayout) -> jax.Array:
    """Assignment rows x [A, K] -> the tile-padded row array [rows, K]
    (rows no assignment fills are zero)."""
    return jnp.zeros((layout.rows, x.shape[-1]), x.dtype).at[
        layout.dest].set(x, mode="drop")


def gather_rows(y: jax.Array, layout: ExpertLayout) -> jax.Array:
    """The tile-padded rows y [rows, N] -> each assignment's row [A, N]
    (zero for an invalid one)."""
    return jnp.take(y, layout.dest, axis=0, mode="fill", fill_value=0)


def bcq_grouped_matmul(x_rows: jax.Array, bank: PlaneBundle,
                       layout: ExpertLayout, *, interpret: bool = False,
                       out_dtype=None) -> jax.Array:
    """y[r] = x_rows[r] @ dequant(bank[expert of r]).T for the rows of
    live tiles.  x_rows [rows, K] in the layout's order; bank packed
    [E, q, N, K/8].  Returns [rows, N] (rows of dead tiles undefined)."""
    if bank.kind != "bcq" or bank.z is None or bank.packed.ndim != 4:
        raise ValueError("the grouped kernel reads [E, q, N, K/8] BCQ "
                         "planes with an offset row")
    k_pad = bank.packed.shape[-1] * 8
    if x_rows.shape[-1] != k_pad:
        x_rows = jnp.pad(x_rows, ((0, 0), (0, k_pad - x_rows.shape[-1])))
    y = _k.bcq_grouped_matmul_tiled(
        layout.tile_expert, layout.tile_src, layout.n_live,
        bit_slabs(x_rows), bank.packed, bank.alpha, bank.z,
        group_size=bank.group_size, block_t=layout.block_t,
        interpret=interpret)
    return y[:, : bank.out_features].astype(out_dtype or x_rows.dtype)
