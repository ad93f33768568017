"""Shared in-kernel machinery for the plane kernels (FIGLUT §III-C/D/E).

The LUT build, the mu-bit key extraction from packed planes, the
half-table sign-decoding read (hFFLUT) and the per-group scale
expansion are the same math for every plane-consuming kernel — the
generic ``lut_gemm`` bit-serial kernel, the ``bcq_matmul`` dequant
kernel and the dedicated ``ternary_matmul`` fast path all import from
here, so the half-LUT sign trick lives in exactly one place.

Layout that keeps every kernel body two-dimensional (what the Mosaic
compiler accepts at (8, 128) tiling):

* **Bit slabs.**  Packed byte ``j`` of a plane holds the bits of weights
  ``8j .. 8j+7`` (LSB first).  Instead of interleaving the eight bits
  back into weight order in-kernel (a lane-changing reshape the TPU
  refuses), the *activations* are permuted once outside the kernel:
  :func:`repro.core.plane.bit_slabs` turns ``x [B, N]`` into eight slabs
  ``xs[s] = x[:, s::8]`` of shape ``[B, N/8]``.  Bit ``s`` of byte ``j``
  then pairs with column ``j`` of slab ``s`` — every unpack is one
  shift-and-mask on a ``[rows, bytes]`` tile.
* **Group scales.**  ``alpha``/``z`` rows stay ``[rows, n_groups]`` and
  enter the kernel whole (their lane dim is the full group count).  The
  scales of one byte chunk are expanded to per-byte columns by a 0/1
  matrix product on the MXU (:func:`expand_groups`) — no gather, no
  dynamic lane slicing.
* **LUT reads.**  A mu-group's table entry ``p`` is a signed sum of mu
  activation slabs, so the whole table of one key position is a list of
  ``[B, bytes]`` tiles.  The read is either a one-hot contraction per
  entry on the MXU (``onehot``) or a 2^mu-way mux sweep on the VPU
  (``select``, the RAC's multiplexer); neither gathers or needs a
  batched 3-D contraction.

The host-side reference implementations of the same math live in
``repro.core.lut`` and the per-kernel ``ref.py`` oracles.
"""
from __future__ import annotations

from typing import Callable, List, Literal

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tune.space import READ_MODES

ReadMode = Literal["onehot", "select"]

#: key widths the in-kernel table build supports (mu must divide 8)
LUT_MUS = (1, 2, 4)

_HI = lax.Precision.HIGHEST


def for_each_chunk(n_bytes: int, chunk: int,
                   body: Callable[[object, jax.Array], jax.Array],
                   init: jax.Array) -> jax.Array:
    """Fold ``body(offset, acc)`` over the byte dim in ``chunk``-wide
    steps: one static step when the chunk spans the dim, else a
    ``fori_loop`` with lane-aligned dynamic offsets."""
    steps = n_bytes // chunk
    if steps == 1:
        return body(0, init)
    return lax.fori_loop(
        0, steps, lambda c, acc: body(pl.multiple_of(c * chunk, chunk), acc),
        init)


def dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """a [R, K] . b [C, K]^T -> f32 [R, C] at full f32 precision."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def expand_groups(rows: jax.Array, offset, chunk: int,
                  group_bytes: int) -> jax.Array:
    """Per-(row, group) scales [R, G] -> per-byte scales [R, chunk] for
    the byte chunk starting at ``offset``: column j carries the scale of
    group ``(offset + j) // group_bytes``.  A 0/1 selection matrix on the
    MXU (exact at full precision: one nonzero term per output)."""
    g = rows.shape[-1]
    k = lax.broadcasted_iota(jnp.int32, (g, chunk), 0) * group_bytes
    j = lax.broadcasted_iota(jnp.int32, (g, chunk), 1) + offset
    sel = ((j >= k) & (j < k + group_bytes)).astype(jnp.float32)
    return lax.dot_general(rows.astype(jnp.float32), sel,
                           (((1,), (0,)), ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def plane_pm1(byte_tile: jax.Array, s: int) -> jax.Array:
    """int32 bytes [R, C] -> f32 ±1 of bit ``s`` (bit 1 encodes +1)."""
    return ((byte_tile >> s) & 1).astype(jnp.float32) * 2.0 - 1.0


def build_lut(slabs: List[jax.Array], h: int, mu: int,
              half: bool) -> List[jax.Array]:
    """Table of key position ``h`` (bits ``mu*h .. mu*h+mu-1`` of each
    byte): entry p is ``sum_t sign_t(p) * slabs[mu*h + t]`` with
    ``sign_t(p) = +1`` iff bit t of p is set (§III-E).  With
    ``half=True`` only the MSB=1 entries are built (hFFLUT, §III-D).
    Returns 2^mu (or 2^(mu-1)) tiles [B, C]."""
    cols = slabs[mu * h: mu * h + mu]
    first = (1 << (mu - 1)) if half else 0
    table = []
    for p in range(first, 1 << mu):
        acc = None
        for t, col in enumerate(cols):
            acc = (col if (p >> t) & 1 else -col) if acc is None else (
                acc + col if (p >> t) & 1 else acc - col)
        table.append(acc)
    return table


def read_lut(table: List[jax.Array], keys: jax.Array, weights: jax.Array,
             mu: int, half: bool, mode: ReadMode) -> jax.Array:
    """``sum_j weights[m, j] * LUT[key[m, j]][b, j]`` -> f32 [B, M].

    table: entries [B, C]; keys int32 [M, C] in [0, 2^mu); weights f32
    [M, C] (the expanded alpha of the plane).  The half table is read at
    ``key - 2^(mu-1)`` for MSB=1 keys and at the complement otherwise,
    with the sign folded into the weights (LUT[p] = -LUT[2^mu-1-p])."""
    if half:
        hsz = 1 << (mu - 1)
        msb = keys >= hsz
        idx = jnp.where(msb, keys - hsz, (hsz - 1) - keys)
        weights = jnp.where(msb, weights, -weights)
    else:
        idx = keys
    if mode == "onehot":
        # one-hot of entry p, scaled, contracted against LUT[p] on the MXU
        acc = None
        for p, entry in enumerate(table):
            part = dot_nt(entry, jnp.where(idx == p, weights, 0.0))
            acc = part if acc is None else acc + part
        return acc
    if mode == "select":
        # 2^mu-way mux sweep per activation row; the row sum over the
        # chunk runs as a ones-vector product so the result lands with
        # output rows on lanes
        n_b = table[0].shape[0]
        ones = jnp.ones((1, keys.shape[1]), jnp.float32)
        row = lax.broadcasted_iota(jnp.int32, (n_b, keys.shape[0]), 0)
        acc = jnp.zeros((n_b, keys.shape[0]), jnp.float32)
        for b in range(n_b):
            vals = jnp.zeros(keys.shape, jnp.float32)
            for p, entry in enumerate(table):
                vals = jnp.where(idx == p, entry[b:b + 1, :], vals)
            acc = jnp.where(row == b, dot_nt(ones, vals * weights), acc)
        return acc
    raise ValueError(f"read_mode must be one of {READ_MODES}, got {mode!r}")


#: scoped VMEM the plane kernels may claim (v5e has 128 MiB per core;
#: the compiler's default scope is 16 MiB)
VMEM_LIMIT = 64 * 1024 * 1024


def plane_vmem_bytes(*, q: int, n_alpha: int, block_b: int, block_m: int,
                     n_bytes: int, n_groups: int, chunk: int) -> int:
    """Upper estimate of one launch's VMEM: double-buffered operand and
    output blocks plus the f32 working tiles of one chunk."""
    blocks = (8 * block_b * n_bytes * 4            # activation slabs
              + q * block_m * n_bytes              # packed planes
              + (n_alpha + 1) * block_m * n_groups * 4
              + block_b * block_m * 4)             # output
    work = (q + 4) * block_m * chunk * 4 + 40 * block_b * chunk * 4
    return 2 * blocks + work


def plane_call(body, xs, packed, alpha, z, *, block_b: int, block_m: int,
               interpret: bool):
    """Launch a plane kernel over a (row-tile, batch-tile) grid.

    xs f32 [8, B, N/8] slabs; packed uint8 [q, M, N/8]; alpha f32
    [na, M, G]; z f32 [M, G] or None.  Every block spans the whole
    reduction dim, so the last two dims of each block are either the
    full array dims or (block_b | block_m, full): no block the (8, 128)
    tiling refuses.  The batch tile is innermost so a weight block stays
    resident while activation tiles stream (weight-stationary, §III-B).
    ``body(x_ref, packed_ref, alpha_ref, [z_ref,] o_ref)`` accumulates
    over the byte dim itself.
    """
    _, b, nb = xs.shape
    q, m, _ = packed.shape
    na, _, ng = alpha.shape
    in_specs = [
        pl.BlockSpec((8, block_b, nb), lambda mi, bi: (0, bi, 0)),
        pl.BlockSpec((q, block_m, nb), lambda mi, bi: (0, mi, 0)),
        pl.BlockSpec((na, block_m, ng), lambda mi, bi: (0, mi, 0)),
    ]
    args = [xs, packed, alpha]
    if z is not None:
        in_specs.append(pl.BlockSpec((block_m, ng), lambda mi, bi: (mi, 0)))
        args.append(z)
    return pl.pallas_call(
        body,
        grid=(m // block_m, b // block_b),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_m), lambda mi, bi: (bi, mi)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*args)


def ternary_plane_bytes(sign_byte: jax.Array, mask_byte: jax.Array):
    """Decode the ternary bundle's (sign, mask) bytes into BCQ plane bytes.

    The ternary identity  w = (a/2)(b1 + b2)  with
    b1 = mask ? sign : +1  and  b2 = mask ? sign : -1  becomes, on the
    packed bit level (bit 1 = +1 / "nonzero"),

        b1 = sign | ~mask          b2 = sign & mask

    — two bitwise ops per byte, the in-kernel realization of the paper's
    sign-decoding unit.  Takes and returns int32 byte tiles.
    """
    b1 = (sign_byte | (~mask_byte & 0xFF)) & 0xFF
    b2 = sign_byte & mask_byte
    return b1, b2
