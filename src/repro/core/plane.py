"""Plane-native weight layout — the single quantize→kernel handoff.

Every quantized format in the repo lowers into one container, the
:class:`PlaneBundle`: packed sign planes + per-group scale rows + layout
metadata, repacked into kernel-tile order once at quantize/admission
time (the FLUTE offline-restructure-then-fuse pattern).  Consumers —
the XLA reference paths, both generic Pallas kernels and the dedicated
ternary kernel, sharding, checkpoints, the manifest — all read this
layout instead of hand-rolling their own plane math.

Two *kinds* of bundle exist today:

``kind="bcq"``       generic binary-coding quantization (paper Eq. (3)):
                     ``packed`` holds q independent ±1 planes,
                     ``alpha`` one scale row per plane, ``z`` an offset
                     row.  RTN/OPTQ/greedy-BCQ all land here.

``kind="ternary"``   the 1.58-bit fast path: plane 0 is the *sign* bit,
                     plane 1 the *nonzero mask*; a single ``alpha`` row
                     carries the shared magnitude and there is no
                     offset (``z is None``).  w = alpha * sign * mask.
                     The identity  w = (alpha/2)(b1 + b2)  with
                     b1 = mask ? sign : +1, b2 = mask ? sign : -1 maps
                     it onto BCQ planes *bitwise in-kernel* (see
                     ``kernels/ternary_matmul``), so the stored bundle
                     keeps only 1 scale row and no z — strictly fewer
                     bytes than the generic 2-plane encoding.

Plane packing is uint8, LSB-first along the input dim (8 weights per
byte; bit value 1 encodes +1 / "nonzero").  Scale rows are per
(out_row, input_group) with ``group_size`` columns per group.

``tile_operands`` is the one place that pads a bundle + activation
batch out to kernel-launch geometry; the per-kernel ``ops.py`` wrappers
delegate here instead of re-deriving the layout at every call site.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "PlaneBundle",
    "KINDS",
    "TERNARY_BITS",
    "pack_planes",
    "unpack_planes",
    "dequantize",
    "bit_slabs",
    "tile_operands",
]

KINDS = ("bcq", "ternary")

# Planner sentinel for the ternary format's information rate (log2 3).
# ``core.mixed_precision`` treats any candidate below 2 as "ternary"
# and ``quant.api`` resolves it to the ternary format per layer.
TERNARY_BITS = 1.585


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PlaneBundle:
    """Plane-packed quantized weight tensor (pytree).

    Attributes:
      packed:   uint8[q, out, in//8]  bit-planes, 8 weights per byte
                (LSB-first within the byte along the input dim).  For
                ``kind="bcq"`` bit 1 encodes b=+1; for ``kind="ternary"``
                plane 0 is the sign bit (1 = +) and plane 1 the nonzero
                mask (1 = keep).
      alpha:    f32[n_alpha, out, n_groups] scale rows — one per plane
                for BCQ, a single shared-magnitude row for ternary.
      z:        f32[out, n_groups] offset row, or ``None`` (ternary).
      kind:     static layout kind, one of :data:`KINDS`.
      group_size: static — input-dim group size for alpha/z.
      in_features / out_features: static logical shape (pre-padding).
    """

    packed: jax.Array
    alpha: jax.Array
    z: Optional[jax.Array]
    group_size: int = dataclasses.field(metadata=dict(static=True))
    in_features: int = dataclasses.field(metadata=dict(static=True))
    out_features: int = dataclasses.field(metadata=dict(static=True))
    kind: str = dataclasses.field(default="bcq", metadata=dict(static=True))

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown bundle kind {self.kind!r}; "
                             f"expected one of {KINDS}")

    @property
    def bits(self) -> int:
        """Stored plane count (2 for ternary: sign + mask)."""
        return self.packed.shape[-3]

    @property
    def effective_bits(self) -> float:
        """Information rate in bits/weight (log2 of the level count)."""
        return TERNARY_BITS if self.kind == "ternary" else float(self.bits)

    @property
    def n_groups(self) -> int:
        return self.alpha.shape[-1]

    def nbytes(self) -> int:
        """Storage footprint in bytes (what HBM actually holds)."""
        n = (self.packed.size * self.packed.dtype.itemsize
             + self.alpha.size * self.alpha.dtype.itemsize)
        if self.z is not None:
            n += self.z.size * self.z.dtype.itemsize
        return n

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        return dequantize(self, dtype=dtype)


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------


def pack_planes(planes: jax.Array) -> jax.Array:
    """Pack {-1,+1} (or {0,1}) bit-planes into uint8, LSB-first.

    planes: [q, out, in] with in % 8 == 0; values in {-1,+1} or {0,1}.
    returns uint8[q, out, in//8].
    """
    q, out, n = planes.shape
    if n % 8 != 0:
        raise ValueError(f"input dim {n} not divisible by 8; pad first")
    bits = (planes > 0).astype(jnp.uint8).reshape(q, out, n // 8, 8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return (bits << shifts).sum(axis=-1).astype(jnp.uint8)


def unpack_planes(packed: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`pack_planes`; returns ±1 planes [q, out, in]."""
    q, out, nb = packed.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (packed[..., None] >> shifts) & jnp.uint8(1)  # [q, out, nb, 8]
    pm1 = bits.astype(dtype) * 2 - 1
    return pm1.reshape(q, out, nb * 8)


# ---------------------------------------------------------------------------
# dequantize (kind-aware reference reconstruction)
# ---------------------------------------------------------------------------


def dequantize(w: PlaneBundle, dtype=jnp.float32) -> jax.Array:
    """Reconstruct the dense weight matrix W[out, in] from a bundle.

    Written as one elementwise chain (unpack -> scale -> reduce) so XLA
    can fuse it into a single kernel whose HBM traffic is the packed
    bytes in + the dense matrix out.  Pass dtype=bf16 on the serve path:
    an f32 dense intermediate doubles the dominant weight-byte term.
    """
    q, out, nb = w.packed.shape[-3:]
    in_pad = nb * 8
    g = w.group_size
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (w.packed[..., None] >> shifts) & jnp.uint8(1)   # [q,out,nb,8]
    if w.kind == "ternary":
        sign = (bits[0].astype(jnp.float32) * 2 - 1).reshape(out, in_pad)
        mask = bits[1].astype(jnp.float32).reshape(out, in_pad)
        a_cols = jnp.repeat(w.alpha[0], g, axis=-1)         # [out, in_pad]
        dense = a_cols * sign * mask
    else:
        pm1 = bits.astype(jnp.float32) * 2 - 1
        alpha_cols = jnp.repeat(w.alpha, g, axis=-1)        # [q,out,in_pad]
        dense = (pm1.reshape(q, out, in_pad) * alpha_cols).sum(0)
        if w.z is not None:
            dense = dense + jnp.repeat(w.z, g, axis=-1)
    return dense[:, : w.in_features].astype(dtype)


# ---------------------------------------------------------------------------
# kernel-tile admission: the one place launch padding happens
# ---------------------------------------------------------------------------


def bit_slabs(xp: jax.Array) -> jax.Array:
    """[B, N] (N % 8 == 0) -> f32 [8, B, N/8] activation bit slabs:
    ``out[s, b, j] = x[b, 8j + s]`` is the activation that pairs with
    bit ``s`` of packed byte ``j`` — the plane kernels unpack each bit
    with one shift-and-mask instead of interleaving bits back into
    weight order in-kernel."""
    b, n = xp.shape
    return xp.astype(jnp.float32).reshape(b, n // 8, 8).transpose(2, 0, 1)


def tile_operands(x2: jax.Array, w: PlaneBundle, *, block_b: int,
                  block_m: int, block_n: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array,
                             Optional[jax.Array], int, int, int, int]:
    """Pad (activations, bundle) out to kernel-launch geometry.

    x2: [b, in_features] flattened activation batch.  Returns
    ``(xs, packed, alpha, z, b, m, block_m, block_n)`` where every array
    is zero-padded to block multiples: xs f32 [8, bp, npad//8] activation
    bit slabs (:func:`bit_slabs`), packed [q, mp, npad//8], alpha
    [n_alpha, mp, agp], z [mp, agp] or None.  ``block_m``/``block_n``
    come back clamped to the (row-aligned, group-aligned) weight extents
    so callers pass the effective values to the tiled launcher.

    Zero padding is correct for every kind: padded x columns contribute
    0 to LUT entries and activation-sums alike, padded groups carry zero
    scales, and padded weight rows produce garbage only in output rows
    that are sliced off ([:b, :m]).
    """
    b = x2.shape[0]
    q, m, _ = w.packed.shape
    n_pad_w = w.packed.shape[-1] * 8          # weight-side padded N (x8)
    ag = w.alpha.shape[-1]
    na = w.alpha.shape[0]

    bp = _round_up(b, block_b)
    block_n = min(block_n, _round_up(n_pad_w, w.group_size))
    npad = _round_up(n_pad_w, block_n)
    block_m = min(block_m, _round_up(m, 8))
    mp = _round_up(m, block_m)
    agp = npad // w.group_size

    xp = jnp.zeros((bp, npad), x2.dtype).at[:b, : x2.shape[1]].set(x2)
    packed, alpha, z = w.packed, w.alpha, w.z
    if npad != n_pad_w or mp != m or agp != ag:
        packed = jnp.zeros((q, mp, npad // 8), jnp.uint8) \
            .at[:, :m, : n_pad_w // 8].set(packed)
        alpha = jnp.zeros((na, mp, agp), alpha.dtype) \
            .at[:, :m, :ag].set(alpha)
        if z is not None:
            z = jnp.zeros((mp, agp), z.dtype).at[:m, :ag].set(z)
    return bit_slabs(xp), packed, alpha, z, b, m, block_m, block_n
