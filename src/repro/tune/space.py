"""Config space for the Pallas kernels (FIGLUT §III-C/D execution shapes).

A :class:`KernelConfig` fixes everything the launcher may vary per call
site: the (block_b, block_m, block_n) tile geometry plus, for the LUT
kernel, the RAC read mode (``select`` mux sweep vs MXU ``onehot``
contraction vs ``gather``) and whether the half table (hFFLUT) is built.

``candidate_configs`` enumerates the space *already clamped to a concrete
(B, M, N) problem* and de-duplicated — on a small layer most of the grid
collapses onto a handful of distinct launches, so the tuner never times
the same launch twice.  ``heuristic_config`` is the deterministic
fallback used when no tuned entry exists (tuning disabled, cold cache,
or interpret mode off-device): it reproduces the seed defaults clamped
to the shape, so untuned behavior is exactly the pre-tuner behavior.

TPU tiling constraints (f32 min tile 8x128, lane dim 128) shape the
grid.  The plane kernels DMA the whole reduction dim per block and walk
it in ``block_n``-weight chunks, so ``block_n`` is a multiple of 1024
(128 lane-aligned bytes) or the whole padded dim; ``block_m`` is the
output tile's lane dim, a multiple of 128 or the whole padded dim;
``block_b`` is a multiple of 8.  Every config ``clamp_config`` returns
compiles for the chip (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Sequence

KERNELS = ("lut_gemm", "bcq_matmul", "ternary_matmul", "paged_attention",
           "paged_prefill")

# kernels whose RAC read mode is a live config axis (ternary_matmul is
# always half-table — the sign decode IS the datapath — so only the
# read mode varies for it)
LUT_KERNELS = ("lut_gemm", "ternary_matmul")

# the paged-attention kernel family shares one config axis (the kv-head
# tile); "paged_prefill" is a distinct kernel NAME so its cache entries
# can never collide with decode's (and stale pre-prefill caches miss)
PAGED_KERNELS = ("paged_attention", "paged_prefill")

# LUT reads: MXU one-hot contraction or VPU mux sweep (no gathers: the
# TPU compiler only lowers 2-D gathers)
READ_MODES = ("onehot", "select")

# lane-tile units of the plane kernels' blocks (see module docstring)
LANE = 128
CHUNK_UNIT = 8 * LANE           # weights per 128-byte chunk

# enumeration grids (pre-clamp); the heuristic picks the first entries
_BLOCK_B = (8, 16, 32)
_BLOCK_M = (128, 256)
_BLOCK_N = (1024, 2048)
# paged kv-head tile (0 = all heads).  A partial tile is the lane dim
# of the int8 scale blocks, so only multiples of 128 compile.
_BLOCK_H = (0, LANE)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One launch configuration.  ``read_mode``/``half_lut`` only affect
    the ``lut_gemm`` kernel and ``block_h`` (kv heads per grid step)
    only the ``paged_attention`` kernel; fields irrelevant to a kernel
    are normalized to their defaults so configs compare/dedupe cleanly."""

    block_b: int = 8
    block_m: int = 128
    block_n: int = 1024
    read_mode: str = "onehot"
    half_lut: bool = True
    block_h: int = 0                 # paged_attention: kv-head tile (0 = all)

    def to_kwargs(self, kernel: str) -> dict:
        """kwargs for the kernel's public op wrapper."""
        if kernel in PAGED_KERNELS:
            return dict(block_h=self.block_h)
        kw = dict(block_b=self.block_b, block_m=self.block_m,
                  block_n=self.block_n)
        if kernel == "lut_gemm":
            kw.update(read_mode=self.read_mode, half_lut=self.half_lut)
        elif kernel == "ternary_matmul":
            kw.update(read_mode=self.read_mode)
        return kw

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def divisor_clamp(block_h: int, hkv: int) -> int:
    """Largest divisor of ``hkv`` that is <= block_h (0 -> all heads).

    The single clamp rule for the paged_attention kv-head tile — used by
    both ``clamp_config`` (dispatch side) and the op wrapper, so a tuned
    cache entry always describes the launch actually run."""
    if block_h <= 0 or block_h >= hkv:
        return hkv
    while hkv % block_h:
        block_h -= 1
    return max(block_h, 1)


def clamp_config(cfg: KernelConfig, kernel: str, *, b: int, m: int, n: int,
                 group_size: int) -> KernelConfig:
    """Snap a config onto a concrete problem so the tiled kernel's
    divisibility asserts hold (mirrors the padding math in ops.py).

    For ``paged_attention`` the problem dims are remapped: ``m`` is the
    kv-head count, ``n`` the per-sequence KV capacity and ``group_size``
    the pool block size; the only live axis is ``block_h`` (a divisor of
    the head count that is a multiple of 128, else all heads — the tiles
    the chip compiles) and the GEMM tile fields are normalized so
    configs dedupe.  ``paged_prefill`` shares the same remapping."""
    if kernel in PAGED_KERNELS:
        hkv = max(m, 1)
        block_h = divisor_clamp(cfg.block_h, hkv)
        return KernelConfig(block_h=block_h if block_h % LANE == 0
                            else hkv)
    # never beyond the largest tiles of the space: bigger LUT tiles
    # overrun VMEM (tests/test_tpu_compile.py compiles the largest)
    n_pad = _round_up(max(n, 1), group_size)
    block_n = _snap(min(cfg.block_n, _BLOCK_N[-1]), n_pad,
                    math.lcm(CHUNK_UNIT, group_size))
    block_m = _snap(min(cfg.block_m, _BLOCK_M[-1]),
                    _round_up(max(m, 1), 8), LANE)
    block_b = _round_up(min(cfg.block_b, _BLOCK_B[-1],
                            _round_up(max(b, 1), 8)), 8)
    read_mode = (cfg.read_mode if kernel in LUT_KERNELS
                 and cfg.read_mode in READ_MODES else "onehot")
    half_lut = cfg.half_lut if kernel == "lut_gemm" else True
    return KernelConfig(block_b=block_b, block_m=block_m, block_n=block_n,
                        read_mode=read_mode, half_lut=half_lut)


def _snap(block: int, full: int, unit: int) -> int:
    """A tile that compiles: the whole (padded) dim, or a multiple of
    ``unit`` smaller than it."""
    block = _round_up(max(block, 1), unit)
    return full if block >= full else block


def heuristic_config(kernel: str, *, b: int, m: int, n: int,
                     mu: int = 4, group_size: int = 128) -> KernelConfig:
    """Deterministic no-measurement fallback.

    (8, 128, 1024, onehot, hFFLUT) with a mild batch scaling — decode
    (b <= 8) keeps the minimum f32 sublane tile, larger batches grow
    block_b so the LUT build amortizes over more rows per launch.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; known: {KERNELS}")
    if kernel in PAGED_KERNELS:
        # decode head counts are small: all kv heads per grid step keeps
        # the grid minimal and the q tile resident (prefill inherits the
        # same default — the chunk dim rides inside the block)
        return clamp_config(KernelConfig(block_h=0), kernel, b=b, m=m, n=n,
                            group_size=group_size)
    block_b = 8 if b <= 8 else (16 if b <= 16 else 32)
    base = KernelConfig(block_b=block_b, block_m=128, block_n=1024,
                        read_mode="onehot", half_lut=True)
    return clamp_config(base, kernel, b=b, m=m, n=n, group_size=group_size)


def candidate_configs(kernel: str, *, b: int, m: int, n: int, mu: int = 4,
                      group_size: int = 128,
                      max_candidates: int = 0) -> list:
    """Enumerate the clamped, de-duplicated config space for one problem.

    The heuristic config is always candidate 0, so a tuner that selects
    the argmin over this list can never do worse than the untuned path.
    ``read_mode``/``half_lut`` vary fastest so a truncated prefix
    (``max_candidates``) still spans the execution-mode axis of the space.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; known: {KERNELS}")
    out = [heuristic_config(kernel, b=b, m=m, n=n, mu=mu,
                            group_size=group_size)]
    seen = {out[0]}
    if kernel in PAGED_KERNELS:
        for bh in _BLOCK_H:
            cfg = clamp_config(KernelConfig(block_h=bh), kernel,
                               b=b, m=m, n=n, group_size=group_size)
            if cfg not in seen:
                seen.add(cfg)
                out.append(cfg)
        if max_candidates and len(out) > max_candidates:
            out = out[:max_candidates]
        return out
    if kernel in LUT_KERNELS and group_size % mu:
        raise ValueError(f"group_size {group_size} not divisible by mu {mu}")
    modes = READ_MODES if kernel in LUT_KERNELS else ("onehot",)
    halves = (True, False) if kernel == "lut_gemm" else (True,)

    for bb, bm, bn, rm, hl in itertools.product(
            _BLOCK_B, _BLOCK_M, _BLOCK_N, modes, halves):
        cfg = clamp_config(
            KernelConfig(bb, bm, bn, rm, hl), kernel,
            b=b, m=m, n=n, group_size=group_size)
        if cfg not in seen:
            seen.add(cfg)
            out.append(cfg)
    if max_candidates and len(out) > max_candidates:
        out = out[:max_candidates]
    return out
