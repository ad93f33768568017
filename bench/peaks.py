"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A copy of ``repro.roofline.analysis.PEAKS`` kept with the benchmark so
that no later change to the program can move the yardstick.  A kind
missing here is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float            # FLOP/s
    int8_ops: float              # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9, source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks(kind: str) -> Peaks:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def bound_s(flops: float, nbytes: float, p: Peaks) -> float:
    """Least time the chip could take: the larger of the two floors."""
    return max(flops / p.bf16_flops, nbytes / p.hbm_bytes_per_s)
