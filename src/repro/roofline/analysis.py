"""Roofline extraction from compiled XLA artifacts.

Three terms per (arch x shape x mesh), all **per device** (cost_analysis
is per-device after SPMD partitioning — verified empirically):

    compute    = HLO_FLOPs / peak_FLOPs            (197 TFLOP/s bf16)
    memory     = HLO_bytes / HBM_bw                (819 GB/s)
    collective = collective_bytes / link_bw        (~50 GB/s/link ICI)

``collective_bytes`` is not in cost_analysis: we parse the optimized HLO
and sum the *result* shapes of every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute (post-SPMD shapes are
per-device, consistent with the other two terms).

**Trip-count correction**: XLA's cost_analysis counts a while-loop body
ONCE (verified in this container), so scanned-layer models undercount by
~n_layers.  ``layer_extrapolated_costs`` therefore lowers two UNROLLED
models that differ by exactly one layer-period and extrapolates linearly
— exact for homogeneous stacks — while the full scanned model is still
compiled for the memory-fit proof.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float            # FLOP/s
    int8_ops: float              # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


#: per-chip peaks keyed by ``jax.Device.device_kind``.  A kind missing
#: here is an error (:func:`device_peaks`), never a default.
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9, source="Google Cloud documentation, 'TPU v5e'"),
}


def device_peaks(kind: str) -> DevicePeaks:
    """Peaks of the chip kind ``jax.devices()[0].device_kind`` reports."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


# the compile-only analysis below targets a described v5e chip
PEAK_FLOPS = PEAKS["TPU v5 lite"].bf16_flops
HBM_BW = PEAKS["TPU v5 lite"].hbm_bytes_per_s
ICI_BW = 50e9                # B/s per link (assumed, not from the table)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"[.\w]*\(", re.MULTILINE)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """{'all-reduce': bytes, ...} summed over the module (per device)."""
    out: dict = {}
    for shape_str, op in _COLL_RE.findall(hlo_text):
        out[op] = out.get(op, 0) + _shape_bytes(shape_str)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device
    coll_bytes: float            # per device
    coll_breakdown: dict
    arg_bytes: float = 0.0
    temp_bytes: float = 0.0
    out_bytes: float = 0.0
    alias_bytes: float = 0.0     # donated buffers (counted once)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def fraction_of_roofline(self) -> float:
        """compute term / binding term — 1.0 means compute-roofline-bound."""
        return self.t_compute / max(self.t_bound, 1e-30)

    def device_memory_gb(self) -> float:
        return (self.arg_bytes + self.temp_bytes + self.out_bytes
                - self.alias_bytes) / 2**30

    def row(self) -> dict:
        return {
            "flops_per_dev": self.flops,
            "bytes_per_dev": self.bytes_accessed,
            "coll_bytes_per_dev": self.coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "roofline_fraction": self.fraction_of_roofline(),
            "device_mem_gb": self.device_memory_gb(),
            "coll_breakdown": self.coll_breakdown,
        }


def from_compiled(compiled) -> Roofline:
    """Roofline terms straight from one compiled executable."""
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    coll = collective_bytes(text)
    return Roofline(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll,
        arg_bytes=float(ma.argument_size_in_bytes),
        temp_bytes=float(ma.temp_size_in_bytes),
        out_bytes=float(ma.output_size_in_bytes),
        alias_bytes=float(ma.alias_size_in_bytes),
    )


def extrapolate(r1: Roofline, r2: Roofline, n1: float, n2: float,
                n_total: float, mem: Optional[Roofline] = None) -> Roofline:
    """Linear layer-count extrapolation (exact for homogeneous periods).

    r1/r2: rooflines of unrolled models with n1/n2 layer-periods;
    n_total: periods in the full model; mem: optional full-model (scanned)
    compile supplying the true memory-fit numbers.
    """
    def ext(a, b):
        slope = (b - a) / max(n2 - n1, 1e-9)
        return a + slope * (n_total - n1)

    coll = {k: ext(r1.coll_breakdown.get(k, 0), r2.coll_breakdown.get(k, 0))
            for k in set(r1.coll_breakdown) | set(r2.coll_breakdown)}
    base = mem if mem is not None else r2
    return Roofline(
        flops=ext(r1.flops, r2.flops),
        bytes_accessed=ext(r1.bytes_accessed, r2.bytes_accessed),
        coll_bytes=ext(r1.coll_bytes, r2.coll_bytes),
        coll_breakdown=coll,
        arg_bytes=base.arg_bytes, temp_bytes=base.temp_bytes,
        out_bytes=base.out_bytes, alias_bytes=base.alias_bytes,
    )


def serve_analytic_bytes(cfg, shape, n_active_params: float, bits: int,
                         n_model: int = 16, n_data: int = 16) -> dict:
    """Analytic per-device HBM bytes for one serve step, three variants.

    The CPU dry-run backend neither fuses the dequant chain nor performs
    in-place cache updates, so its `bytes accessed` overstates a TPU
    execution.  These closed-form numbers use each execution path's
    *intended* traffic: the Pallas kernel's is fixed by its BlockSpecs
    (weights stream packed, LUT/dense tiles live in VMEM only) and is
    validated against ref.py in tests.

      dense_bf16  — FPE baseline: bf16 weights (2 B/w)
      xla_bf16    — bcq_xla fused dequant: packed read + bf16 dense (2.56 B/w)
      kernel_q    — lut_gemm/bcq_matmul Pallas kernels: packed only (q/8 B/w)

    plus the (shared) KV/state-cache read traffic per step.
    """
    w_global = n_active_params
    w_dense = 2.0 * w_global / n_model
    w_packed = (bits / 8.0) * w_global / n_model
    b_loc = shape.global_batch // n_data

    cache = 0.0
    if cfg.is_ssm_only or cfg.is_hybrid:
        d_inner = cfg.ssm_expand * cfg.d_model
        h = d_inner // cfg.ssm_head_dim
        n_mamba = sum(1 for i in range(cfg.n_layers)
                      if cfg.layer_kind(i) == "mamba")
        state = b_loc * max(h // n_model, 1) * cfg.ssm_head_dim * cfg.ssm_state * 4
        cache += n_mamba * state * 2                    # read + write
    n_attn = sum(1 for i in range(cfg.n_layers) if cfg.layer_kind(i) == "attn")
    if n_attn and cfg.attention != "none":
        length = min(shape.seq_len, cfg.sliding_window or shape.seq_len)
        if cfg.attention == "mla":
            per = b_loc * (length // n_model) * \
                (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2
        else:
            hd = cfg.head_dim_
            hkv = cfg.n_kv_heads
            shard = n_model if hkv % n_model == 0 else \
                (n_model if hd % n_model == 0 else 1)
            per = b_loc * length * hkv * hd * 2 * 2 // shard   # k + v
        cache += n_attn * per

    out = {}
    for name, wb in [("dense_bf16", w_dense), ("xla_bf16", w_dense + w_packed),
                     ("kernel_q", w_packed)]:
        total = wb + cache
        out[name] = {"bytes_per_dev": total, "t_memory_s": total / HBM_BW,
                     "weight_bytes": wb, "cache_bytes": cache}
    return out


def model_flops(cfg, shape, n_active_params: float,
                n_total_params: float) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) — global, forward+
    backward for train; 2*N*D forward-only for serving shapes.

    Encoder-decoder: the encoder's params see encoder_seq frames, not the
    decoder token count — counted separately (whisper's 24+24 layers over
    1500-frame inputs otherwise overstate useful FLOPs ~2x).
    """
    mult = 6.0 if shape.kind == "train" else 2.0
    dec_tokens = (shape.global_batch * shape.seq_len
                  if shape.kind != "decode" else shape.global_batch)
    if cfg is not None and getattr(cfg, "is_encdec", False):
        # split params by stack depth share (enc and dec layers are same-width)
        enc_frac = cfg.n_encoder_layers / (cfg.n_encoder_layers + cfg.n_layers)
        n_enc = n_active_params * enc_frac
        n_dec = n_active_params - n_enc
        enc_tokens = shape.global_batch * cfg.encoder_seq \
            if shape.kind != "decode" else 0      # encoder cached at decode
        return mult * (n_dec * dec_tokens + n_enc * enc_tokens)
    return mult * n_active_params * dec_tokens
