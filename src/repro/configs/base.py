"""Model / run configuration system.

One :class:`ModelConfig` dataclass describes every architecture in the zoo
(dense GQA, MLA, MoE, SSM, hybrid, enc-dec, stub-fronted VLM/audio).  Each
``src/repro/configs/<arch>.py`` exports ``CONFIG`` with the exact assigned
hyperparameters plus ``reduced()`` for CPU smoke tests.  ``registry()``
resolves ``--arch <id>`` strings.

Shape cells (assigned): train_4k / prefill_32k / decode_32k / long_500k —
see ``SHAPES`` and ``ModelConfig.input_specs``.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.spec import QuantSpec

# ---------------------------------------------------------------------------
# assigned shape cells
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeCfg:
    name: str
    seq_len: int
    global_batch: int
    kind: str             # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeCfg("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCfg("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCfg("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCfg("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# model config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads

    # attention
    attention: str = "gqa"            # gqa | mla | none
    sliding_window: int = 0           # >0 -> SWA (sub-quadratic full-attn)
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    pos: str = "rope"                 # rope | learned | none

    # YaRN rotary scaling (rope_scaling type "yarn"); factor 0 -> none
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max_pos: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0

    # MLA (deepseek-v2 / minicpm3)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0                 # expert hidden dim (d_ff if 0)
    moe_layer_period: int = 1         # MoE every k-th layer
    first_dense_layers: int = 0       # leading dense layers (deepseek)
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True       # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0  # gates times this (deepseek)

    # SSM (mamba2 / jamba mamba layers)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    attn_layer_period: int = 0        # hybrid: 1 attn layer every k (jamba 8)
    attn_layer_offset: int = 4

    # enc-dec (whisper)
    n_encoder_layers: int = 0
    encoder_seq: int = 0              # precomputed frame count (stub frontend)

    # vlm stub
    num_patches: int = 0              # precomputed patch embeds prepended

    # misc
    mlp_act: str = "swiglu"           # swiglu | gelu
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    max_seq_len: int = 524288

    # execution
    quant: Optional[QuantSpec] = None  # declarative quantization spec: the
                                      # single source of truth for format /
                                      # bits / group / backend preference
                                      # (repro.quant); None -> unquantized
    remat: bool = True
    scan_layers: bool = True
    kv_replication: int = 1           # replicate kv heads r-fold so the KV
                                      # cache shards over TP > n_kv_heads
                                      # (vLLM practice: 2x memory beats the
                                      # per-layer cache all-gather)
    kv_cache_bits: int = 16           # 8 -> int8 KV cache (per-slot-per-head
                                      # symmetric scales): halves the cache
                                      # bytes that dominate long-context
                                      # decode (beyond-paper extension of
                                      # the weight-quantization insight)
    paged_kernel: str = "auto"        # paged attention path (decode AND
                                      # chunked prefill, resolved per
                                      # variant): auto (fused Pallas
                                      # kernels where hardware-native,
                                      # else gathered view) | fused
                                      # (force; interpret off-TPU) |
                                      # gather

    # ---------------------------------------------------------------
    @property
    def backend_preference(self) -> str:
        """Execution-backend preference fed to the registry
        (:mod:`repro.quant.backends`): the ``quant`` spec's choice;
        "auto" lets capability negotiation pick per weight.  Unquantized
        models run dense linears, where the preference is inert."""
        if self.quant is not None:
            return self.quant.backend
        return "dense"

    def quant_spec(self) -> Optional[QuantSpec]:
        """The declarative QuantSpec (None when the model is
        unquantized).  The ``gemm_backend``/``quant_bits`` shims that
        used to synthesize a spec here were removed after their
        one-release deprecation window — set ``quant=QuantSpec(...)``."""
        return self.quant

    @property
    def yarn(self) -> Optional[tuple]:
        """The YaRN parameters ``(factor, original_max_pos, beta_fast,
        beta_slow, mscale, mscale_all_dim)``, or None without YaRN
        (scalars, so the config stays hashable)."""
        if not self.rope_yarn_factor:
            return None
        return (self.rope_yarn_factor, self.rope_yarn_original_max_pos,
                self.rope_yarn_beta_fast, self.rope_yarn_beta_slow,
                self.rope_yarn_mscale, self.rope_yarn_mscale_all_dim)

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 so the vocab dim always shards over the
        model axis (un-shardable logits cost ~75 GiB/device at train_4k —
        standard MaxText-style embedding padding)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def is_hybrid(self) -> bool:
        return self.attn_layer_period > 0

    @property
    def is_ssm_only(self) -> bool:
        return self.attention == "none" and self.ssm_state > 0

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    def layer_kind(self, i: int) -> str:
        """'attn' or 'mamba' for decoder layer i."""
        if self.is_ssm_only:
            return "mamba"
        if self.is_hybrid:
            return ("attn" if i % self.attn_layer_period == self.attn_layer_offset
                    else "mamba")
        return "attn"

    def mlp_kind(self, i: int) -> str:
        """'dense' or 'moe' for decoder layer i."""
        if self.n_experts and i >= self.first_dense_layers \
                and i % self.moe_layer_period == (self.moe_layer_period - 1 if self.moe_layer_period > 1 else 0):
            return "moe"
        return "dense"

    def supports_long_context(self) -> bool:
        """Sub-quadratic decode: SSM, hybrid, or sliding-window attention."""
        return self.is_ssm_only or self.is_hybrid or self.sliding_window > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---------------------------------------------------------------
    def input_specs(self, shape: ShapeCfg, *, per_device: bool = False,
                    data_shards: int = 1) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of a shape cell.

        ``train``  -> token batch (B, S) (+frontend stubs)
        ``prefill``-> token batch (B, S)
        ``decode`` -> (B, 1) new tokens; the KV/SSM cache is supplied
                      separately (see models.model.abstract_cache).
        """
        b = shape.global_batch // (data_shards if per_device else 1)
        s = shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(self.dtype)
        if shape.kind == "decode":
            specs = {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
            return specs
        if self.is_encdec:
            enc = self.encoder_seq or 1500
            return {
                "frames": jax.ShapeDtypeStruct((b, enc, self.d_model), dt),
                "tokens": jax.ShapeDtypeStruct((b, s), i32),
            }
        if self.num_patches:
            p = min(self.num_patches, s // 2)
            return {
                "tokens": jax.ShapeDtypeStruct((b, s - p), i32),
                "patch_embeds": jax.ShapeDtypeStruct((b, p, self.d_model), dt),
            }
        return {"tokens": jax.ShapeDtypeStruct((b, s), i32)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "pixtral_12b", "deepseek_v2_236b", "mixtral_8x7b", "phi4_mini_3_8b",
    "stablelm_1_6b", "qwen1_5_32b", "minicpm3_4b", "mamba2_2_7b",
    "whisper_medium", "jamba_1_5_large_398b", "opt_6_7b",
    "deepseek_v2_lite",
]


def get_config(arch: str) -> ModelConfig:
    arch = arch.replace("-", "_").replace(".", "_")
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def get_reduced(arch: str) -> ModelConfig:
    arch = arch.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.reduced()


def registry() -> dict:
    return {a: get_config(a) for a in ARCH_IDS}
