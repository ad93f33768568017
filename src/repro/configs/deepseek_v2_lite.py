"""deepseek-v2-lite [moe] — MLA attention + 64-expert top-6 MoE, YaRN.

27L d_model=2048 16H vocab=102400; MLA kv_lora=512 (no q-LoRA),
qk_nope 128, qk_rope 64, v_head 128; layer 0 dense (d_ff 10944), then
26 MoE layers of 64 routed experts 1408 wide (softmax top-6, gates not
renormalised, routed_scaling_factor 1) and 2 shared; untied head; YaRN
rotary (factor 40 over 4096 positions, beta 32/1, mscale 0.707)
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite]
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,              # MLA: full heads after latent decompression
    d_ff=10944,                 # the dense layer 0
    vocab_size=102400,
    attention="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_yarn_factor=40.0,
    rope_yarn_original_max_pos=4096,
    rope_yarn_beta_fast=32.0,
    rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=0.707,
    rope_yarn_mscale_all_dim=0.707,
    n_experts=64,
    n_shared_experts=2,
    experts_per_token=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    mlp_act="swiglu",
    norm="rmsnorm",
    max_seq_len=163840,
)


def reduced() -> ModelConfig:
    return CONFIG.replace(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab_size=256, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_experts=8, n_shared_experts=1,
        experts_per_token=2, moe_d_ff=32, rope_yarn_original_max_pos=16,
        scan_layers=False, max_seq_len=128,
    )
