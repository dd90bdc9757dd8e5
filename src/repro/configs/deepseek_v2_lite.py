"""deepseek-v2-lite [moe] — multi-head latent attention (kv_lora_rank 512,
no query LoRA, YaRN rope), one leading dense layer, then 26 layers of 64
routed experts (top-6, softmax over all 64, not renormalised) and 2
shared [hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434].  Every expert
is held here; a deployment's chip share sets ``experts_first`` /
``experts_held``."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400, head_dim=192,
    rope_theta=10000.0, lead_blocks=("mla",), block_pattern=("mla_moe",),
    n_experts=64, top_k=6, n_shared_experts=2, moe_d_ff=1408,
    router="softmax_topk", routed_scaling=1.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0, yarn_original_max_len=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
)
