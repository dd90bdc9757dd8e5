"""Model / run configuration schema.

Every assigned architecture is expressed as a ModelConfig with a
``block_pattern``: the repeating sequence of block kinds scanned over by the
transformer assembly (models/transformer.py).  Kinds:

    attn            global causal self-attention + SwiGLU MLP
    attn_swa        sliding-window self-attention + MLP (Mixtral)
    attn_local      local self-attention + MLP (RecurrentGemma, window)
    moe             self-attention + MoE FFN
    ssd             Mamba-2 SSD block (attention-free, no separate MLP)
    rglru           RG-LRU recurrent block + MLP
    cross           cross-attention (vision/encoder states) + MLP
    enc_attn        bidirectional self-attention + MLP (encoders)
    dec_attn_cross  decoder self-attn + cross-attn + MLP (Whisper decoder)
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense|moe|ssm|vlm|audio|hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 500000.0
    norm: str = "rms"                 # rms | ln
    tie_embeddings: bool = False

    # block pattern
    block_pattern: Tuple[str, ...] = ("attn",)
    extra_blocks: Tuple[str, ...] = ()   # appended after the scanned stack
    lead_blocks: Tuple[str, ...] = ()    # applied before the scanned stack
    window: int = 0                    # SWA window for attn_swa
    local_window: int = 0              # window for attn_local

    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0                  # per-expert hidden (fine-grained MoE)
    capacity_factor: float = 1.25
    # "pjit": capacity-scatter dispatch partitioned by XLA SPMD (simple but
    # partitioner-limited, see EXPERIMENTS.md §Roofline); "shard_map":
    # explicit local-dispatch + all_to_all expert parallelism (requires
    # n_experts % model-axis == 0)
    moe_impl: str = "pjit"
    # router weights: "topk_softmax" softmaxes the top-k logits (Mixtral,
    # renormalised); "softmax_topk" softmaxes all logits and keeps the
    # top-k probabilities as they are, times ``routed_scaling`` (DeepSeek-V2)
    router: str = "topk_softmax"
    routed_scaling: float = 1.0
    # the routed experts this chip holds, [experts_first, experts_first +
    # experts_held) of n_experts; 0 holds them all.  Read by the dropless
    # layer (mla_moe blocks), which routes over all n_experts.
    experts_first: int = 0
    experts_held: int = 0

    # multi-head latent attention (mla / mla_moe blocks): K/V come from a
    # kv_lora_rank latent plus one shared rope key of qk_rope_head_dim;
    # per head, queries and keys are qk_nope_head_dim + qk_rope_head_dim
    # wide and values v_head_dim (no query LoRA)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (DeepSeek-V2); yarn_factor 0 is plain rope
    yarn_factor: float = 0.0
    yarn_original_max_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # SSM (Mamba-2)
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 0
    conv_kernel: int = 4
    ssd_chunk: int = 256

    # RG-LRU
    rglru_width: int = 0

    # encoder-decoder (Whisper): n_layers = decoder layers
    enc_layers: int = 0

    # modality frontend stub (audio frames / vision patches): number of
    # precomputed frontend embedding tokens the caller feeds
    frontend_tokens: int = 0

    # compute
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    q_block: int = 512
    kv_block: int = 1024
    remat: bool = True
    # remat policy: "full" rematerializes everything; "dots" saves matmul
    # outputs (jax dots_saveable) trading HBM for ~25% less recompute
    remat_policy: str = "full"

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.n_heads, 1))

    @property
    def n_pattern_blocks(self) -> int:
        per = len(self.block_pattern)
        return (self.n_layers - len(self.extra_blocks)
                - len(self.lead_blocks)) // per

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this chip holds."""
        return self.experts_first, self.experts_held or self.n_experts

    def validate(self):
        per = len(self.block_pattern)
        assert (self.n_layers - len(self.extra_blocks)
                - len(self.lead_blocks)) % per == 0, \
            f"{self.name}: {self.n_layers} layers not divisible by " \
            f"pattern {self.block_pattern} + lead {self.lead_blocks} + " \
            f"extras {self.extra_blocks}"
        first, held = self.held_experts
        assert first + held <= self.n_experts, \
            f"{self.name}: experts {first}..{first + held - 1} held of " \
            f"{self.n_experts}"
