"""kimi-k2-1t-a32b [moe] — Kimi-K2-Instruct, the DeepSeek-V3 block
[hf:moonshotai/Kimi-K2-Instruct config.json, model_type kimi_k2].

61 layers at hidden 7168: one leading dense layer (SwiGLU 18432), then 60
MoE layers of 384 sigmoid-routed experts (width 2048, 8 per token, chosen
by score plus a correction bias, weights normalised and scaled by 2.827)
beside one shared expert.  Attention is MLA with 64 heads (q rank 1536, kv
latent 512, 128 + 64 rotary query/key dims, 128 value dims), yarn rope
(factor 32 over 4,096 original positions, theta 50,000).  Untied 163,840
vocabulary; no multi-token prediction layer.

1T of parameters forces the memory recipe for training: an int8
blockwise first moment and a factored second moment.
"""
from repro.configs.base import (AttnConfig, MLAConfig, ModelConfig,
                                MoEConfig, OptimizerConfig, YarnConfig)

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b", family="moe",
    num_layers=61, d_model=7168, num_heads=64, num_kv_heads=64,
    d_ff=18_432, vocab_size=163_840,
    lead_pattern=("mla_dense",), block_pattern=("mla_moe",),
    attn=AttnConfig(rope_theta=50_000.0, rope_scaling=YarnConfig(
        factor=32.0, beta_fast=1.0, beta_slow=1.0, mscale=1.0,
        mscale_all_dim=1.0, original_max_position_embeddings=4096)),
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=384, top_k=8, expert_ff=2048,
                  num_shared_experts=1, routed_scaling=2.827),
    norm_eps=1e-6,
    tie_embeddings=False,
)

OPTIMIZER = OptimizerConfig(moment_dtype="int8", second_moment="factored")
