"""DeepSeek-V2-Lite (16B total, 2.4B active). [arXiv:2405.04434; hf
deepseek-ai/DeepSeek-V2-Lite config.json]

27L d_model=2048, 16 heads of multi-head latent attention (no query
compression, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128; YaRN
rotary, factor 40 over 4096 positions, theta 1e4, interleaved pairs).
Layer 0 has a dense SwiGLU MLP of width 10944; layers 1-26 have 64 routed
SwiGLU experts of width 1408, top-6 by softmax without renormalisation,
plus 2 shared experts run as one MLP of width 2816. vocab=102400,
untied, embeddings not scaled, RMSNorm epsilon 1e-6.
"""
from repro.configs.base import (AttentionConfig, ModelConfig, MoEConfig,
                                MoPConfig, YarnConfig)

CONFIG = ModelConfig(
    arch_id="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    d_ff=10944,
    vocab_size=102400,
    attention=AttentionConfig(
        num_heads=16, num_kv_heads=16, head_dim=128, rope_theta=1e4,
        kv_lora_rank=512, qk_rope_head_dim=64, v_head_dim=128,
        yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                        beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                        mscale_all_dim=0.707)),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  norm_topk_prob=False, routed_scaling_factor=1.0,
                  d_ff_shared=2 * 1408),
    mop=MoPConfig(enabled=True, bits=4, group_size=64, num_q_experts=0),
    first_dense_layers=1,
    embed_multiplier=1.0,
    act="swiglu",
)
