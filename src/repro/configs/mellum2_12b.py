"""Mellum2-12B-A2.5B (JetBrains) as one chip's share of an expert-parallel
deployment [hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

28L d_model=2304, 32 query / 4 KV heads of 128, vocab 98304 (untied);
layers (sliding, sliding, sliding, full) x 7: sliding layers see a 1024-token
window with plain RoPE theta 5e5, full layers YaRN (factor 16 over 8192,
beta 32/1, attention factor 1.27726); every MLP is 64 SwiGLU experts of
width 896, softmax router, top-8 renormalised, no shared expert.

The deployment: 8 chips, each holding 8 experts of every layer (expert
parallel) and all attention weights, serving its own lanes (data-parallel
attention).  This chip holds experts 0-7: the router scores all 64, and the
expert layer returns the held experts' part.  The config has no q/k norm
and no attention bias; the MTP head is not modelled.
"""
from repro.core.config import (ArchConfig, AttentionConfig, DMSConfig,
                               MLPConfig, MoEConfig, YarnConfig)

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    num_layers=28,
    d_model=2304,
    vocab_size=98304,
    attn=AttentionConfig(
        num_heads=32, num_kv_heads=4, head_dim=128, rope="full",
        rope_theta=5e5, window=1024,
        yarn=YarnConfig(factor=16.0, original_max_position_embeddings=8192,
                        beta_fast=32.0, beta_slow=1.0,
                        attention_factor=1.2772588722239782)),
    mlp=MLPConfig(d_ff=896, kind="swiglu",
                  moe=MoEConfig(num_experts=64, top_k=8,
                                held_experts=tuple(range(8)))),
    layer_pattern=("attn_local",) * 3 + ("attn",),
    norm_eps=1e-6,
    tie_embeddings=False,
    dms=DMSConfig(enabled=True, window=256, target_cr=8.0),
    family="moe",
    sub_quadratic=False,
)

# one period of the pattern; one of 8 shares (2 of 16 experts, top-4); a
# 16-token window and a 4-step DMS delay, so short decodes cross both
SMOKE = ArchConfig(
    name="mellum2-12b-a2.5b",
    num_layers=4,
    d_model=64,
    vocab_size=512,
    attn=AttentionConfig(
        num_heads=4, num_kv_heads=2, head_dim=16, rope="full",
        rope_theta=5e5, window=16,
        yarn=YarnConfig(factor=4.0, original_max_position_embeddings=32,
                        beta_fast=32.0, beta_slow=1.0,
                        attention_factor=1.1386294361119891)),
    mlp=MLPConfig(d_ff=32, kind="swiglu",
                  moe=MoEConfig(num_experts=16, top_k=4,
                                held_experts=(0, 1))),
    layer_pattern=("attn_local",) * 3 + ("attn",),
    norm_eps=1e-6,
    tie_embeddings=False,
    dms=DMSConfig(enabled=True, window=4, target_cr=8.0),
    family="moe",
)
