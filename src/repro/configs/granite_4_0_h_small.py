"""Granite 4.0-H Small (32B total, 9B active): Mamba-2 + NoPE GQA hybrid,
attention at layers l % 10 == 5, a 72-expert top-10 MoE plus one shared
SwiGLU on every layer, muP multipliers.

[hf:ibm-granite/granite-4.0-h-small config.json]

One chip holds experts 0-8 of 72: the deployment is 8 chips (a v5e 2x4)
with expert parallelism over 8 and everything else replicated.  The router
keeps its 72 outputs and top-10; each chip computes its own experts' part.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,              # one expert's width
    vocab_size=100352,
    n_experts=72,
    top_k=10,
    n_shared_experts=1,
    d_ff_shared=1536,
    n_experts_held=9,      # this chip's share: experts 0-8 of 72
    expert_first=0,
    ssm_state=128,
    d_inner=8192,          # 128 heads of 64
    ssm_head_dim=64,
    ssm_chunk=256,
    conv_kernel=4,
    attn_every=10,         # attention at l % 10 == 5, Mamba-2 elsewhere
    moe_every=1,           # MoE + shared MLP on every layer
    moe_offset=0,
    rope_theta=0.0,        # position_embedding_type: nope
    attn_scale=0.0078125,  # attention_multiplier: 1/128, not 1/sqrt(128)
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_embeddings=True,
    norm_eps=1e-5,
    source="hf:ibm-granite/granite-4.0-h-small",
    subquadratic=True,
    notes="36 Mamba-2 + 4 NoPE attention layers; 72 experts top-10 + a "
          "shared SwiGLU of 1536 on every layer; 9 experts held per chip.",
)
