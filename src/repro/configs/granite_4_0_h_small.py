"""granite-4.0-h-small [hybrid moe]: 40 layers in four periods of ten —
Mamba2 mixers at positions 0-4 and 6-9, GQA attention without positional
embedding at 5 (32H, kv=8, head 128) — each mixer followed by an MoE FFN
of 72 experts top-10 (SwiGLU width 768) plus a shared SwiGLU expert of
width 1536.  d_model 4096; Mamba2 128 heads of 64, d_state 128, one
group, conv 4; vocab 100352, tied.  Granite multipliers: embedding x12,
attention scale 1/128, residual x0.22, logits /16.
[hf:ibm-granite/granite-4.0-h-small]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    position_embedding_type="nope",
    rope_theta=1e4,
    tie_embeddings=True,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    attention_multiplier=0.0078125,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    num_experts=72,
    experts_per_token=10,
    capacity_factor=0.0,    # dropless, as published
    moe_shared_expert=True,
    d_ff_shared=1536,
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_ngroups=1,
    ssm_chunk=256,
    ssm_dconv=4,
)
