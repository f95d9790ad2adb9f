"""granite-4.0-h-small [hybrid] — 40L d_model=4096, Mamba-2 (128 heads of
64, d_state 128) with one NoPE GQA layer (32H, kv=8, head 128) per period
of 10, MoE 72e top-10 (width 768) + a shared SwiGLU expert (1536) in every
layer, vocab=100352, tied. 32B total, 9B active.
[hf:ibm-granite/granite-4.0-h-small config.json]

muP constants: embeddings × 12, every mixer and FFN output × 0.22,
attention scores × 1/128, logits / 16.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    mlp_kind="swiglu",
    norm_kind="rmsnorm",
    norm_eps=1e-5,
    pos_embedding="none",
    moe_experts=72,
    moe_top_k=10,
    moe_shared_ff=1536,
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_chunk=256,
    ssm_groups=1,
    attn_every=10,
    attn_index=5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attn_scale=0.0078125,
    logits_scaling=16.0,
    param_dtype="bfloat16",
)
