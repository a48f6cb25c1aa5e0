"""granite-4.0-h-small [hybrid] — 40L d_model=4096; Mamba-2 (128 heads of
64, d_state 128, one group, conv 4) at 36 layers and NoPE GQA attention
(32 query / 8 KV heads of 128) at layers 5, 15, 25 and 35; every layer an
MoE FFN of 72 experts of width 768, top-10, plus one shared expert of
width 1,536; tied embeddings, vocab 100,352; granite's multipliers
(embedding 12, attention 1/128, residual 0.22, logits 1/16). 32B total,
9B active. [hf:ibm-granite/granite-4.0-h-small, config.json]

Routing is dropless (``moe_capacity_factor`` 0): at decode a capacity of
1.25 drops tokens whenever routing is uneven. A deployment holds a share
of each layer's experts on each chip (``ModelConfig.chip_share``).
"""
from repro.common.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    arch_type="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    num_experts=72,
    experts_per_token=10,
    moe_every=1,
    moe_capacity_factor=0.0,
    shared_expert_ff=1536,
    attn_every=10,
    attn_index=5,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_conv_width=4,
    use_rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    global_attn_cap=131072,
    citation="hf:ibm-granite/granite-4.0-h-small",
)
