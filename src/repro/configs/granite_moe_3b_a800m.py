"""Granite 3.0 3B-A800M (IBM): fine-grained MoE, 40 experts of width 512,
top-8, tied embeddings and four muP scalars.
[hf:ibm-granite/granite-3.0-3b-a800m-base, config.json, GraniteMoeForCausalLM]"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,
    head_dim=64,
    d_ff=512,                  # intermediate_size: one expert's SwiGLU width
    vocab=49155,
    moe=MoEConfig(num_experts=40, top_k=8),
    tie_embeddings=True,
    rms_eps=1e-6,
    rope_theta=10_000.0,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=6.0,
    long_decode_window=4096,   # long_500k sliding-window variant (DESIGN.md)
    source="hf:ibm-granite/granite-3.0-3b-a800m-base",
)
