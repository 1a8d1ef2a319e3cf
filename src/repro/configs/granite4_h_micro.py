"""granite-4.0-h-micro [hybrid] — 40 layers in periods of ten (nine Mamba-2
layers, then attention at index 5), NoPE GQA 32/8, a SwiGLU MLP of 8192
in every layer, muP multipliers, tied embeddings
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid].
"""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,                # config.json leaves it unset: 2048 / 32
    d_ff=8192,                  # shared_intermediate_size (no experts)
    vocab_size=100352,
    # layer_types: attention at 5, 15, 25, 35
    layer_pattern="MMMMMAMMMM",
    ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, chunk_size=256,
                  conv_width=4),
    tie_embeddings=True,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    nope=True,                  # position_embedding_type "nope"
    source="hf:ibm-granite/granite-4.0-h-micro (config.json)",
)
