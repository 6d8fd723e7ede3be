"""Granite-4.0-H-Micro — 40 layers in periods of ten: nine Mamba-2 mixers,
then one GQA attention layer with no positional encoding (at 5, 15, 25,
35); a dense SwiGLU MLP in every layer; tied embeddings; Granite's four
scalar multipliers [hf:ibm-granite/granite-4.0-h-micro, granitemoehybrid]."""
from repro.configs.base import ModelConfig, register

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

GRANITE_4_H_MICRO = register(ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,                  # shared_intermediate_size; no routed experts
    vocab_size=100_352,
    ssm_state=128,
    ssm_conv=4,
    d_inner=4096,               # mamba_expand 2
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_chunk=256,
    layer_types=_PERIOD * 4,
    rope=False,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    source="hf:ibm-granite/granite-4.0-h-micro; hf",
))
