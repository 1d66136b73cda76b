"""granite-4.0-h-micro [hybrid] — Mamba-2 layers interleaved with GQA.

40L d_model=2048: per 10 layers 5 Mamba-2, 1 attention, 4 Mamba-2; every
layer has a SwiGLU MLP of 8192. Attention 32H (GQA kv=8) of 64, no rotary
(NoPE). Mamba-2 64 heads of 64, state 128, one group, conv 4 with a bias.
Granite's multipliers: embedding 12, attention 1/64, residual 0.22, logits
divided by 8. vocab=100352, tied.
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid]
"""
from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    source="hf:ibm-granite/granite-4.0-h-micro; hf",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,                   # shared_intermediate_size
    vocab_size=100352,
    attention="full",
    position_embedding="nope",
    ssm=SSMConfig(state_size=128, head_dim=64, expand=2, conv_width=4,
                  chunk_size=256, conv_bias=True),
    mixer_period=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    tie_embeddings=True,
)
