"""granite-3-2b [dense] — GQA decoder-only transformer.

40L d_model=2048 32H (GQA kv=8) d_ff=8192 vocab=49155
[hf:ibm-granite/granite-3.0-2b-base; hf]
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-3-2b",
    family="dense",
    source="hf:ibm-granite/granite-3.0-2b-base; hf",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=49155,
    attention="full",
    tie_embeddings=True,
    # Granite's multipliers are not run: the block is a plain dense decoder
    embedding_multiplier=1.0,
    residual_multiplier=1.0,
    attention_multiplier=None,   # head_dim ** -0.5
    logits_scaling=1.0,
)
