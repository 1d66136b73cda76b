"""Shared layers: norms, RoPE, MLPs, embeddings."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import ParamDef


# ----------------------------------------------------------------------
# RMSNorm
# ----------------------------------------------------------------------

def rmsnorm_schema(dim: int, cfg: ArchConfig):
    return {"scale": ParamDef((dim,), ("norm",), dtype=cfg.param_dtype, init="ones")}


@jax.named_scope("norm")
def rmsnorm(params, x, eps: float):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta)                         # (half,)
    ang = positions[..., :, None].astype(jnp.float32) * inv   # (..., seq, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., :, None, :]                                # broadcast over heads
    cos = cos[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# SwiGLU MLP
# ----------------------------------------------------------------------

def mlp_schema(cfg: ArchConfig, d_in: Optional[int] = None,
               d_ff: Optional[int] = None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    pd = cfg.param_dtype
    return {
        "wi_gate": ParamDef((d, f), ("embed", "mlp"), dtype=pd),
        "wi_up":   ParamDef((d, f), ("embed", "mlp"), dtype=pd),
        "wo":      ParamDef((f, d), ("mlp", "embed"), dtype=pd, init="scaled_normal"),
    }


@jax.named_scope("mlp")
def mlp(params, x, cfg: ArchConfig):
    from repro.parallel.context import constrain
    dt = jnp.dtype(cfg.dtype)
    # Megatron pattern: gather the seq-sharded residual, run TP over d_ff,
    # the block-boundary constraint reduce-scatters the output back. Left
    # implicit, XLA can instead replicate d_ff and all-reduce ~GiB blocks
    # (qwen2.5 under microbatching — EXPERIMENTS.md §Perf).
    x = constrain(x, "act_batch", "act_seq", "act_embed")
    gate = jnp.einsum("...d,df->...f", x, params["wi_gate"].astype(dt))
    gate = constrain(gate, "act_batch", "act_seq", "act_mlp")
    up = jnp.einsum("...d,df->...f", x, params["wi_up"].astype(dt))
    up = constrain(up, "act_batch", "act_seq", "act_mlp")
    h = jax.nn.silu(gate) * up
    return jnp.einsum("...f,fd->...d", h, params["wo"].astype(dt))


# ----------------------------------------------------------------------
# Embedding / unembedding
# ----------------------------------------------------------------------

def embed_schema(cfg: ArchConfig):
    from repro.configs.base import phys_vocab
    vp = phys_vocab(cfg.vocab_size)
    s = {"embedding": ParamDef((vp, cfg.d_model), ("vocab", "embed"),
                               dtype=cfg.param_dtype, scale=0.02)}
    if not cfg.tie_embeddings:
        s["unembed"] = ParamDef((cfg.d_model, vp), ("embed", "vocab"),
                                dtype=cfg.param_dtype)
    return s


@jax.named_scope("embed")
def embed(params, tokens, cfg: ArchConfig):
    table = params["embedding"].astype(jnp.dtype(cfg.dtype))
    x = jnp.take(table, tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


@jax.named_scope("unembed")
def unembed(params, x, cfg: ArchConfig):
    from repro.parallel.context import constrain
    dt = jnp.dtype(cfg.dtype)
    if cfg.tie_embeddings:
        w = params["embedding"].astype(dt)        # (V, D)
        logits = jnp.einsum("...d,vd->...v", x, w)
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["unembed"].astype(dt))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return constrain(logits, "act_batch", "act_seq", "act_vocab")
