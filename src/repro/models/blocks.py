"""Block assembly: dense/MoE decoder blocks, SSM blocks, hybrid & enc-dec."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp, mlp_schema, rmsnorm, rmsnorm_schema


# ----------------------------------------------------------------------
# Transformer decoder block (self-attn + MLP or MoE)
# ----------------------------------------------------------------------

def decoder_block_schema(cfg: ArchConfig, cross: bool = False):
    s = {
        "ln1": rmsnorm_schema(cfg.d_model, cfg),
        "attn": attn.attention_schema(cfg),
        "ln2": rmsnorm_schema(cfg.d_model, cfg),
    }
    if cross:
        s["ln_x"] = rmsnorm_schema(cfg.d_model, cfg)
        s["cross"] = attn.attention_schema(cfg)
    if cfg.is_moe:
        s["moe"] = moe_mod.moe_schema(cfg)
    else:
        s["mlp"] = mlp_schema(cfg)
    return s


def decoder_block_apply(params, x, cfg: ArchConfig, *, positions,
                        enc_out=None, causal=True):
    from repro.parallel.context import constrain
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    x = x + attn.attn_apply(params["attn"], h, cfg, positions=positions,
                            causal=causal)
    x = constrain(x, "act_batch", "act_seq_blk", "act_embed")
    if enc_out is not None:
        h = rmsnorm(params["ln_x"], x, cfg.norm_eps)
        x = x + attn.attn_apply(params["cross"], h, cfg, positions=positions,
                                kv_x=enc_out, causal=False, rope=False)
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if cfg.is_moe:
        y, aux = moe_mod.moe_apply(params["moe"], h, cfg)
    else:
        y, aux = mlp(params["mlp"], h, cfg), jnp.float32(0.0)
    return constrain(x + y, "act_batch", "act_seq_blk", "act_embed"), aux


def decoder_block_decode(params, x, cfg: ArchConfig, cache, *, cache_index,
                         layer=None, cross_cache=None,
                         row_stable: bool = False):
    """One-token decode. cache: {"k","v"}, the stack of every layer's where
    ``layer`` is given (``attention.decode_attn_apply``); cross_cache:
    precomputed enc K/V of this layer."""
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    a, cache = attn.decode_attn_apply(params["attn"], h, cfg, cache,
                                      cache_index=cache_index, layer=layer,
                                      row_stable=row_stable)
    x = x + a
    if cross_cache is not None:
        h = rmsnorm(params["ln_x"], x, cfg.norm_eps)
        a, _ = attn.decode_attn_apply(params["cross"], h, cfg, cross_cache,
                                      cache_index=cache_index, cross=True,
                                      row_stable=row_stable)
        x = x + a
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_mod.moe_apply(params["moe"], h, cfg)
    else:
        y = mlp(params["mlp"], h, cfg)
    return x + y, cache


# ----------------------------------------------------------------------
# SSM (Mamba2) block
# ----------------------------------------------------------------------

def ssm_block_schema(cfg: ArchConfig):
    return {"ln": rmsnorm_schema(cfg.d_model, cfg),
            "ssm": ssm_mod.ssm_schema(cfg)}


def ssm_block_apply(params, x, cfg: ArchConfig):
    from repro.parallel.context import constrain
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    y = x + ssm_mod.ssm_apply(params["ssm"], h, cfg)
    return constrain(y, "act_batch", "act_seq_blk", "act_embed")


def ssm_block_decode(params, x, cfg: ArchConfig, cache, cache_index):
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    y, cache = ssm_mod.ssm_decode_step(params["ssm"], h, cfg, cache,
                                       cache_index)
    return x + y, cache


# ----------------------------------------------------------------------
# Interleaved hybrid layer (granite-4.0-h): x += r·mixer(norm(x));
# x += r·mlp(norm(x)), the mixer Mamba-2 or attention
# ----------------------------------------------------------------------

def mixer_schema(cfg: ArchConfig, kind: str):
    """One layer's mixer and its norm: ``kind`` "mamba" or "attention"."""
    if kind == "mamba":
        return ssm_block_schema(cfg)
    return {"ln": rmsnorm_schema(cfg.d_model, cfg),
            "attn": attn.attention_schema(cfg)}


def mlp_block_schema(cfg: ArchConfig):
    return {"ln": rmsnorm_schema(cfg.d_model, cfg), "mlp": mlp_schema(cfg)}


def _residual(x, y, cfg: ArchConfig):
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def interleaved_layer_apply(mixer, mlp_params, x, cfg: ArchConfig, kind: str,
                            *, positions):
    from repro.parallel.context import constrain
    h = rmsnorm(mixer["ln"], x, cfg.norm_eps)
    if kind == "mamba":
        y = ssm_mod.ssm_apply(mixer["ssm"], h, cfg)
    else:
        y = attn.attn_apply(mixer["attn"], h, cfg, positions=positions,
                            causal=True)
    x = constrain(_residual(x, y, cfg), "act_batch", "act_seq_blk",
                  "act_embed")
    h = rmsnorm(mlp_params["ln"], x, cfg.norm_eps)
    return constrain(_residual(x, mlp(mlp_params["mlp"], h, cfg), cfg),
                     "act_batch", "act_seq_blk", "act_embed")


def interleaved_layer_decode(mixer, mlp_params, x, cfg: ArchConfig,
                             kind: str, cache, *, layer, cache_index):
    """One-token decode of one layer. ``cache`` is the stack of the
    layers of ``kind`` (the SSM state and conv windows, or K and V) and
    ``layer`` the index of this one in it; its rows are written in place.
    Attention reads the stack one sequence at a time (``decode_attn_apply``'s
    row-stable read): read as a batch, the layer's K and V are sliced out
    and the whole stack laid out anew each step."""
    h = rmsnorm(mixer["ln"], x, cfg.norm_eps)
    if kind == "mamba":
        y, cache = ssm_mod.ssm_decode_step(mixer["ssm"], h, cfg, cache,
                                           cache_index, layer=layer)
    else:
        y, cache = attn.decode_attn_apply(mixer["attn"], h, cfg, cache,
                                          cache_index=cache_index,
                                          layer=layer, row_stable=True)
    x = _residual(x, y, cfg)
    h = rmsnorm(mlp_params["ln"], x, cfg.norm_eps)
    return _residual(x, mlp(mlp_params["mlp"], h, cfg), cfg), cache


# ----------------------------------------------------------------------
# Encoder block (bidirectional)
# ----------------------------------------------------------------------

def encoder_block_schema(cfg: ArchConfig):
    return {
        "ln1": rmsnorm_schema(cfg.d_model, cfg),
        "attn": attn.attention_schema(cfg),
        "ln2": rmsnorm_schema(cfg.d_model, cfg),
        "mlp": mlp_schema(cfg),
    }


def encoder_block_apply(params, x, cfg: ArchConfig, *, positions):
    from repro.parallel.context import constrain
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    x = x + attn.attn_apply(params["attn"], h, cfg, positions=positions,
                            causal=False)
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    return constrain(x + mlp(params["mlp"], h, cfg),
                     "act_batch", "act_seq", "act_embed")
