"""Model assembly for every assigned architecture family.

The same ``ArchConfig`` drives schema construction (parameters + logical
sharding axes), the training forward pass, and the decode (serving) path.
Layer stacks are scanned (``jax.lax.scan``) so HLO size — and hence dry-run
compile time on the 512-device mesh — stays O(1) in depth.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import blocks, params as P, ssm as ssm_mod
from repro.models.layers import (embed, embed_schema, rmsnorm,
                                 rmsnorm_schema, unembed)
from repro.models.params import ParamDef


# ----------------------------------------------------------------------
# Schema
# ----------------------------------------------------------------------

def model_schema(cfg: ArchConfig):
    s: Dict[str, Any] = {"embed": embed_schema(cfg),
                         "ln_f": rmsnorm_schema(cfg.d_model, cfg)}
    if cfg.is_ssm:
        s["layers"] = P.stack(blocks.ssm_block_schema(cfg), cfg.num_layers)
    elif cfg.is_hybrid:
        every = cfg.shared_attention_every
        groups = cfg.num_layers // every
        s["layers"] = P.stack(blocks.ssm_block_schema(cfg),
                              cfg.num_layers, axis_name="layers")
        s["shared_attn"] = {          # ONE weight set, applied per group
            "ln1": rmsnorm_schema(cfg.d_model, cfg),
            "attn": attn_mod.attention_schema(cfg),
            "ln2": rmsnorm_schema(cfg.d_model, cfg),
            "mlp": blocks.mlp_schema(cfg),
        }
        assert cfg.num_layers % every == 0, (cfg.num_layers, every)
        del groups
    elif cfg.is_interleaved:
        kinds = cfg.layer_types
        s["layers"] = {
            kind: P.stack(blocks.mixer_schema(cfg, kind), kinds.count(kind))
            for kind in ("mamba", "attention")}
        s["layers"]["mlp"] = P.stack(blocks.mlp_block_schema(cfg),
                                     cfg.num_layers)
    else:
        s["layers"] = P.stack(
            blocks.decoder_block_schema(cfg, cross=cfg.is_encdec),
            cfg.num_layers)
    if cfg.is_encdec:
        s["enc_layers"] = P.stack(blocks.encoder_block_schema(cfg),
                                  cfg.encoder_layers)
        s["ln_enc"] = rmsnorm_schema(cfg.d_model, cfg)
    if cfg.frontend is not None:
        s["frontend_proj"] = ParamDef((cfg.frontend.embed_dim, cfg.d_model),
                                      ("frontend", "embed"),
                                      dtype=cfg.param_dtype)
    return s


def init_params(cfg: ArchConfig, key):
    return P.init(model_schema(cfg), key)


def abstract_params(cfg: ArchConfig):
    return P.abstract(model_schema(cfg))


def logical_axes(cfg: ArchConfig):
    return P.logical_axes(model_schema(cfg))


# ----------------------------------------------------------------------
# Scanned trunk (training / prefill)
# ----------------------------------------------------------------------

def _scan_layers(layer_params, x, body, cfg: ArchConfig):
    """Scan ``body(x, one_layer_params) -> (x, aux)`` over stacked params."""
    fn = jax.checkpoint(body) if cfg.remat else body

    def wrapped(carry, lp):
        return fn(carry, lp)

    x, auxs = jax.lax.scan(wrapped, x, layer_params)
    return x, jnp.sum(auxs)


def _layer_period(cfg: ArchConfig):
    """A model with Mamba layers as a period of layer kinds and the number
    of periods in its depth: Mamba-2 one "mamba" layer; zamba2
    ``shared_attention_every`` of them and its shared attention block,
    "shared"; the interleaved hybrid its ``mixer_period``."""
    if cfg.is_interleaved:
        return cfg.mixer_period, cfg.num_layers // len(cfg.mixer_period)
    if cfg.is_hybrid:
        every = cfg.shared_attention_every
        return ("mamba",) * every + ("shared",), cfg.num_layers // every
    return ("mamba",), cfg.num_layers


def _period_runs(period):
    """``period`` as runs of one kind: ``(kind, first index among the
    period's layers of that kind, first layer, length)``."""
    runs, seen = [], {kind: 0 for kind in period}
    for i, kind in enumerate(period):
        if runs and runs[-1][0] == kind:
            k, first, layer, n = runs[-1]
            runs[-1] = (k, first, layer, n + 1)
        else:
            runs.append((kind, seen[kind], i, 1))
        seen[kind] += 1
    return runs, seen


def _period_scan(period, periods: int, carry, layer_fn,
                 remat: bool = False):
    """Layers scanned period by period: an outer scan over the ``periods``
    periods and in each one a scan per run of layers of one kind
    (granite-4.0-h: 5 Mamba, 1 attention, 4 Mamba).
    ``layer_fn(carry, kind, index, layer)`` applies the ``layer``-th layer
    of the depth, the ``index``-th of its kind, both traced int32
    scalars."""
    runs, per_kind = _period_runs(period)

    def one_period(c, p):
        for kind, first, layer0, n in runs:
            def body(cc, i, kind=kind, first=first, layer0=layer0):
                return layer_fn(cc, kind, p * per_kind[kind] + first + i,
                                p * len(period) + layer0 + i), None
            if remat:
                body = jax.checkpoint(body)
            c, _ = jax.lax.scan(body, c, jnp.arange(n))
        return c, None

    carry, _ = jax.lax.scan(one_period, carry, jnp.arange(periods))
    return carry


def _layer_params(stack, index):
    return jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, index, 0, keepdims=False),
        stack)


def _trunk(params, x, cfg: ArchConfig, positions, enc_out=None):
    """Hidden-state trunk shared by train and prefill. Returns (x, aux)."""
    if cfg.is_interleaved:
        layers = params["layers"]

        def layer(h, kind, index, l):
            return blocks.interleaved_layer_apply(
                _layer_params(layers[kind], index),
                _layer_params(layers["mlp"], l), h, cfg, kind,
                positions=positions)
        return _period_scan(*_layer_period(cfg), x, layer,
                            remat=cfg.remat), jnp.float32(0.0)

    if cfg.is_ssm:
        def body(h, lp):
            return blocks.ssm_block_apply(lp, h, cfg), jnp.float32(0.0)
        return _scan_layers(params["layers"], x, body, cfg)

    if cfg.is_hybrid:
        every = cfg.shared_attention_every
        groups = cfg.num_layers // every
        grouped = jax.tree.map(
            lambda t: t.reshape(groups, every, *t.shape[1:]), params["layers"])
        shared = params["shared_attn"]

        def group_body(h, glp):
            def inner(hh, lp):
                return blocks.ssm_block_apply(lp, hh, cfg), None
            # nested remat: without it the inner scan stashes every SSM
            # intermediate for all ``every`` layers during the group backward
            inner_fn = jax.checkpoint(inner) if cfg.remat else inner
            h, _ = jax.lax.scan(inner_fn, h, glp)
            hn = rmsnorm(shared["ln1"], h, cfg.norm_eps)
            h = h + attn_mod.attn_apply(shared["attn"], hn, cfg,
                                        positions=positions, causal=True)
            hn = rmsnorm(shared["ln2"], h, cfg.norm_eps)
            h = h + blocks.mlp(shared["mlp"], hn, cfg)
            return h, jnp.float32(0.0)

        body = jax.checkpoint(group_body) if cfg.remat else group_body
        x, auxs = jax.lax.scan(body, x, grouped)
        return x, jnp.sum(auxs)

    def body(h, lp):
        return blocks.decoder_block_apply(lp, h, cfg, positions=positions,
                                          enc_out=enc_out, causal=True)
    return _scan_layers(params["layers"], x, body, cfg)


def _encode(params, frames, cfg: ArchConfig):
    """Audio/encoder stack over precomputed frame embeddings (stub frontend)."""
    dt = jnp.dtype(cfg.dtype)
    x = jnp.einsum("bse,ed->bsd", frames.astype(dt),
                   params["frontend_proj"].astype(dt))
    positions = jnp.arange(x.shape[1])[None, :]

    def body(h, lp):
        return blocks.encoder_block_apply(lp, h, cfg, positions=positions), \
            jnp.float32(0.0)

    x, _ = _scan_layers(params["enc_layers"], x, body, cfg)
    return rmsnorm(params["ln_enc"], x, cfg.norm_eps)


def forward_hidden(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]):
    """Trunk only -> (final normed hidden (B, S, D), aux_loss).

    batch:
      tokens (B, S) int32            — always present (decoder tokens)
      patch_embeds (B, P, E)         — vlm only (prefix tokens)
      frames (B, S_enc, E)           — audio enc-dec only
    """
    from repro.parallel.context import constrain
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens, cfg)
    x = constrain(x, "act_batch", "act_seq_blk", "act_embed")
    enc_out = None

    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        dt = jnp.dtype(cfg.dtype)
        patches = jnp.einsum("bpe,ed->bpd", batch["patch_embeds"].astype(dt),
                             params["frontend_proj"].astype(dt))
        x = jnp.concatenate([patches, x], axis=1)
    if cfg.is_encdec:
        enc_out = _encode(params, batch["frames"], cfg)

    positions = jnp.arange(x.shape[1])[None, :]
    with jax.named_scope("layers"):
        x, aux = _trunk(params, x, cfg, positions, enc_out=enc_out)
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return x, aux


def forward(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]):
    """Training/prefill forward -> (full logits, aux_loss)."""
    x, aux = forward_hidden(params, cfg, batch)
    return unembed(params["embed"], x, cfg), aux


# ----------------------------------------------------------------------
# Decode (serving) path
# ----------------------------------------------------------------------

def _stacked(one, n: int):
    """``n`` layers' caches: every leaf of the one-layer cache ``one``
    broadcast along a new leading layer axis."""
    return jax.tree.map(lambda t: jnp.broadcast_to(t, (n,) + t.shape), one)


def init_cache(cfg: ArchConfig, batch: int, seq_len: int,
               enc_len: Optional[int] = None):
    """Decode-state pytree for one new token against a seq_len-deep
    context: one stack per kind of layer state, each layer's at its
    index."""
    L = cfg.num_layers
    if cfg.ssm is None:
        cache = {"layers": _stacked(
            attn_mod.init_kv_cache(cfg, batch, seq_len), L)}
        if cfg.is_encdec:
            cache["cross"] = _stacked(attn_mod.init_kv_cache(
                cfg, batch, enc_len or seq_len), L)
        return cache
    ssm_one = ssm_mod.init_ssm_cache(cfg, batch)
    if cfg.is_interleaved:
        kinds = cfg.layer_types
        return {"mamba": _stacked(ssm_one, kinds.count("mamba")),
                "attention": _stacked(
                    attn_mod.init_kv_cache(cfg, batch, seq_len),
                    kinds.count("attention"))}
    cache = {"layers": _stacked(ssm_one, L)}
    if cfg.is_hybrid:
        cache["shared_kv"] = _stacked(
            attn_mod.init_kv_cache(cfg, batch, seq_len),
            L // cfg.shared_attention_every)
    return cache


#: sublane rows of a TPU (8, 128) tile. Below it the chip's compiler lays
#: activations out in smaller tiles and fuses the projections differently,
#: so a row would round differently in a smaller batch.
ROW_TILE = 8


def row_stable_decode(cfg: ArchConfig) -> bool:
    """Whether ``decode_step(row_stable=True)`` covers ``cfg``: attention
    decoders whose sequences never meet (no expert capacity shared between
    tokens). Models with SSM state (Mamba-2, the shared-attention hybrid,
    the interleaved hybrid) are not covered yet."""
    return not (cfg.is_ssm or cfg.is_hybrid or cfg.is_interleaved
                or cfg.is_moe)


def _decoder_kind(cfg: ArchConfig) -> str:
    """What keeps ``cfg`` out of ``row_stable_decode``."""
    if cfg.is_interleaved:
        return "an interleaved Mamba-2/attention hybrid"
    if cfg.is_hybrid:
        return "a shared-attention hybrid"
    return "a Mamba-2 model" if cfg.is_ssm else "a mixture of experts"


def decode_step(params, cfg: ArchConfig, tokens, cache, cache_index, *,
                row_stable: bool = False):
    """One-token decode. tokens: (B, 1) int32. Returns (logits, new cache).

    The layer scan carries the cache, one stack per kind of layer state
    (``init_cache``), and each layer reads and writes its own slice of its
    stack at a traced index, in place: a donated cache is updated where it
    lies, with no layer slice or whole-cache copy. Attention decoders scan
    their layers in one scan; models with Mamba layers scan them period by
    period (``_layer_period``).

    ``row_stable`` computes each sequence the same way whatever ``B`` is:
    the dense layers run on the batch padded to a multiple of ``ROW_TILE``
    rows, and attention reads the cache one sequence at a time. A replica
    that spreads its batch over more devices then keeps its tokens bit for
    bit (``serve/replica.py``). Only where ``row_stable_decode(cfg)``.
    """
    B = tokens.shape[0]
    if row_stable:
        if not row_stable_decode(cfg):
            raise NotImplementedError(
                f"row-stable decode covers attention decoders, not "
                f"{cfg.name} ({_decoder_kind(cfg)})")
        tokens = jnp.pad(tokens, ((0, -B % ROW_TILE), (0, 0)))
    x = embed(params["embed"], tokens, cfg)

    if cfg.ssm is None:
        def body(carry, sc):
            h, kv = carry
            lp, layer = sc
            h, kv = blocks.decoder_block_decode(
                lp, h, cfg, kv, cache_index=cache_index, layer=layer,
                cross_cache=cache.get("cross"), row_stable=row_stable)
            return (h, kv), None

        with jax.named_scope("layers"):
            (x, kv), _ = jax.lax.scan(
                body, (x, cache["layers"]),
                (params["layers"], jnp.arange(cfg.num_layers)))
        cache = {**cache, "layers": kv}

    else:
        layers, shared = params["layers"], params.get("shared_attn")
        # each kind of layer: its mixer's parameters at its index, its
        # MLP's at its layer (None: no MLP), its cache stack's key, and
        # whether its attention reads the cache one sequence at a time;
        # zamba2's shared block keeps the batch read
        if cfg.is_interleaved:
            mlp = functools.partial(_layer_params, layers["mlp"])
            kinds = {kind: (functools.partial(_layer_params, layers[kind]),
                            mlp, kind, kind == "attention")
                     for kind in ("mamba", "attention")}
        else:
            kinds = {"mamba": (functools.partial(_layer_params, layers),
                               None, "layers", False),
                     "shared": (lambda _: {"ln": shared["ln1"],
                                           "attn": shared["attn"]},
                                lambda _: {"ln": shared["ln2"],
                                           "mlp": shared["mlp"]},
                                "shared_kv", False)}

        def layer(carry, kind, index, l):
            h, c = carry
            mixer, mlp, key, per_row = kinds[kind]
            h, new = blocks.mixer_layer_decode(
                mixer(index), None if mlp is None else mlp(l), h, cfg, kind,
                c[key], layer=index, cache_index=cache_index,
                row_stable=per_row)
            return h, {**c, key: new}

        with jax.named_scope("layers"):
            x, cache = _period_scan(*_layer_period(cfg), (x, cache), layer)

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg)
    if row_stable:
        # as in decode_attn_apply: the slice must not move into the unembed
        logits = jax.lax.optimization_barrier(logits)
    return logits[:B], cache
