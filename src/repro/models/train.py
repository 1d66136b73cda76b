"""Loss + train_step / serve_step factories.

``TrainState`` is the *complete* job state: on a malleability resize the whole
pytree is redistributed to the new mesh (DMRlib's "robust restart").
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import model as M
from repro.optim.adamw import AdamW, OptState


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    step: jnp.ndarray          # int32 scalar
    rng: jnp.ndarray           # PRNG key data
    data_cursor: jnp.ndarray   # int32 sample counter (data-pipeline state)


def init_state(cfg: ArchConfig, optimizer: AdamW, seed: int = 0) -> TrainState:
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, key)
    return TrainState(params=params, opt=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32),
                      rng=jax.random.key_data(jax.random.PRNGKey(seed + 1)),
                      data_cursor=jnp.zeros((), jnp.int32))


def abstract_state(cfg: ArchConfig, optimizer: AdamW) -> TrainState:
    """ShapeDtypeStruct TrainState for dry-run lowering (no allocation)."""
    params = M.abstract_params(cfg)
    mdt = jnp.dtype(optimizer.moment_dtype)
    mom = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, mdt), params)
    return TrainState(
        params=params,
        opt=OptState(mu=mom, nu=jax.tree.map(lambda x: x, mom),
                     count=jax.ShapeDtypeStruct((), jnp.int32)),
        step=jax.ShapeDtypeStruct((), jnp.int32),
        rng=jax.ShapeDtypeStruct((4,), jnp.uint32),
        data_cursor=jax.ShapeDtypeStruct((), jnp.int32))


LOSS_CHUNK = 1024   # sequence chunk for the CE loss (0 => unchunked)


def _ce_chunk(embed_params, x_c, labels_c, mask_c, cfg: ArchConfig):
    """Cross-entropy over one sequence chunk; logits never leave the chunk."""
    from repro.models.layers import unembed
    logits = unembed(embed_params, x_c, cfg).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - ll) * mask_c)


def chunked_ce(embed_params, x, labels, mask, cfg: ArchConfig,
               chunk: int = LOSS_CHUNK):
    """Sum of masked CE without materializing (B, S, V) logits.

    The (B, chunk, V) logits are recomputed in the backward (checkpoint),
    bounding the loss-region memory at 235B-vocab scale.
    """
    B, S, D = x.shape
    c = min(chunk, S) if chunk else S
    if S % c != 0:
        c = S
    nc = S // c
    if nc <= 1:
        return _ce_chunk(embed_params, x, labels, mask, cfg)

    xs = jnp.moveaxis(x.reshape(B, nc, c, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, c), 1, 0)
    ms = jnp.moveaxis(mask.reshape(B, nc, c), 1, 0)

    @jax.checkpoint
    def body(tot, inp):
        x_c, l_c, m_c = inp
        return tot + _ce_chunk(embed_params, x_c, l_c, m_c, cfg), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ls, ms))
    return tot


def loss_fn(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]):
    x, aux = M.forward_hidden(params, cfg, batch)
    labels, mask = batch["labels"], batch["mask"]
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        # hidden covers [patch prefix + text]; loss only on the text span
        p = cfg.frontend.tokens_per_sample
        x = x[:, p:, :]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = chunked_ce(params["embed"], x, labels, mask, cfg) / denom
    return loss + aux, {"ce_loss": loss, "aux_loss": aux}


def make_train_step(cfg: ArchConfig, optimizer: AdamW):
    mb = max(1, cfg.train_microbatches)

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        B = batch["tokens"].shape[0]
        eff_mb = mb if (B % mb == 0 and B >= mb) else 1
        if eff_mb == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, batch), has_aux=True)(state.params)
        else:
            # gradient accumulation: halves activation/stash memory per pass;
            # the per-microbatch psum also overlaps with the next microbatch's
            # compute under XLA's latency-hiding scheduler.
            mb_batch = jax.tree.map(
                lambda t: t.reshape(eff_mb, t.shape[0] // eff_mb, *t.shape[1:]), batch)
            acc_dt = jnp.dtype(cfg.opt_moment_dtype)

            def body(acc, one):
                (l, m), g = jax.value_and_grad(
                    lambda p: loss_fn(p, cfg, one), has_aux=True)(state.params)
                acc = jax.tree.map(lambda a, gg: a + gg.astype(acc_dt), acc, g)
                return acc, (l, m)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), state.params)
            grads, (losses, ms) = jax.lax.scan(body, zeros, mb_batch)
            grads = jax.tree.map(lambda g: g / eff_mb, grads)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, ms)

        new_params, new_opt, gnorm = optimizer.update(grads, state.opt,
                                                      state.params)
        new_state = TrainState(
            params=new_params, opt=new_opt, step=state.step + 1,
            rng=state.rng,
            data_cursor=state.data_cursor + batch["tokens"].shape[0])
        metrics = dict(metrics, loss=loss, grad_norm=gnorm,
                       step=new_state.step)
        return new_state, metrics

    return train_step


def _mask_padded_vocab(logits, cfg: ArchConfig):
    """Physical vocab is padded to a shardable multiple; mask the pad ids."""
    v = logits.shape[-1]
    if v == cfg.vocab_size:
        return logits
    ids = jnp.arange(v)
    return jnp.where(ids[None, :] < cfg.vocab_size, logits, -jnp.inf)


def make_serve_step(cfg: ArchConfig, row_stable: bool = False):
    """One-token batched decode: (params, cache, tokens, index) -> ...
    (``row_stable``: see ``models.model.decode_step``)."""
    def serve_step(params, cache, tokens, cache_index):
        logits, cache = M.decode_step(params, cfg, tokens, cache, cache_index,
                                      row_stable=row_stable)
        with jax.named_scope("sample"):
            masked = _mask_padded_vocab(logits[:, -1, :], cfg)
            next_tok = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache

    return serve_step


def make_prefill_step(cfg: ArchConfig):
    """Full-sequence forward; only last-position logits are materialized."""
    from repro.models.layers import unembed

    def prefill_step(params, batch):
        x, _ = M.forward_hidden(params, cfg, batch)
        logits = unembed(params["embed"], x[:, -1:, :], cfg)
        masked = _mask_padded_vocab(logits[:, -1, :], cfg)
        return jnp.argmax(masked, axis=-1).astype(jnp.int32)

    return prefill_step
