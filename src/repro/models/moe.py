"""Mixture-of-experts: top-k router + capacity-bucketed dispatch.

Dispatch is scatter/gather-based so HLO FLOPs reflect *active* experts only
(roofline honesty) and the (experts, capacity, d_model) buckets shard cleanly
over the expert-parallel mesh axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import ParamDef


def moe_schema(cfg: ArchConfig):
    assert cfg.moe is not None
    d, e, f = cfg.d_model, cfg.moe.num_experts, cfg.moe.d_ff
    pd = cfg.param_dtype
    return {
        "router": ParamDef((d, e), ("embed", "experts_in"), dtype=pd),
        "wi_gate": ParamDef((e, d, f), ("experts", "embed", "expert_mlp"), dtype=pd),
        "wi_up":   ParamDef((e, d, f), ("experts", "embed", "expert_mlp"), dtype=pd),
        "wo":      ParamDef((e, f, d), ("experts", "expert_mlp", "embed"), dtype=pd,
                            init="scaled_normal"),
    }


def capacity(tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(tokens * m.experts_per_token * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)   # round up to 8


def moe_apply(params, x, cfg: ArchConfig):
    """x: (B, S, D) -> (B, S, D), plus aux load-balancing loss.

    With an active sharding context the dispatch runs under shard_map with
    explicit collectives (XLA's auto-partitioner replicates the scatter onto
    the expert-sharded buckets — a 60+ GiB/device disaster at 235B scale);
    otherwise the global reference formulation below is used (CPU tests, and
    the oracle the shard_map path is validated against).
    """
    from repro.parallel.context import get_context
    ctx = get_context()
    if ctx is not None and ctx[0].devices.size > 1:
        return _moe_apply_shardmap(params, x, cfg, ctx[0], ctx[1])
    return moe_apply_reference(params, x, cfg)


def moe_apply_reference(params, x, cfg: ArchConfig):
    """Global (mesh-agnostic) reference formulation."""
    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    B, S, D = x.shape
    T = B * S
    k = m.experts_per_token
    E = m.num_experts
    C = capacity(T, cfg)

    xf = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)              # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # ---- aux loss (Switch-style load balancing) ----------------------
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    aux_loss = jnp.sum(density * density_prob) * E * m.aux_loss_weight

    # ---- capacity bucketing (sort-based ranks: O(Tk) memory, never the
    # (Tk, E) one-hot cumsum — that buffer alone is 4 GiB+ at 1M tokens) ----
    flat_e = expert_idx.reshape(T * k)                           # (Tk,)
    order = jnp.argsort(flat_e)                                  # stable
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))           # (E,)
    ranks_sorted = jnp.arange(T * k) - starts[sorted_e]
    pos = jnp.zeros((T * k,), jnp.int32).at[order].set(
        ranks_sorted.astype(jnp.int32))
    keep = pos < C
    slot_e = jnp.where(keep, flat_e, E)                          # drop -> OOB
    slot_c = jnp.where(keep, pos, 0)

    from repro.parallel.context import constrain
    token_rows = jnp.repeat(xf.astype(dt), k, axis=0)            # (Tk, D)
    buckets = jnp.zeros((E, C, D), dt).at[slot_e, slot_c].add(
        token_rows, mode="drop")                                 # (E, C, D)
    buckets = constrain(buckets, "act_experts", "act_cap", "act_embed")

    # ---- expert compute (EP-shardable grouped matmul) ----------------
    g = jnp.einsum("ecd,edf->ecf", buckets, params["wi_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buckets, params["wi_up"].astype(dt))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(dt))   # (E, C, D)
    y = constrain(y, "act_experts", "act_cap", "act_embed")

    # ---- combine ------------------------------------------------------
    gathered = y.at[slot_e, slot_c].get(mode="fill", fill_value=0)  # (Tk, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    w = gate_vals.reshape(T * k, 1).astype(dt)
    out = jnp.sum((gathered * w).reshape(T, k, D), axis=1)
    return out.reshape(B, S, D), aux_loss


# ----------------------------------------------------------------------
# shard_map dispatch: per-shard routing + explicit collectives
# ----------------------------------------------------------------------

def _local_dispatch(xf, logits, cfg: ArchConfig, C: int):
    """Token->bucket dispatch for a LOCAL token block.

    xf: (T, D), logits: (T, E). Returns (buckets (E,C,D), slot_e, slot_c,
    keep, gate_vals, aux_loss).
    """
    m = cfg.moe
    T, D = xf.shape
    E, k = m.num_experts, m.experts_per_token
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_prob) * E * m.aux_loss_weight

    flat_e = expert_idx.reshape(T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # local: small
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    slot_e = jnp.where(keep, flat_e, E)
    slot_c = jnp.where(keep, pos, 0)
    rows = jnp.repeat(xf, k, axis=0)
    buckets = jnp.zeros((E, C, D), xf.dtype).at[slot_e, slot_c].add(
        rows, mode="drop")
    return buckets, slot_e, slot_c, keep, gate_vals, aux


def _local_combine(y, slot_e, slot_c, keep, gate_vals, T: int):
    """y: (E, C, D) -> (T, D) weighted combine."""
    k = gate_vals.shape[-1]
    D = y.shape[-1]
    g = y.at[slot_e, slot_c].get(mode="fill", fill_value=0)
    g = jnp.where(keep[:, None], g, 0)
    w = gate_vals.reshape(T * k, 1).astype(y.dtype)
    return jnp.sum((g * w).reshape(T, k, D), axis=1)


def _moe_apply_shardmap(params, x, cfg: ArchConfig, mesh, rules):
    """Expert dispatch with explicit collectives under shard_map.

    Modes (picked by how the expert axis is sharded in the rules):
      EP  — experts sharded over "model": local dispatch -> all_to_all over
            "model" -> expert matmul on E/ep experts -> all_to_all back.
      TP  — experts replicated, expert_mlp sharded over "model" (mixtral):
            local dispatch -> per-shard F-slice matmul -> psum("model").
    Expert weights are FSDP-sharded over "data" at rest and all-gathered
    just-in-time (the paper-era analogue: weights live distributed, compute
    needs them whole).
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel.context import constrain
    from repro.parallel.sharding import spec_for_axes

    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    B, S, D = x.shape
    E, k = m.num_experts, m.experts_per_token

    ep_n = mesh.shape.get("model", 1)
    ep_mode = (rules.get("experts") is not None
               and "model" in (rules.get("experts") or ())
               and E % ep_n == 0 and ep_n > 1)

    # EP: tokens stay fully distributed (each shard routes its own tokens;
    # the all-to-all moves them to their experts and back). TP: the model
    # axis F-slices each token's expert MLP and psum-combines, so every
    # model shard MUST hold the SAME tokens — gather the sequence first
    # (seq-sharded TP would psum partials of *different* tokens).
    seq_axis = "act_seq_blk" if ep_mode else "act_seq"
    x = constrain(x, "act_batch", seq_axis, "act_embed")
    x_spec = spec_for_axes(("act_batch", seq_axis, "act_embed"),
                           rules, mesh, x.shape)

    def pspec(name):
        d = params[name]
        ax = {"router": ("embed", "experts_in"),
              "wi_gate": ("experts", "embed", "expert_mlp"),
              "wi_up": ("experts", "embed", "expert_mlp"),
              "wo": ("experts", "expert_mlp", "embed")}[name]
        return spec_for_axes(ax, rules, mesh, d.shape)

    in_specs = (pspec("router"), pspec("wi_gate"), pspec("wi_up"), pspec("wo"),
                x_spec)
    out_specs = (x_spec, P())

    # local token count per shard (for capacity)
    def _shards(spec, dim_size, i):
        ent = spec[i] if i < len(spec) else None
        if ent is None:
            return 1
        ents = ent if isinstance(ent, tuple) else (ent,)
        n = 1
        for a in ents:
            n *= mesh.shape[a]
        return n

    B_loc = B // _shards(x_spec, B, 0)
    S_loc = S // _shards(x_spec, S, 1)
    T_loc = B_loc * S_loc
    C_loc = max(8, -(-int(T_loc * k * m.capacity_factor / E) // 8) * 8)

    def body(rw, wig, wiu, wo, xb):
        # gather FSDP ("data") shards of the weights just-in-time
        if "data" in mesh.axis_names and mesh.shape["data"] > 1:
            rw = jax.lax.all_gather(rw, "data", axis=0, tiled=True)
            wig = jax.lax.all_gather(wig, "data", axis=1, tiled=True)
            wiu = jax.lax.all_gather(wiu, "data", axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        xf = xb.reshape(-1, D).astype(dt)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            rw.astype(jnp.float32))
        buckets, se, sc, keep, gv, aux = _local_dispatch(xf, logits, cfg, C_loc)

        if ep_mode:
            # (E, C, D) -> (E/ep, C*ep, D)
            b = jax.lax.all_to_all(buckets, "model", split_axis=0,
                                   concat_axis=1, tiled=True)
            g = jnp.einsum("ecd,edf->ecf", b, wig.astype(dt))
            u = jnp.einsum("ecd,edf->ecf", b, wiu.astype(dt))
            h = jax.nn.silu(g) * u
            y = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))
            y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                                   tiled=True)          # back to (E, C, D)
        else:
            # expert-TP: every shard holds all experts with an F-slice
            g = jnp.einsum("ecd,edf->ecf", buckets, wig.astype(dt))
            u = jnp.einsum("ecd,edf->ecf", buckets, wiu.astype(dt))
            h = jax.nn.silu(g) * u
            y = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))
            y = jax.lax.psum(y, "model")

        out = _local_combine(y, se, sc, keep, gv, T_loc)
        out = out.reshape(B_loc, S_loc, D)
        aux = jax.lax.pmean(aux, tuple(a for a in mesh.axis_names))
        return out, aux

    out, aux = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
        params["router"], params["wi_gate"], params["wi_up"], params["wo"], x)
    return out, aux
