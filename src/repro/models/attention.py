"""GQA attention: memory-bounded chunked (flash-style) softmax in pure JAX.

The chunked path is the XLA reference used by dry-runs and CPU tests; the
Pallas TPU kernel in ``repro.kernels.flash_attention`` implements the same
contract and is validated against ``repro.kernels.ref``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.params import ParamDef
from repro.models.layers import apply_rope, rmsnorm

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Schema
# ----------------------------------------------------------------------

def attention_schema(cfg: ArchConfig):
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    s = {
        "wq": ParamDef((d, h, hd), ("embed", "q_heads", "head_dim"), dtype=pd),
        "wk": ParamDef((d, hk, hd), ("embed", "kv_heads", "head_dim"), dtype=pd),
        "wv": ParamDef((d, hk, hd), ("embed", "kv_heads", "head_dim"), dtype=pd),
        "wo": ParamDef((h, hd, d), ("q_heads", "head_dim", "embed"), dtype=pd,
                       init="scaled_normal"),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamDef((h, hd), ("q_heads", "head_dim"), dtype=pd, init="zeros")
        s["bk"] = ParamDef((hk, hd), ("kv_heads", "head_dim"), dtype=pd, init="zeros")
        s["bv"] = ParamDef((hk, hd), ("kv_heads", "head_dim"), dtype=pd, init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = ParamDef((hd,), ("head_dim",), dtype=pd, init="ones")
        s["k_norm"] = ParamDef((hd,), ("head_dim",), dtype=pd, init="ones")
    return s


# ----------------------------------------------------------------------
# Projections
# ----------------------------------------------------------------------

@jax.named_scope("attn_qkv")
def _project_qkv(params, x, cfg: ArchConfig, positions, kv_x=None,
                 rope: bool = True):
    dt = jnp.dtype(cfg.dtype)
    kv_in = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", kv_in, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", kv_in, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": params["k_norm"]}, k, cfg.norm_eps)
    if rope and cfg.position_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions if kv_x is None else
                       jnp.arange(kv_in.shape[1])[None, :], cfg.rope_theta)
    return q, k, v


# ----------------------------------------------------------------------
# Chunked (flash-style) attention
# ----------------------------------------------------------------------

def _mask(qpos, kpos, causal: bool, window: int):
    """(Cq, Ck) additive mask."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return jnp.where(m, 0.0, NEG_INF)


def repeat_kv(k, num_heads: int):
    """GQA -> MHA: repeat kv heads to the full head count.

    KV projections are replicated over the model axis (kv_heads < TP degree on
    most archs), so the repeat shards cleanly over heads with no collective —
    Megatron-style KV duplication. Per-device footprint: H/TP heads.
    """
    Hkv = k.shape[2]
    G = num_heads // Hkv
    if G == 1:
        return k
    return jnp.repeat(k, G, axis=2)


@jax.named_scope("attend")
def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      chunk_q: int = 1024, chunk_k: int = 1024,
                      q_offset: int = 0, scale: Optional[float] = None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd). Online softmax over KV chunks;
    ``scale`` multiplies the scores (default ``hd ** -0.5``).

    Memory is bounded by (B, H, chunk_q, chunk_k) score blocks regardless of
    sequence length — required for the 32k prefill cells. Head dim stays flat
    (no Hkv/G split) so TP over heads shards every intermediate.
    """
    from repro.parallel.context import constrain
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, cq, Sk, ck)
    nq, nk = Sq // cq, Sk // ck
    if scale is None:
        scale = hd ** -0.5

    # pin the chunk stacks to (batch, -, -, heads, -) BEFORE the loops:
    # otherwise XLA spreads the model axis over the chunk dims and every
    # dynamic-slice inside the loops pays a full rematerialization.
    qc = constrain(q.reshape(B, nq, cq, H, hd),
                   "act_batch", None, "act_seq", "act_heads", None)
    kc = constrain(k.reshape(B, nk, ck, H, hd),
                   "act_batch", None, "act_seq", "act_heads", None)
    vc = constrain(v.reshape(B, nk, ck, H, hd),
                   "act_batch", None, "act_seq", "act_heads", None)

    def q_block(iq, qi):
        # qi: (B, cq, H, hd)
        qpos = q_offset + iq * cq + jnp.arange(cq)

        # checkpoint: backward recomputes the (cq, ck) score block from the
        # chunk inputs instead of stashing it per (q, kv) pair — the flash-
        # attention backward trade.
        @jax.checkpoint
        def kv_block(carry, inputs):
            ik, ki, vi = inputs
            acc, m, l = carry
            kpos = ik * ck + jnp.arange(ck)
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            s = s + _mask(qpos, kpos, causal, window)[None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vi.dtype),
                            vi, preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, H, cq, hd), jnp.float32)
        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_block, (acc0, m0, l0),
            (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # cast before stacking: the per-chunk outputs are stacked by lax.map,
        # f32 stacking doubles the buffer for no numeric gain downstream.
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), jnp.moveaxis(qc, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, hd)


# ----------------------------------------------------------------------
# Full layer applications
# ----------------------------------------------------------------------

def attn_apply(params, x, cfg: ArchConfig, *, positions, kv_x=None,
               causal: bool = True, rope: bool = True):
    """Self- or cross-attention over a full sequence (train / prefill)."""
    from repro.parallel.context import constrain, get_context
    ctx = get_context()
    if ctx is not None and kv_x is None:
        mesh, rules = ctx
        model_n = mesh.shape.get("model", 1)
        S = x.shape[1]
        if (model_n > 1 and cfg.num_heads % model_n != 0
                and S % model_n == 0 and S >= model_n):
            # head count not divisible by the model axis (phi4: 24, qwen2.5:
            # 40): head-TP is impossible and XLA falls back to replicated
            # attention with per-block all-reduces (~TiBs of wire). Run
            # sequence-parallel attention under shard_map instead: local q
            # over the seq shard, ONE KV all-gather per layer.
            return _attn_apply_seq_shardmap(params, x, cfg, mesh, rules,
                                            causal=causal, rope=rope)
    dt = jnp.dtype(cfg.dtype)
    # Megatron-SP: gather the sequence-sharded residual stream BEFORE the
    # qkv projections (one cheap bf16 all-gather of (B,S,D)); otherwise the
    # seq-sharded K/V must reshard to head-sharded mid-attention, which XLA
    # SPMD resolves by full rematerialization (a 2 GiB f32 all-gather).
    x = constrain(x, "act_batch", "act_seq", "act_embed")
    if kv_x is not None:
        kv_x = constrain(kv_x, "act_batch", "act_seq", "act_embed")
    q, k, v = _project_qkv(params, x, cfg, positions, kv_x=kv_x, rope=rope)
    window = cfg.window if cfg.attention == "swa" else 0
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
                            scale=cfg.attention_scale)
    with jax.named_scope("attn_out"):
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


def _attn_apply_seq_shardmap(params, x, cfg: ArchConfig, mesh, rules, *,
                             causal: bool, rope: bool):
    """Sequence-parallel self-attention (shard_map).

    Layout: x arrives sequence-sharded over "model" (the block-boundary
    residual layout); each shard projects q/k/v for its seq slice, all-
    gathers K/V over "model" (2 x (B, S, Hkv, hd) bf16 — cheap for GQA),
    and runs the chunked-attention kernel locally with a causal q_offset.
    Weights are FSDP-gathered over "data" just-in-time.
    """
    from repro.parallel.context import suspend_sharding_context
    from repro.parallel.sharding import spec_for_axes
    from jax.sharding import PartitionSpec as P

    dt = jnp.dtype(cfg.dtype)
    B, S, D = x.shape
    model_n = mesh.shape.get("model", 1)
    S_loc = S // model_n
    x_spec = spec_for_axes(("act_batch", "act_seq_blk", "act_embed"),
                           rules, mesh, x.shape)

    names = ["wq", "wk", "wv", "wo"]
    axmap = {"wq": ("embed", "q_heads", "head_dim"),
             "wk": ("embed", "kv_heads", "head_dim"),
             "wv": ("embed", "kv_heads", "head_dim"),
             "wo": ("q_heads", "head_dim", "embed")}
    if cfg.qkv_bias:
        names += ["bq", "bk", "bv"]
        axmap.update(bq=("q_heads", "head_dim"), bk=("kv_heads", "head_dim"),
                     bv=("kv_heads", "head_dim"))
    if cfg.qk_norm:
        names += ["q_norm", "k_norm"]
        axmap.update(q_norm=("head_dim",), k_norm=("head_dim",))
    in_specs = tuple(
        spec_for_axes(axmap[n], rules, mesh, params[n].shape) for n in names
    ) + (x_spec,)

    data_gather = "data" in mesh.axis_names and mesh.shape["data"] > 1

    def body(*args):
        *ws, xb = args
        p = dict(zip(names, ws))
        if data_gather:
            gather_axis = {"wq": 0, "wk": 0, "wv": 0, "wo": 2}
            for n in names:
                ax = gather_axis.get(n)
                if ax is not None and p[n].shape[ax] * mesh.shape["data"] == \
                        {"wq": D, "wk": D, "wv": D, "wo": D}[n]:
                    p[n] = jax.lax.all_gather(p[n], "data", axis=ax,
                                              tiled=True)
        offset = jax.lax.axis_index("model") * S_loc
        pos = (offset + jnp.arange(S_loc))[None, :]
        with suspend_sharding_context():
            q, k_loc, v_loc = _project_qkv(p, xb, cfg, pos, rope=rope)
            k = jax.lax.all_gather(k_loc, "model", axis=1, tiled=True)
            v = jax.lax.all_gather(v_loc, "model", axis=1, tiled=True)
            window = cfg.window if cfg.attention == "swa" else 0
            out = chunked_attention(
                q, k, v, causal=causal, window=window,
                chunk_q=min(cfg.attn_chunk_q, S_loc),
                chunk_k=cfg.attn_chunk_k, q_offset=offset,
                scale=cfg.attention_scale)
            with jax.named_scope("attn_out"):
                return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=x_spec, check_vma=False)(
        *[params[n] for n in names], x)


def _attend_cache(q, k, v, valid, cfg: ArchConfig):
    """q: (B, 1, H, hd) against k, v: (B, S, Hkv, hd) -> (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, 1, Hkv, H // Hkv, hd)
    s = jnp.einsum("bqngd,bsnd->bngqs", qg, k,
                   preferred_element_type=jnp.float32) * cfg.attention_scale
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(jnp.dtype(cfg.dtype))
    o = jnp.einsum("bngqs,bsnd->bqngd", p, v)
    return o.reshape(B, 1, H, hd)


def _seq_rows(t, layer, b):
    """Sequence ``b``'s rows (1, S, Hkv, hd) of layer ``layer`` of a
    stacked cache leaf, sliced where they lie."""
    return jax.lax.dynamic_slice(t, (layer, b) + (0,) * (t.ndim - 2),
                                 (1, 1) + t.shape[2:])[0]


def decode_attn_apply(params, x, cfg: ArchConfig, cache, *, cache_index,
                      layer, cross: bool = False, row_stable: bool = False):
    """One-token decode of layer ``layer`` against the stacked KV cache.

    cache: {"k","v"}: every layer's, (L, B, S_cache, Hkv, hd), as a layer
    scan carries it; ``layer`` is a traced int32 scalar, ``0 <= layer <
    L``. ``cache_index`` is the absolute position of the new token; for
    SWA the cache is a rolling buffer of ``window`` slots.  ``x`` may
    carry padding rows after the cache's B sequences
    (``decode_step(row_stable=True)``); they stay out of the cache.

    The new token's K and V rows are written into the stack in place at
    (``layer``, :, slot), attention reads layer ``layer``'s rows straight
    from the stack, and the stack comes back with only those rows changed.
    ``kernels.ops.kv_row_write`` writes them: one kernel into the tile
    column that holds the slot where the chip keeps the positions in the
    lanes, else one ``dynamic_update_slice`` each. A ``cross`` cache is
    only read.

    ``row_stable`` reads the cache one sequence at a time, so that a
    sequence's scores do not depend on how many others the program holds.
    """
    dt = jnp.dtype(cfg.dtype)
    B, rows = cache["k"].shape[1], x.shape[0]
    pos = jnp.full((rows, 1), cache_index)
    q, k_new, v_new = _project_qkv(params, x, cfg, pos, rope=not cross)
    if row_stable:
        # the barrier keeps XLA from moving the slice below into the
        # projections, which would run them on fewer rows again
        q, k_new, v_new = jax.lax.optimization_barrier((q, k_new, v_new))
    q, k_new, v_new = q[:B], k_new[:B], v_new[:B]
    if cross:
        k, v = cache["k"], cache["v"]
        valid = jnp.ones((k.shape[2],), bool)
    else:
        S = cache["k"].shape[2]
        slot = jnp.mod(cache_index, S) if cfg.attention == "swa" else cache_index
        with jax.named_scope("kv_write"):
            k, v = ops.kv_row_write(cache["k"], cache["v"], k_new[:, 0],
                                    v_new[:, 0], layer, slot)
        cache = {"k": k, "v": v}
        with jax.named_scope("attend"):        # the mask is attention's
            kpos = jnp.arange(S)
            if cfg.attention == "swa":
                valid = jnp.ones((S,), bool)   # rolling buffer: all slots live
            else:
                valid = kpos <= cache_index
    # split-KV (flash-decoding) attention: q is tiny (one token) and stays
    # replicated over the model axis; the cache remains GROUPED (no repeat_kv
    # -- expanding a 32k cache 16x in heads costs GiBs/device) and sequence-
    # sharded, so scores/PV contract over the sharded cache dim and XLA emits
    # the split-KV psum combine.
    with jax.named_scope("attend"):
        if row_stable:
            # the sequence index rides in the carry: scanned from an array,
            # each step would first load it from memory
            def one(b, qb):
                return b + 1, _attend_cache(
                    qb[None], _seq_rows(k, layer, b),
                    _seq_rows(v, layer, b), valid, cfg)[0]
            _, o = jax.lax.scan(one, jnp.int32(0), q)
        else:
            k, v = (jax.lax.dynamic_index_in_dim(t, layer, 0, False)
                    for t in (k, v))
            o = _attend_cache(q, k, v, valid, cfg)
    with jax.named_scope("attn_out"):
        o = jnp.pad(o, ((0, rows - B), (0, 0), (0, 0), (0, 0)))
        out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
    return out, cache


def init_kv_cache(cfg: ArchConfig, batch: int, seq_len: int):
    window = cfg.window if cfg.attention == "swa" else 0
    S = min(seq_len, window) if window else seq_len
    shp = (batch, S, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, cfg.dtype), "v": jnp.zeros(shp, cfg.dtype)}
