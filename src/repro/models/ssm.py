"""Mamba2 SSD (state-space duality) block — chunked scan, pure-JAX reference.

The chunked algorithm follows arXiv:2405.21060 §6: within-chunk quadratic
(duality) term + cross-chunk linear state recurrence, computed under one
``lax.scan`` so the transient (B, Q, Q, H) block is the only quadratic buffer.
The Pallas TPU kernel in ``repro.kernels.ssd_scan`` mirrors this contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.params import ParamDef
from repro.models.layers import rmsnorm


def ssm_schema(cfg: ArchConfig):
    assert cfg.ssm is not None
    d = cfg.d_model
    di = cfg.ssm_d_inner
    n = cfg.ssm.state_size
    h = cfg.ssm_num_heads
    w = cfg.ssm.conv_width
    pd = cfg.param_dtype
    s = {
        "w_z": ParamDef((d, di), ("embed", "ssm_inner"), dtype=pd),
        "w_x": ParamDef((d, di), ("embed", "ssm_inner"), dtype=pd),
        "w_B": ParamDef((d, n), ("embed", "ssm_state"), dtype=pd),
        "w_C": ParamDef((d, n), ("embed", "ssm_state"), dtype=pd),
        "w_dt": ParamDef((d, h), ("embed", "ssm_heads"), dtype=pd),
        "conv_x": ParamDef((w, di), (None, "ssm_inner"), dtype=pd, scale=0.5),
        "conv_B": ParamDef((w, n), (None, "ssm_state"), dtype=pd, scale=0.5),
        "conv_C": ParamDef((w, n), (None, "ssm_state"), dtype=pd, scale=0.5),
        "dt_bias": ParamDef((h,), ("ssm_heads",), dtype=pd, init="zeros"),
        "A_log": ParamDef((h,), ("ssm_heads",), dtype=pd, init="small_a_log"),
        "D_skip": ParamDef((h,), ("ssm_heads",), dtype=pd, init="ones"),
        "norm": ParamDef((di,), ("ssm_inner",), dtype=pd, init="ones"),
        "w_out": ParamDef((di, d), ("ssm_inner", "embed"), dtype=pd,
                          init="scaled_normal"),
    }
    if cfg.ssm.conv_bias:
        s["conv_x_bias"] = ParamDef((di,), ("ssm_inner",), dtype=pd,
                                    init="zeros")
        s["conv_B_bias"] = ParamDef((n,), ("ssm_state",), dtype=pd,
                                    init="zeros")
        s["conv_C_bias"] = ParamDef((n,), ("ssm_state",), dtype=pd,
                                    init="zeros")
    return s


def _causal_conv(x, w, state=None, bias=None):
    """Depthwise causal conv. x: (B,S,C); w: (W,C); state: (B,W-1,C) or
    None; bias: (C,) or None."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)               # (B, S+W-1, C)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(W))
    if bias is not None:
        out = out + bias
    new_state = xp[:, -(W - 1):, :]
    return out, new_state


def _conv_bias(params, name, dt_):
    b = params.get(f"conv_{name}_bias")
    return None if b is None else b.astype(dt_)


@jax.named_scope("ssd_scan")
def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, state0=None):
    """SSD sequence transform.

    x:  (B, S, H, P) inputs (already multiplied by nothing; dt applied here)
    dt: (B, S, H)    positive step sizes
    A:  (H,)         negative decay rates
    Bm, Cm: (B, S, N) input/output mixers (shared across heads)
    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    a = (dt * A[None, None, :]).astype(jnp.float32)        # (B,S,H) negative
    xdt = (x * dt[..., None]).astype(x.dtype)              # (B,S,H,P)

    def to_chunks(t):
        return jnp.moveaxis(t.reshape(Bsz, nc, Q, *t.shape[2:]), 1, 0)

    xs, dts, As, Bs, Cs = map(to_chunks, (xdt, dt, a, Bm, Cm))

    causal = jnp.tril(jnp.ones((Q, Q), bool))

    if state0 is None:
        state0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    @jax.checkpoint
    def chunk_step(state, inputs):
        xc, ac, bc, cc = inputs                            # (B,Q,H,P),(B,Q,H),(B,Q,N)x2
        cum = jnp.cumsum(ac, axis=1)                       # (B,Q,H)
        # within-chunk duality term
        G = jnp.einsum("bqn,bsn->bqs", cc.astype(jnp.float32),
                       bc.astype(jnp.float32))             # (B,Q,Q)
        # mask the exponent (not the output) so masked entries never reach
        # exp-overflow — inf would poison the backward pass via inf * 0.
        diff = cum[:, :, None, :] - cum[:, None, :, :]         # (B,Q,Q,H)
        diff = jnp.where(causal[None, :, :, None], diff, -jnp.inf)
        L = jnp.exp(diff)
        M = G[..., None] * L                               # (B,Q,Q,H)
        y_diag = jnp.einsum("bqsh,bshp->bqhp", M, xc.astype(jnp.float32))
        # incoming-state term
        y_off = jnp.einsum("bqn,bhpn,bqh->bqhp", cc.astype(jnp.float32),
                           state, jnp.exp(cum))
        # state update
        total = cum[:, -1, :]                              # (B,H)
        decay_end = jnp.exp(total[:, None, :] - cum)       # (B,Q,H)
        state_new = state * jnp.exp(total)[:, :, None, None] + jnp.einsum(
            "bqn,bqhp,bqh->bhpn", bc.astype(jnp.float32),
            xc.astype(jnp.float32), decay_end)
        return state_new, (y_diag + y_off)

    state, ys = jax.lax.scan(chunk_step, state0, (xs, As, Bs, Cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, P)
    return y.astype(x.dtype), state


def ssm_apply(params, x, cfg: ArchConfig, cache=None):
    """Full Mamba2 block. x: (B,S,D). cache: None (train/prefill from zero)."""
    from repro.parallel.context import constrain
    s = cfg.ssm
    dt_ = jnp.dtype(cfg.dtype)
    H, P, N = cfg.ssm_num_heads, s.head_dim, s.state_size
    B_, S, D = x.shape
    # the SSD scan and conv mix over seq: gather the sequence-sharded stream
    # here (cheap bf16 all-gather), compute head-sharded.
    x = constrain(x, "act_batch", "act_seq", "act_embed")

    z = jnp.einsum("bsd,de->bse", x, params["w_z"].astype(dt_))
    xs = jnp.einsum("bsd,de->bse", x, params["w_x"].astype(dt_))
    Bm = jnp.einsum("bsd,dn->bsn", x, params["w_B"].astype(dt_))
    Cm = jnp.einsum("bsd,dn->bsn", x, params["w_C"].astype(dt_))
    dt = jnp.einsum("bsd,dh->bsh", x, params["w_dt"].astype(dt_))

    xs, _ = _causal_conv(xs, params["conv_x"].astype(dt_),
                         bias=_conv_bias(params, "x", dt_))
    Bm, _ = _causal_conv(Bm, params["conv_B"].astype(dt_),
                         bias=_conv_bias(params, "B", dt_))
    Cm, _ = _causal_conv(Cm, params["conv_C"].astype(dt_),
                         bias=_conv_bias(params, "C", dt_))
    xs, Bm, Cm = jax.nn.silu(xs), jax.nn.silu(Bm), jax.nn.silu(Cm)

    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    xh = xs.reshape(B_, S, H, P)
    y, _ = ssd_chunked(xh, dt, A, Bm, Cm, chunk=s.chunk_size)
    y = y + xh * params["D_skip"].astype(dt_)[None, None, :, None]
    y = y.reshape(B_, S, H * P)
    y = y * jax.nn.silu(z)
    y = rmsnorm({"scale": params["norm"]}, y, cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, params["w_out"].astype(dt_))


# ----------------------------------------------------------------------
# Decode path (single-token recurrence; the SSM analogue of a KV cache)
# ----------------------------------------------------------------------

def init_ssm_cache(cfg: ArchConfig, batch: int):
    s = cfg.ssm
    H, P, N = cfg.ssm_num_heads, s.head_dim, s.state_size
    W = s.conv_width
    return {
        "state": jnp.zeros((batch, H, P, N), s.state_dtype),
        "conv_x": jnp.zeros((batch, W - 1, cfg.ssm_d_inner), cfg.dtype),
        "conv_B": jnp.zeros((batch, W - 1, N), cfg.dtype),
        "conv_C": jnp.zeros((batch, W - 1, N), cfg.dtype),
    }


def _window(stack, layer, fresh):
    """Layer ``layer``'s conv window, zeros where the sequences begin."""
    t = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    return jnp.where(fresh, jnp.zeros_like(t), t)


def ssm_decode_step(params, x, cfg: ArchConfig, cache, cache_index, layer):
    """x: (B, 1, D) at position ``cache_index`` -> (y (B,1,D), new cache).

    The cache leaves are the stack of every Mamba layer's, (L, B, ...),
    that a layer scan carries, and ``layer`` (a traced int32 scalar) is
    this one's index in it: the layer's conv windows and state are read
    from it at ``layer`` and its new ones written back there in place, so
    a donated stack is updated where it lies.

    At position 0 the batch's sequences begin: no state or conv window is
    carried in from the slot's last sequence (KV needs no such reset, its
    positions are masked).

    The state's decay, update and read-out are one pass over the layer's
    state (``repro.kernels.ssm_state``): it is read once, written in place
    once and read out while it is in fast memory, in float32.

    Named scopes: ``ssm_in`` the input projections, ``ssm_conv`` the
    convolution and its window, ``ssm_state`` the state's one pass,
    ``ssm_out`` the output projection.
    """
    s = cfg.ssm
    dt_ = jnp.dtype(cfg.dtype)
    H, P, N = cfg.ssm_num_heads, s.head_dim, s.state_size
    B_ = x.shape[0]
    fresh = cache_index == 0

    with jax.named_scope("ssm_in"):
        z = jnp.einsum("bsd,de->bse", x, params["w_z"].astype(dt_))
        xs = jnp.einsum("bsd,de->bse", x, params["w_x"].astype(dt_))
        Bm = jnp.einsum("bsd,dn->bsn", x, params["w_B"].astype(dt_))
        Cm = jnp.einsum("bsd,dn->bsn", x, params["w_C"].astype(dt_))
        dt = jnp.einsum("bsd,dh->bsh", x, params["w_dt"].astype(dt_))

    new_cache = {}
    with jax.named_scope("ssm_conv"):
        out = []
        for name, t in (("x", xs), ("B", Bm), ("C", Cm)):
            key = f"conv_{name}"
            t, window = _causal_conv(
                t, params[key].astype(dt_), _window(cache[key], layer, fresh),
                bias=_conv_bias(params, name, dt_))
            new_cache[key] = jax.lax.dynamic_update_index_in_dim(
                cache[key], window.astype(cache[key].dtype), layer, 0)
            out.append(jax.nn.silu(t))
        xs, Bm, Cm = out

    with jax.named_scope("ssm_state"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             params["dt_bias"].astype(jnp.float32))[:, 0]
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        a = jnp.exp(dt * A[None, :])                                # (B,H)

        xh = xs.reshape(B_, H, P).astype(jnp.float32)
        # one pass over the layer's state: read once, written in place
        # once, read out against C while it is in fast memory
        new_cache["state"], y = ops.ssm_state_update(
            cache["state"], layer, fresh, a, xh * dt[..., None], Bm[:, 0],
            Cm[:, 0], interpret=ops.interpret_here())
        y = y + xh * params["D_skip"].astype(jnp.float32)[None, :, None]
        y = y.reshape(B_, 1, H * P).astype(dt_)
        y = y * jax.nn.silu(z)
    y = rmsnorm({"scale": params["norm"]}, y, cfg.norm_eps)
    with jax.named_scope("ssm_out"):
        out = jnp.einsum("bse,ed->bsd", y, params["w_out"].astype(dt_))
    return out, new_cache
