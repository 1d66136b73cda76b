"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def attention_reference(q, k, v, *, causal: bool = True, window: int = 0):
    """Naive softmax attention. q: (B,H,Sq,D); k,v: (B,Hkv,Sk,D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_reference(xdt, a, bm, cm):
    """Sequential (per-token) SSD recurrence — obviously-correct oracle.

    xdt: (B,H,S,P) pre-multiplied by dt; a: (B,H,S); bm, cm: (B,S,N).
    state_t = state_{t-1} * exp(a_t) + xdt_t (outer) B_t;  y_t = state_t @ C_t
    """
    B, H, S, P = xdt.shape
    N = bm.shape[-1]

    def step(state, t):
        xa, aa, bb, cc = t
        state = state * jnp.exp(aa)[..., None, None] + \
            jnp.einsum("bhp,bn->bhpn", xa, bb)
        y = jnp.einsum("bhpn,bn->bhp", state, cc)
        return state, y

    xs = (jnp.moveaxis(xdt.astype(jnp.float32), 2, 0),
          jnp.moveaxis(a.astype(jnp.float32), 2, 0),
          jnp.moveaxis(bm.astype(jnp.float32), 1, 0),
          jnp.moveaxis(cm.astype(jnp.float32), 1, 0))
    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 2).astype(xdt.dtype)    # (B,H,S,P)


def ssm_state_update_reference(state, layer, fresh, a, xdt, bm, cm):
    """One token's Mamba-2 state update and read-out as XLA ops: the update
    written into the stack at ``layer``, then read back for the read-out.

    state: (L, B, H, P, N); a: (B, H); xdt: (B, H, P); bm, cm: (B, N).
    h' = h * a + xdt (outer) B, h taken as zero where ``fresh``;
    y = sum_n h' * C. Returns (the stack, y (B, H, P) float32)."""
    f32 = jnp.float32
    h = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    h = jnp.where(fresh, jnp.zeros_like(h), h)
    new = h * a.astype(f32)[:, :, None, None] \
        + xdt.astype(f32)[..., None] * bm.astype(f32)[:, None, None, :]
    state = jax.lax.dynamic_update_index_in_dim(state, new.astype(state.dtype),
                                                layer, 0)
    new = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    y = jnp.sum(new.astype(f32) * cm.astype(f32)[:, None, None, :], axis=-1)
    return state, y


def kv_row_write_reference(k, v, k_new, v_new, layer, slot):
    """The rows k_new, v_new (B, Hkv, hd) written into the stacks k, v
    (L, B, S, Hkv, hd) at (layer, :, slot)."""
    return (k.at[layer, :, slot].set(k_new.astype(k.dtype)),
            v.at[layer, :, slot].set(v_new.astype(v.dtype)))


def repack_reference(src, idx):
    """out[i] = src[idx[i]]."""
    return src[idx]
