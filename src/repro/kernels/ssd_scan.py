"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid (B, H, nc): the chunk axis iterates sequentially, carrying the SSM state
(P, N) in VMEM scratch — the cross-chunk recurrence lives entirely on-chip.
Per-chunk compute is the SSD duality: within-chunk quadratic (Q, Q) term plus
the incoming-state contribution. B/C mixers are shared across heads, so their
BlockSpec index maps ignore h (no replication in HBM).

VMEM working set at (Q, P, N) = (256, 64, 128): x(Q,P) + B/C(Q,N) + L(Q,Q) +
state(P,N) + out(Q,P) in f32 ~= 1.1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)  — already x*dt
    a = a_ref[0, 0].astype(jnp.float32)          # (1, Q)  — dt * A (negative)
    bm = b_ref[0].astype(jnp.float32)            # (Q, N)
    cm = c_ref[0].astype(jnp.float32)            # (Q, N)

    # cumulative decay as a column and as a row, both from the lane-major
    # ``a``: a masked lane reduction and a matmul with the upper triangle
    # (Pallas has no TPU lowering for cumsum)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col
    a_b = jnp.broadcast_to(a, (chunk, chunk))    # a_b[i, k] = a[k]
    cum_c = jnp.sum(jnp.where(causal, a_b, 0.0), axis=1,
                    keepdims=True)               # (Q, 1): cum[i]
    cum_r = jax.lax.dot_general(
        a_b[:8], jnp.where(row <= col, 1.0, 0.0),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[:1]  # (1, Q): cum[j]
    # within-chunk duality term
    g = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    l = jnp.exp(jnp.where(causal, cum_c - cum_r, -jnp.inf))
    y = jax.lax.dot_general(g * l, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)
    # incoming-state term: y_off[q] = exp(cum[q]) * C[q] @ state^T
    state = state_ref[...]                       # (P, N)
    y_off = jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + y_off * jnp.exp(cum_c)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: state' = state * exp(total) + sum_q decay_q * x[q] (x) B[q]
    total = jnp.sum(a, axis=1, keepdims=True)    # (1, 1)
    xw = x * jnp.exp(total - cum_c)              # (Q, P)
    state_ref[...] = state * jnp.exp(total) + jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (P, N)


def ssd_scan_fwd(xdt, a, bm, cm, *, chunk: int = 256,
                 interpret: bool = False):
    """SSD sequence transform.

    xdt: (B, H, S, P) inputs pre-multiplied by dt
    a:   (B, H, S)    dt * A (negative decay exponents)
    bm, cm: (B, S, N) shared input/output mixers
    Returns y: (B, H, S, P).
    """
    B, H, S, P = xdt.shape
    N = bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    kernel = functools.partial(_ssd_kernel, chunk=Q)
    # ``a`` rides lane-major, (B, H, 1, S): a (1, Q) block of it meets the
    # chip's tiling rule, where a (1, 1, Q) block of (B, H, S) does not
    a = a.reshape(B, H, 1, S)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),   # h-shared
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),   # h-shared
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, a, bm, cm)
