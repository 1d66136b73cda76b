"""Plain float32 operations the references share, and the lower-precision
matrix product their controls put in its place.

``precision`` of every product is ``HIGHEST``: on a TPU a float32 product
otherwise runs in bfloat16 passes.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _int8(t, axis: int):
    """Symmetric int8 codes of ``t`` and their scales, one scale per slice
    along ``axis`` (the contraction axis)."""
    s = jnp.max(jnp.abs(t), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(t / s), -127, 127).astype(jnp.int8), s


def matmul(x, w, quant: Optional[str] = None):
    """``x @ w`` over the last axis of ``x`` and the first of ``w`` (2-D).

    ``quant="int8"``: both operands in int8 (a scale per row of ``x`` and per
    column of ``w``), products summed exactly in int32, the control of a
    bfloat16 configuration. Its gradient passes straight through the
    rounding, as int8 training does.
    """
    if quant is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    if quant != "int8":
        raise ValueError(f"unknown quant {quant!r}")
    xq, sx = _int8(x, -1)
    wq, sw = _int8(w, 0)
    y = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
    y = y.astype(jnp.float32) * sx * sw
    exact = jnp.matmul(x, w, precision=HIGHEST)
    return exact + jax.lax.stop_gradient(y - exact)


def rope(x, positions, theta: float):
    """Rotary embedding, rotating the two halves of each head (the HF Llama
    convention). x: (..., S, H, hd); positions: (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv        # (S, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
