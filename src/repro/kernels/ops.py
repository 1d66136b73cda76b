"""Jitted public wrappers for the Pallas kernels.

The kernels compile for the TPU by default. A caller that wants the Pallas
interpreter (tests and CPU runs) passes ``interpret=True``. The model's
decode step asks :func:`interpret_here` instead, the one place that decides
from the backend: the kernel compiles on a TPU and is interpreted anywhere
else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.kernels.blockcyclic import blockcyclic_repack
from repro.kernels.ssm_state import ssm_state_update_fwd


def interpret_here() -> bool:
    """Whether a kernel the model calls runs in the Pallas interpreter: on
    every backend but the TPU."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, H, Sq, D)."""
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xdt, a, bm, cm, *, chunk: int = 256, interpret: bool = False):
    """SSD transform; see repro.kernels.ssd_scan."""
    return ssd_scan_fwd(xdt, a, bm, cm, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def repack(src, idx, *, interpret: bool = False):
    """Block gather: out[i] = src[idx[i]] (block-cyclic redistribution)."""
    return blockcyclic_repack(src, idx, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_update(state, layer, fresh, a, xdt, bm, cm, *,
                     interpret: bool = False):
    """One pass over a Mamba-2 layer's decode state; see
    repro.kernels.ssm_state. ``state`` (L, B, H, P, N) -> (the stack with
    layer ``layer`` updated, y (B, H, P) float32)."""
    return ssm_state_update_fwd(state, layer, fresh, a, xdt, bm, cm,
                                interpret=interpret)
