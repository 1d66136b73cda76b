"""Jitted public wrappers for the Pallas kernels.

The kernels compile for the TPU by default. A caller that wants the Pallas
interpreter (tests and CPU runs) passes ``interpret=True``; nothing falls back
to it silently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.kernels.blockcyclic import blockcyclic_repack


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
