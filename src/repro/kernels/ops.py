"""Jitted public wrappers for the Pallas kernels.

The kernels compile for the TPU by default. A caller that wants the Pallas
interpreter (tests and CPU runs) passes ``interpret=True``. The model's
decode step asks :func:`interpret_here` instead, the one place that decides
from the backend: the kernel compiles on a TPU and is interpreted anywhere
else.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.kernels.blockcyclic import blockcyclic_repack
from repro.kernels.ssm_state import ssm_state_update_fwd
from repro.kernels.kv_row import kv_row_write_fwd, lane_major
from repro.parallel.context import get_context


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


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_row_kernel(k, v, k_new, v_new, layer, slot, *, interpret: bool):
    return kv_row_write_fwd(k, v, k_new, v_new, layer, slot,
                            interpret=interpret)


def kv_row_write(k, v, k_new, v_new, layer, slot, *,
                 interpret: Optional[bool] = None):
    """One position's K and V rows written into layer ``layer`` of the
    stacked cache. ``k``, ``v`` (L, B, S, Hkv, hd), ``k_new``, ``v_new``
    (B, Hkv, hd) -> the two stacks, changed at (layer, :, slot) alone.

    Where the chip keeps the stacks with the positions in the lanes
    (``kv_row.lane_major``) and the step runs on one device's rows (no
    active sharding context: a single-device step, or a replica's
    shard_map), the ``kv_row_write`` kernel writes both rows into the tile
    column that holds the slot; ``interpret`` None asks
    :func:`interpret_here`. Elsewhere one ``dynamic_update_slice`` each
    writes them: the kernel has no partitioning rule, so a step that GSPMD
    partitions keeps XLA's write."""
    if lane_major(k.shape) and get_context() is None:
        if interpret is None:
            interpret = interpret_here()
        return _kv_row_kernel(k, v, k_new, v_new, layer, slot,
                              interpret=interpret)
    at = (layer, 0, slot, 0, 0)
    return tuple(jax.lax.dynamic_update_slice(
        t, new[None, :, None].astype(t.dtype), at)
        for t, new in ((k, k_new), (v, v_new)))
