"""Operations and bytes a step needs, from the shapes of its configuration:
the arithmetic behind every utilization and roofline share.

Counts are of what the algorithm needs, not of what a program happens to
execute: recomputed operations do not count, and bytes are those that have
to cross HBM at least once.
"""
from __future__ import annotations

import math
from typing import Dict

import jax

from chipbench import weights as W


def param_count(layout) -> int:
    return sum(math.prod(l.full_shape)
               for l in jax.tree.leaves(layout, is_leaf=W.is_leaf))


def matmul_param_count(layout) -> int:
    """Parameters that enter a matrix product (two or more axes per layer)."""
    return sum(math.prod(l.full_shape)
               for l in jax.tree.leaves(layout, is_leaf=W.is_leaf)
               if len(l.shape) >= 2)


# ----------------------------------------------------------------------
# Mamba-2 training
# ----------------------------------------------------------------------

def ssd_flops_per_token(c: Dict) -> float:
    """Forward operations per token of one layer's SSD transform beyond its
    parameters' products (chunked form): ``C B^T`` and the masked mix inside
    a chunk (2QN + 2QHP), the chunk's state and its read-out (2 x 2HPN)."""
    q, n = c["chunk_size"], c["d_state"]
    di = c["expand"] * c["d_model"]           # H * P
    return 2.0 * q * n + 2.0 * q * di + 4.0 * di * n


def train_flops(c: Dict, layout, tokens: int) -> Dict[str, float]:
    """Model operations of one training step over ``tokens`` tokens:
    ``6 N T`` for the parameters' products forward and backward (the tied
    table counts once, as the output head; its lookup is free) and three
    times the SSD's forward operations."""
    params = 6.0 * param_count(layout) * tokens
    ssd = 3.0 * ssd_flops_per_token(c) * c["n_layer"] * tokens
    return {"params": params, "ssd": ssd, "total": params + ssd}


# ----------------------------------------------------------------------
# dense GQA decode
# ----------------------------------------------------------------------

def kv_row_bytes(c: Dict) -> int:
    """Bytes of one position's keys and values over all layers."""
    item = 2 if c["dtype"]["cache"] == "bfloat16" else 4
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * item)


def decode_step(c: Dict, layout, batch: int, pos: int) -> Dict[str, float]:
    """Least operations and bytes of one decode step of ``batch`` sequences
    at position ``pos`` (``pos`` cached positions before it): the weights
    read once, each sequence's cache up to its position, the new row
    written; two operations per parameter product and per sequence, and the
    scores and mix over ``pos + 1`` positions."""
    w_item = 2 if c["dtype"]["weights"] == "bfloat16" else 4
    weight_bytes = param_count(layout) * w_item
    cache_bytes = batch * (pos + 1) * kv_row_bytes(c)
    heads = c["num_attention_heads"] * c["head_dim"]
    flops = batch * (2.0 * matmul_param_count(layout)
                     + 4.0 * (pos + 1) * heads * c["num_hidden_layers"])
    return {"flops": flops, "bytes": float(weight_bytes + cache_bytes)}


def leaf_norms(tree):
    """The float32 norm of every leaf of ``tree``, in flattening order."""
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


def least_s(flops: float, nbytes: float, peak_flops: float,
            peak_bytes_per_s: float) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
