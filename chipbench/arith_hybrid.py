"""Operations and bytes of one decode step of an interleaved Mamba-2 /
attention hybrid (``refs/granite_hybrid.py``): the least a step must do,
from the shapes of its configuration.

A Mamba layer reads and writes each sequence's state and conv windows once;
an attention layer reads each sequence's keys and values up to its position
and writes the new row; the weights are read once. Operations: two per
parameter of a matrix product and per sequence, the scores and mix of the
attention layers, and for each Mamba layer and sequence the state's decay
and update (three per element) and its read-out (two).
"""
from __future__ import annotations

from typing import Dict

from chipbench import arith
from chipbench.refs import granite_hybrid as ref

ITEM = {"bfloat16": 2, "float32": 4}


def state_bytes(c: Dict) -> int:
    """One sequence's SSM state over all Mamba layers."""
    _, _, n, h, p = ref.dims(c)
    return ref.counts(c)["mamba"] * h * p * n * ITEM[c["dtype"]["ssm_state"]]


def conv_bytes(c: Dict) -> int:
    """One sequence's conv windows (the last ``d_conv - 1`` inputs of x, B
    and C) over all Mamba layers."""
    _, di, n, _, _ = ref.dims(c)
    return (ref.counts(c)["mamba"] * (c["mamba_d_conv"] - 1) * (di + 2 * n)
            * ITEM[c["dtype"]["cache"]])


def kv_row_bytes(c: Dict) -> int:
    """One position's keys and values over all attention layers."""
    return (2 * ref.counts(c)["attention"] * c["num_key_value_heads"]
            * c["head_dim"] * ITEM[c["dtype"]["cache"]])


def decode_step(c: Dict, layout, batch: int, pos: int) -> Dict[str, float]:
    """Least operations and bytes of one decode step of ``batch`` sequences
    at position ``pos`` (``pos`` cached positions before it)."""
    weight_bytes = arith.param_count(layout) * ITEM[c["dtype"]["weights"]]
    per_seq = (2 * state_bytes(c) + 2 * conv_bytes(c)
               + (pos + 1) * kv_row_bytes(c))
    _, _, n, h, p = ref.dims(c)
    heads = c["num_attention_heads"] * c["head_dim"]
    counts = ref.counts(c)
    flops = batch * (2.0 * arith.matmul_param_count(layout)
                     + 4.0 * (pos + 1) * heads * counts["attention"]
                     + 5.0 * h * p * n * counts["mamba"])
    return {"flops": flops, "bytes": float(weight_bytes + batch * per_seq)}


def decode_least_s(c: Dict, batch: int, pos: int, peaks: Dict) -> float:
    step = decode_step(c, ref.layout(c), batch, pos)
    return arith.least_s(step["flops"], step["bytes"], peaks["flops"],
                         peaks["hbm_bytes_per_s"])
