"""Tiny cells for the CPU tests: the cells of ``BENCHMARK.json`` and of
``pending.json`` cut to a size a test run holds, run through the same
harness and kinds."""
from __future__ import annotations

import copy

from chipbench import harness


def full(name: str) -> harness.Cell:
    """The cell ``name`` as its files give it, admitted or pending."""
    return harness.resolve_cell(harness.load_bench(pending=True), name)


def cell(name: str) -> harness.Cell:
    """The cell ``name`` at a tiny size, float32."""
    whole = full(name)
    c, t = copy.deepcopy(whole.config), copy.deepcopy(whole.traffic)
    if c["arch"] == "dense_gqa":
        c.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, intermediate_size=128,
                 vocab_size=256,
                 dtype={"weights": "float32", "compute": "float32",
                        "cache": "float32"},
                 program={"registry": "granite-3-2b-smoke"})
        c["limits"] = {"max_logit_gap": 1e-3}
        t.update(batch=4, cache_len=16, prompt_len=8, gen_len=8, warm_steps=2,
                 trace_steps=[6, 10], check_sequences=3)
    elif c["arch"] == "mamba2":
        c.update(n_layer=2, d_model=64, vocab_size=256, d_state=16,
                 headdim=16, chunk_size=32,
                 dtype={"params": "float32", "compute": "float32",
                        "optimizer_moments": "float32"},
                 program={"registry": "mamba2-370m-smoke"})
        c["limits"] = {"loss_gap": 1e-4, "grad_gap": 1e-3,
                       "change_gap": 1e-3}
        t.update(global_batch=4, seq_len=64, reference_rows=2)
    else:
        raise KeyError(c["arch"])
    return harness.Cell(name=whole.name, chips=whole.chips, config=c,
                        traffic=t, end_to_end=whole.end_to_end,
                        per_layer=whole.per_layer)


def run(name: str, seed: int = 7, seconds: float = 0.0, trace: bool = False,
        devices=None, trace_dir=None):
    """One run of a tiny cell on the CPU; returns the result object."""
    import dataclasses
    import time

    import jax
    from chipbench.peaks import PEAKS
    devices = devices or jax.devices()[:cell(name).chips]
    # the CPU has no published peaks: the arithmetic is fed a v5e's, and
    # nothing a test reads from it is a device number
    peaks = dataclasses.asdict(PEAKS["TPU v5 lite"])
    return harness.run_cell(cell(name), seed, seconds, trace, devices,
                            time.perf_counter(), peaks, log=lambda rec: None,
                            trace_dir=trace_dir)
