"""The hybrid cell in small for the CPU tests, beside ``tiny.py``'s:
``granite4h-decode-chat`` at two periods of its layer pattern and small
widths, float32.

    python -m chipbench.tests.tiny_hybrid [fault]

runs it on the CPU and prints the run's logged records, then the result
object.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
import time

from chipbench import harness
from chipbench.tests import tiny

HYBRID = "granite4h-decode-chat"


def cell(name: str) -> harness.Cell:
    if name != HYBRID:
        raise KeyError(name)
    whole = tiny.full(name)
    c, t = copy.deepcopy(whole.config), copy.deepcopy(whole.traffic)
    period = c["layer_types"][:10]
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=128, shared_intermediate_size=128,
             vocab_size=256, mamba_d_state=16, mamba_d_head=16,
             mamba_n_heads=8, mamba_chunk_size=32, num_hidden_layers=20,
             layer_types=period * 2,
             dtype={"weights": "float32", "compute": "float32",
                    "cache": "float32", "ssm_state": "float32"},
             program={"registry": "granite-4.0-h-micro-smoke",
                      "overrides": {"num_layers": 20,
                                    "mixer_period": tuple(period)}})
    # float32 throughout against the float32 reference: what is left is
    # the order of the sums (the chunked SSD against the recurrence)
    c["limits"] = {"max_logit_gap": 1e-3, "mean_logit_gap": 1e-4}
    t.update(batch=4, cache_len=16, prompt_len=8, gen_len=8, warm_steps=2,
             trace_steps=[6, 10], check_sequences=3)
    return harness.Cell(name=whole.name, chips=whole.chips, config=c,
                        traffic=t, end_to_end=whole.end_to_end,
                        per_layer=whole.per_layer)


def run(name: str, seed: int = 7, trace: bool = False, devices=None,
        log=None):
    """One run of a tiny cell on the CPU; returns the result object."""
    import jax
    from chipbench.peaks import PEAKS
    small = cell(name)
    devices = devices or jax.devices()[:small.chips]
    # the CPU has no published peaks: the arithmetic is fed a v5e's, and
    # nothing a test reads from it is a device number
    peaks = dataclasses.asdict(PEAKS["TPU v5 lite"])
    return harness.run_cell(small, seed, 0.0, trace, devices,
                            time.perf_counter(), peaks,
                            log=log or (lambda rec: None))


def main(argv):
    from chipbench.tests import faults
    fault = argv[0] if argv else "none"
    logged = []
    if fault == "none":
        result = run(HYBRID, log=logged.append)
    else:
        with faults.FAULTS[fault]():
            result = run(HYBRID, log=logged.append)
    print(json.dumps(logged))
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
