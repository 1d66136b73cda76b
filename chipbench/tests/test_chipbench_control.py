"""The controls at a size a test run holds: the reference computed in int8,
put in the program's place, reads wider than the program does and fails the
cell's comparison, which the float32 program at this size passes. At the
cells' own size the same readings come from ``chipbench/calibrate.py`` on
the chip (``PERF.md``)."""
import time

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.kinds import common
from chipbench.peaks import PEAKS
from chipbench.tests import tiny


def _ctx(name, seed):
    import dataclasses
    cell = tiny.cell(name)
    if name == "granite-decode-chat":
        # at the tiny width int8 rounding seldom moves the first token; at
        # 512 it does on most seeds (at 2048 and 4 layers: a gap of 0.24 to
        # 0.26 against the bf16 program's 0.026 to 0.027, CPU)
        d, v = 512, 8192
        cell.config.update(hidden_size=d, intermediate_size=4 * d,
                           vocab_size=v, program={
                               "registry": "granite-3-2b-smoke",
                               "overrides": {"d_model": d, "d_ff": 4 * d,
                                             "vocab_size": v}})
        cell.traffic.update(gen_len=16, cache_len=24)
    return harness.Context(cell, seed, 0.0, False,
                           jax.devices()[:1], time.perf_counter(),
                           dataclasses.asdict(PEAKS["TPU v5 lite"]),
                           log=lambda rec: None)


@pytest.mark.parametrize("seed", [1, 4])
def test_decode_control_fails_where_the_program_passes(seed):
    ctx = _ctx("granite-decode-chat", seed)
    kind = harness.load_module("kinds", ctx.traffic["kind"])
    ref = common.reference(ctx.config)
    out = kind.run(ctx)
    program = out.checks[0]
    control = kind.check(ctx, ref, out.record["served"], control=True)[0]
    assert program.ok
    assert not control.ok
    assert control.value > 3 * max(program.value, 1e-6)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_training_control_and_faults_fail(seed):
    ctx = _ctx("mamba2-train-resize", seed)
    kind = harness.load_module("kinds", ctx.traffic["kind"])
    ref = common.reference(ctx.config)
    limits = ctx.config["limits"]
    base = kind.reference_readings(ctx, ref)
    again = kind.readings(kind.reference_readings(ctx, ref), base)
    assert all(v == 0.0 for v in again.values())     # the reference repeats
    d_inner = ctx.config["expand"] * ctx.config["d_model"]
    for fault in ({"quant": "int8"},
                  {"rows_used": ctx.traffic["global_batch"] // 2},
                  {"keep": d_inner // 2}):
        got = kind.readings(kind.reference_readings(ctx, ref, **fault), base)
        assert any(v > limits[k] for k, v in got.items()), (fault, got)
        assert np.isfinite(list(got.values())).all()
