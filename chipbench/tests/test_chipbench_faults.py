"""Each cell's comparison, driven end to end on the CPU at a tiny size with
the harness's look for a chip skipped: a sound run comes out correct, and a
run with a fault planted in the timed path comes out not correct — once for
each fault the cell can have. The resize cell runs on four host devices in a
child process (the device count is fixed when JAX starts)."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests import faults, tiny


def _checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


@pytest.mark.parametrize("fault", ["none", "state_unchanged",
                                   "token_altered"])
def test_decode_cell(fault):
    if fault == "none":
        result = tiny.run("granite-decode-chat")
        assert result["correct"], _checks(result)
    else:
        with faults.FAULTS[fault]():
            result = tiny.run("granite-decode-chat")
        assert not result["correct"], _checks(result)
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch",
                                   "no_exchange"])
def test_resize_cell(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.faults",
         "mamba2-train-resize", fault],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] == (fault == "none"), _checks(result)
    assert result["attempted"] == 8      # one cycle
