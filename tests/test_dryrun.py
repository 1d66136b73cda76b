"""Dry-run harness smoke (512 host devices, child interpreter): one train
cell and one decode cell compile on the single-pod mesh, the decode cell
with XLA's K/V row write; a long_500k cell on a quadratic arch is skipped
with the documented reason; HLO analysis fields populate."""
import json

from tests.util import run_devices

SCRIPT = r"""
import warnings; warnings.filterwarnings("ignore")
import json, re
import repro.launch.dryrun as D
from repro.launch.dryrun import run_cell

programs = {}
lower_cell = D.lower_cell


def keep_program(arch, shape, *args, **kwargs):
    out = lower_cell(arch, shape, *args, **kwargs)
    programs[arch, shape] = out[0].as_text()
    return out


D.lower_cell = keep_program

r1 = run_cell("granite-3-2b", "train_4k", multi_pod=False, verbose=False)
assert r1["status"] == "ok", r1
assert r1["memory"]["fits_16gib"], r1["memory"]
assert r1["hlo"]["dot_flops"] > 1e12
assert r1["roofline"]["bottleneck"] in ("compute", "memory", "collective")
assert 0 < r1["roofline"]["mfu"] <= 1

r2 = run_cell("seamless-m4t-medium", "decode_32k", multi_pod=False,
              verbose=False)
assert r2["status"] == "ok", r2.get("error", "")
# GSPMD partitions this step (the cache's positions sharded over "model"):
# the K/V rows are XLA's dynamic-update-slice, not the kv_row_write kernel,
# which has no partitioning rule
assert not re.search(r'op_name="[^"]*kv_row_write',
                     programs["seamless-m4t-medium", "decode_32k"])

r3 = run_cell("granite-3-2b", "long_500k", multi_pod=False, verbose=False)
assert r3["status"] == "skipped" and "quadratic" in r3["reason"]
print("DRYRUN_OK", json.dumps({"mfu": r1["roofline"]["mfu"]}))
"""


def test_dryrun_cells():
    out = run_devices(SCRIPT, n_devices=512, timeout=560)
    assert "DRYRUN_OK" in out
