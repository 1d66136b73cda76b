"""Runs one cell of ``BENCHMARK.json`` once, on the chips this machine holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and ``checks`` last); the last lines of stderr give each number compared
with its limit. Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from chipbench import harness
    sys.exit(harness.main(sys.argv[1:], t0=T0))
