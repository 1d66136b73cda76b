"""Benchmark harness — one entry per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV rows; full tables land in
``experiments/bench/*.csv``. Run: ``PYTHONPATH=src python -m benchmarks.run``.
"""
from __future__ import annotations

import sys
import traceback

from benchmarks.common import force_host_devices

from benchmarks import (allocation_rate, energy, fault_tolerance,
                        kernels_bench, live_cluster, mixed_pool,
                        partial_malleability, per_job_times,
                        redistribution_overhead, scaling_study,
                        scenario_suite, serving, submission_modes,
                        tpu_lm_workload, trace_replay, usability_sloc,
                        workload_evolution, workload_speedup)

BENCHES = [
    ("fig3", scaling_study),
    ("fig4", workload_speedup),
    ("fig5", workload_evolution),
    ("fig6_7", per_job_times),
    ("fig8", submission_modes),
    ("fig9", allocation_rate),
    ("table7", partial_malleability),
    ("fig10", energy),
    ("table2", usability_sloc),
    ("redistribution", redistribution_overhead),
    ("kernels", kernels_bench),
    ("tpu_lm", tpu_lm_workload),
    ("straggler", fault_tolerance),
    ("scenarios", scenario_suite),
    ("trace_replay", trace_replay),
    ("live_cluster", live_cluster),
    ("serving", serving),
    ("mixed_pool", mixed_pool),
]


def main() -> None:
    # before any benchmark touches JAX: the live ones need 8 devices
    force_host_devices(8)
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in BENCHES:
        try:
            mod.run()
        except Exception as e:                      # keep the harness going
            failures += 1
            print(f"{name},0,FAILED:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
