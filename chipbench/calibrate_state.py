"""The readings of a decode cell that carries SSM state, per seed: the
program's and the int8 control's, as ``calibrate.py --what control`` gives
them, and the program's with its SSM state stored in a lower precision
than the configuration states (bfloat16 for float32: the configuration's
``dtype.ssm_state`` and the program's ``ssm.state_dtype`` changed
together): the state rounded at every step, as a cache that
halves the state's bytes would round it. The benchmark's runs never run
this.

    python3 chipbench/calibrate_state.py --workload <name> --seeds 1,2,3

One JSON line per seed; the last line gives each reading's least and
greatest value over the seeds.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
#: the precision below the configuration's float32 state
LOWER = "bfloat16"


def main(argv=None) -> int:
    import argparse
    import copy
    import dataclasses

    import jax

    from chipbench import harness
    from chipbench.kinds import common
    from chipbench.peaks import peaks_for

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.resolve_cell(harness.load_bench(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate_state: needs a TPU chip", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    peaks = dataclasses.asdict(peaks_for(devices[0].device_kind))
    kind = harness.load_module("kinds", cell.traffic["kind"])
    ref = common.reference(cell.config)
    low_config = copy.deepcopy(cell.config)
    low_config["dtype"]["ssm_state"] = LOWER
    low_config["program"].setdefault("overrides", {})[
        "ssm.state_dtype"] = LOWER
    low_cell = dataclasses.replace(cell, config=low_config)

    summary = {}
    for seed in seeds:
        ctx = harness.Context(cell, seed, 0.0, False, devices,
                              time.perf_counter(), peaks,
                              log=lambda rec: None)
        out = kind.run(ctx)
        row = {"seed": seed,
               "program": {c.name: c.value for c in out.checks},
               "control": {c.name: c.value for c in kind.check(
                   ctx, ref, out.record["served"], control=True)}}
        low = kind.run(harness.Context(low_cell, seed, 0.0, False, devices,
                                       time.perf_counter(), peaks,
                                       log=lambda rec: None))
        row[f"state_{LOWER}"] = {c.name: c.value for c in low.checks}
        row["elapsed_s"] = time.perf_counter() - T0
        print(json.dumps(row), flush=True)
        for part, vals in row.items():
            if isinstance(vals, dict):
                for k, v in vals.items():
                    s = summary.setdefault(part, {}).setdefault(k, [v, v])
                    s[0], s[1] = min(s[0], v), max(s[1], v)
    print(json.dumps({"min_max": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
