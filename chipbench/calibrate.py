"""Readings that a cell's limits are set from: the program's, the control's
and the planted faults', at the cell's own size on the chip. The benchmark's
runs never run this.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 --what <w>

``--what program``: the cell itself with a window of one batch or cycle, per
seed, in one process; its comparisons (the lower readings).
``--what control`` (decode): the same, and the int8 control's gaps on the
same prompts and served tokens.
``--what faults`` (training): the reference put in the program's place,
computed as the int8 control and with each planted fault, against the plain
reference, on the cell's chips.

A cell may be admitted (``BENCHMARK.json``) or pending (``pending.json``).
One JSON line per seed, with each training reading's gap leaf by leaf and
the leaves left out of the change; the last line sums the numbers up.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse
    import dataclasses

    import jax

    from chipbench import harness
    from chipbench.kinds import common
    from chipbench.peaks import peaks_for

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", choices=("program", "control", "faults"),
                   required=True)
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    cell = harness.resolve_cell(harness.load_bench(pending=True),
                                args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chips", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = dataclasses.asdict(peaks_for(devices[0].device_kind))
    kind = harness.load_module("kinds", cell.traffic["kind"])
    ref = common.reference(cell.config)

    rows = []
    for seed in seeds:
        logged = []
        ctx = harness.Context(cell, seed, 0.0, False, devices,
                              time.perf_counter(), peaks, log=logged.append)
        row, leaves = {"seed": seed}, {}
        if args.what == "faults":
            base = kind.reference_readings(ctx, ref)
            d_inner = cell.config["expand"] * cell.config["d_model"]
            for name, fault in (
                    ("control", {"quant": "int8"}),
                    ("half_batch", {"rows_used":
                                    cell.traffic["global_batch"] // 2}),
                    ("no_exchange", {"keep": d_inner // 2})):
                got = kind.reference_readings(ctx, ref, **fault)
                row[name] = kind.readings(got, base)
                leaves[name] = kind.leaf_report(got, base,
                                                ref.layout(cell.config))
        else:
            out = kind.run(ctx)
            row["program"] = {c.name: c.value for c in out.checks}
            for rec in logged:
                if "leaves" in rec:
                    leaves["program"] = rec["leaves"]
            if args.what == "control":
                row["control"] = {c.name: c.value for c in kind.check(
                    ctx, ref, out.record["served"], control=True)}
        row["elapsed_s"] = time.perf_counter() - T0
        if leaves:
            row["leaves"] = leaves
        print(json.dumps(row), flush=True)
        rows.append(row)

    summary = {}
    for row in rows:
        for part, vals in row.items():
            if isinstance(vals, dict) and part != "leaves":
                for k, v in vals.items():
                    s = summary.setdefault(part, {}).setdefault(k, [v, v])
                    s[0], s[1] = min(s[0], v), max(s[1], v)
    print(json.dumps({"min_max": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
