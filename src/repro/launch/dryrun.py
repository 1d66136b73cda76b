import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any jax import (jax locks the device count
on first init); 512 host devices back both the single-pod (16, 16) and
multi-pod (2, 16, 16) production meshes. This is a CPU-only tool: importing
it rewrites the process's XLA flags and platform, so nothing on the chip
path (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``) imports it.

Per cell this prints/records:
  * compiled.memory_analysis()  — proves the cell fits per-chip HBM
  * compiled.cost_analysis()    — raw XLA flops/bytes (loop bodies counted 1x)
  * trip-count-adjusted HLO analysis (dot FLOPs, HBM traffic, collective wire
    bytes) and the three roofline terms (launch/roofline.py)

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import gc
import json
import time
import traceback
import warnings

warnings.filterwarnings("ignore")

import jax                                                     # noqa: E402

from repro.configs import (SHAPES, all_configs, get_config, get_shape,
                           shape_applicable)                   # noqa: E402
from repro.data.pipeline import input_specs                    # noqa: E402
from repro.launch.hloanalysis import analyze_hlo               # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.launch.roofline import build_roofline, chip_peaks  # noqa: E402
from repro.models import model as M                            # noqa: E402
from repro.models.train import (abstract_state, make_prefill_step,
                                make_serve_step, make_train_step)  # noqa: E402
from repro.optim import AdamW                                  # noqa: E402
from repro.parallel.context import sharding_context            # noqa: E402
from repro.parallel.sharding import (batch_shardings, cache_shardings,
                                     param_shardings, replicated, rules_for,
                                     state_shardings)          # noqa: E402


#: the chip the production meshes model (their devices are host devices)
PEAKS = chip_peaks("TPU v5 lite")


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               rules_overrides=None):
    """Lower + compile one cell; returns (compiled, cfg, shape, chips)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = rules_for(cfg, rules_overrides)
    opt = AdamW(learning_rate=1e-4, moment_dtype=cfg.opt_moment_dtype)

    with sharding_context(mesh, rules):
        if shape.kind == "train":
            st = abstract_state(cfg, opt)
            specs = input_specs(cfg, shape)
            ss = state_shardings(cfg, mesh)
            bs = batch_shardings(cfg, shape, mesh, specs)
            lowered = jax.jit(make_train_step(cfg, opt),
                              in_shardings=(ss, bs),
                              donate_argnums=(0,)).lower(st, specs)
        elif shape.kind == "prefill":
            params = M.abstract_params(cfg)
            specs = input_specs(cfg, shape)
            ps = param_shardings(cfg, mesh)
            bs = batch_shardings(cfg, shape, mesh, specs)
            lowered = jax.jit(make_prefill_step(cfg),
                              in_shardings=(ps, bs)).lower(params, specs)
        else:  # decode
            params = M.abstract_params(cfg)
            B, S = shape.global_batch, shape.seq_len
            cache = jax.eval_shape(
                lambda: M.init_cache(cfg, B, S, enc_len=S))
            ps = param_shardings(cfg, mesh)
            cs = cache_shardings(cfg, shape, mesh, cache)
            tok = jax.ShapeDtypeStruct((B, 1), jax.numpy.int32)
            idx = jax.ShapeDtypeStruct((), jax.numpy.int32)
            ts = batch_shardings(cfg, shape, mesh, {"tokens": tok})["tokens"]
            lowered = jax.jit(make_serve_step(cfg),
                              in_shardings=(ps, cs, ts, replicated(mesh)),
                              donate_argnums=(1,)).lower(params, cache, tok,
                                                         idx)
        compiled = lowered.compile()
    return compiled, cfg, shape, chips


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir=None,
             verbose=True, rules_overrides=None):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": why}
        if verbose:
            print(f"[skip] {arch} x {shape_name}: {why}")
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fn = os.path.join(out_dir,
                              f"{arch}__{shape_name}__{mesh_name}.json")
            with open(fn, "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    t0 = time.time()
    try:
        compiled, cfg, shape, chips = lower_cell(arch, shape_name, multi_pod,
                                                 rules_overrides)
    except Exception as e:                       # a failure here is a bug
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "FAILED", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        if verbose:
            print(f"[FAIL] {arch} x {shape_name} x {mesh_name}: {e}")
        return rec

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    mem = float(ma.argument_size_in_bytes + ma.temp_size_in_bytes)
    hlo = analyze_hlo(compiled.as_text(), chips)
    rl = build_roofline(cfg, shape, mesh_name, chips, hlo, mem, PEAKS)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_gib": ma.argument_size_in_bytes / 2 ** 30,
            "temp_gib": ma.temp_size_in_bytes / 2 ** 30,
            "output_gib": ma.output_size_in_bytes / 2 ** 30,
            "total_gib": mem / 2 ** 30,
            "fits_16gib": mem <= PEAKS.hbm_bytes,
        },
        "xla_cost_analysis": {"flops": ca.get("flops", 0.0),
                              "bytes_accessed": ca.get("bytes accessed", 0.0)},
        "hlo": hlo.as_dict(),
        "roofline": rl.as_dict(),
    }
    if verbose:
        m = rec["memory"]
        r = rec["roofline"]
        print(f"[ok] {arch} x {shape_name} x {mesh_name}: "
              f"mem {m['total_gib']:.2f} GiB (fit={m['fits_16gib']}), "
              f"terms c/m/n = {r['compute_s']:.3e}/{r['memory_s']:.3e}/"
              f"{r['collective_s']:.3e} s -> {r['bottleneck']}, "
              f"MFU {r['mfu']:.1%}, useful {r['useful_ratio']:.2f}, "
              f"compile {rec['compile_s']}s")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    del compiled
    gc.collect()
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", choices=["single", "multi", "both"],
                   default="single")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="experiments/dryrun")
    args = p.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    cells = []
    if args.all:
        for a in all_configs():
            for s in SHAPES:
                cells.append((a, s.name))
    elif args.arch and not args.shape:
        for s in SHAPES:                       # all shapes for one arch
            cells.append((args.arch, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    results = []
    for mp in meshes[args.mesh]:
        for a, s in cells:
            results.append(run_cell(a, s, mp, out_dir=args.out))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "FAILED" for r in results)
    print(f"\n== dry-run: {n_ok} ok / {n_skip} skipped / {n_fail} FAILED ==")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
