"""Elastic training driver — DMRlib malleability on a live training job.

Examples (CPU demo on host devices):

  JAX_PLATFORMS=cpu python -m repro.launch.train --arch granite-3-2b-smoke \\
      --steps 20 --host-devices 8 --min 2 --pref 4 \\
      --resize-at 5:8 --resize-at 12:2

  # operator-driven resizes (the Slurm-RPC stand-in):
  ... --rms-file /tmp/resize.json      # echo '{"target": 8}' > /tmp/resize.json

On a TPU host the same driver runs on the chips JAX finds; ``--max``
defaults to all of them and ``--pref`` to ``--max``. ``run`` is the loop
itself, callable in-process (``chip_smoke.py`` drives it).
"""
import argparse
import os
import time
from typing import Dict, List, Optional, Sequence

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_shape
from repro.configs.base import ShapeConfig
import repro.dmr as dmr
from repro.core.lm_app import lm_train_app
from repro.launch.device import describe, enable_compile_cache
from repro.optim import AdamW, cosine_schedule


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default=None,
                   help="named shape; default: a small training shape")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--min", type=int, default=1)
    p.add_argument("--max", type=int, default=None,
                   help="default: every device JAX finds")
    p.add_argument("--pref", type=int, default=None,
                   help="default: --max")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--resize-at", action="append", default=[],
                   metavar="STEP:TARGET")
    p.add_argument("--rms-file", default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--host-devices", type=int, default=None,
                   help="force N host (CPU) devices; main() sets it before "
                        "JAX's backend starts")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def run(args: argparse.Namespace, devices: Optional[List] = None,
        log=print) -> Dict:
    """The training loop. Every step ends in ``block_until_ready``, so
    ``step_s[i]`` is step ``i``'s wall time (the first includes compile).
    ``state_devices[i]`` counts the devices the state's shardings span
    after step ``i``. Returns ``{"losses", "step_s", "workers",
    "state_devices", "events", "state"}``."""
    devices = list(devices) if devices is not None else jax.devices()
    cfg = get_config(args.arch)
    if args.shape:
        shape = get_shape(args.shape)
    else:
        shape = ShapeConfig("cli_train", "train", args.seq_len,
                            args.global_batch)
    hi = args.max or len(devices)
    pref = args.pref or hi

    opt = AdamW(learning_rate=cosine_schedule(args.lr, 10, args.steps),
                moment_dtype=cfg.opt_moment_dtype)
    app = lm_train_app(cfg, shape, opt, seed=args.seed)
    params = dmr.set_parameters(args.min, hi, pref)
    if args.rms_file:
        rms = dmr.connect(f"file:{args.rms_file}")
    else:
        rms = dmr.connect({int(s.split(":")[0]): int(s.split(":")[1])
                           for s in args.resize_at})
    runner = dmr.MalleableRunner(app, params, rms, devices=devices[:hi])
    ckpt = CheckpointManager(args.checkpoint_dir or "/tmp/repro_ckpt",
                             every_steps=args.checkpoint_every)

    state = runner.init()
    start = int(jax.device_get(state.step))
    log(f"# elastic train: {cfg.name} on {runner.current} workers "
        f"(min {args.min} / pref {pref} / max {hi}), "
        f"batch {shape.global_batch} x seq {shape.seq_len}")
    losses, step_s, workers, state_devices = [], [], [], []
    for step in range(start, args.steps):
        t0 = time.perf_counter()
        state = dmr.reconfig(runner, state, step)
        state, metrics = runner.step(state, step)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(jax.device_get(metrics["loss"])))
        workers.append(runner.current)
        state_devices.append(len({d for x in jax.tree.leaves(state)
                                  for d in x.sharding.device_set}))
        log(f"step {step:4d}  workers {runner.current:3d}  "
            f"loss {losses[-1]:.4f}  {step_s[-1] * 1e3:.1f} ms")
        if args.checkpoint_every:
            ckpt.maybe_save(jax.device_get(state), step)
    for e in runner.events:
        log(f"# resize @step {e.step}: {e.action} {e.from_procs}->"
            f"{e.to_procs}, moved {e.transfer.bytes_moved/1e6:.1f} MB in "
            f"{e.transfer.seconds*1e3:.1f} ms, compile {e.compile_s:.2f} s, "
            f"swap {e.recompile_s*1e3:.1f} ms")
    return {"losses": losses, "step_s": step_s, "workers": workers,
            "state_devices": state_devices, "events": list(runner.events),
            "state": state}


def main(argv: Optional[Sequence[str]] = None):
    args = parse_args(argv)
    if args.host_devices:
        # read when JAX's backend starts, which no import above triggers
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.host_devices}")
    enable_compile_cache()
    print(f"# device: {describe()}")
    run(args)
    print("# done")


if __name__ == "__main__":
    main()
