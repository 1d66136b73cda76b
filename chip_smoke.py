"""Smoke run of the main path on TPU chips, through the entry points a user
calls, at the registry's full widths with random weights from a seed.

    python chip_smoke.py              # one chip: train phase + serve phase
    python chip_smoke.py --chips 4    # four chips: resize paths only

One chip:

* **train** -- ``repro.launch.train.run`` (``lm_train_app`` under
  ``dmr.MalleableRunner`` and ``dmr.reconfig``) on mamba2-370m, sequence
  2048, global batch 16 (the largest of 4, 8, 16 whose step compiles into
  one v5e chip's HBM), min = max = pref = 1 worker. Two warm-up steps, then
  five timed ones; every loss must be finite.
* **serve** -- ``repro.serve.decode_demo`` on granite-3-2b: batch 8, prompt
  64, 32 decode tokens, cache 2048. Every token must be in the vocabulary.

Four chips (``--chips 4``), each compared with a run that does not resize:

* **train** -- mamba2-370m resized 1 -> 2 -> 4 -> 2 by a scripted RMS; each
  loss must agree with the fixed one-chip run's within ``LOSS_RTOL``.
* **decode** -- a granite-3-2b replica grown 1 -> 2 -> 4 in place; its
  tokens must equal those of the run with no resize, bit for bit.

Each phase prints one JSON line, with the seconds JAX spent compiling in
it (``compile_s``) and the number of programs XLA built or loaded from the
persistent compile cache (``programs_built``). Against an earlier run, a
warm cache shows as the same count in a fraction of the seconds, and a
higher count as programs the phase did not build before. The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``,
printed only when every phase passed. With no TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TRAIN_ARCH, TRAIN_SEQ, TRAIN_BATCH = "mamba2-370m", 2048, 16
WARMUP, TIMED = 2, 5
SERVE_ARCH, SERVE_BATCH = "granite-3-2b", 8
PROMPT, DECODE, CACHE = 64, 32, 2048
#: train resize schedule on four chips, ``{step: workers}`` (1 -> 2 -> 4 -> 2)
TRAIN_RESIZES = {2: 2, 4: 4, 6: 2}
TRAIN_STEPS_4 = 8
#: decode resize schedule, both grows mid-generation so that the first
#: tokens come out before any resize
DECODE_RESIZES = {PROMPT + 8: 2, PROMPT + 20: 4}
#: bf16 compute, and a model-axis split of every matmul once resized: the
#: reductions run in another order, so losses agree only to this relative
#: bound. It is ten times the worst gap of a CPU run of the same schedule at
#: full width and 4 layers (2.1e-4), and a quarter of the loss's own drop
#: over the run, so a resize that lost or reset state would exceed it.
LOSS_RTOL = 2e-3


def compile_lap():
    """A function that returns, since its previous call, the seconds JAX
    spent tracing, lowering and compiling (``compile_s``) and the programs
    XLA built or loaded from the persistent cache (``programs_built``), from
    ``repro.core.spans.COMPILE``."""
    from repro.core.spans import COMPILE
    last = COMPILE.seconds, COMPILE.compiles

    def lap() -> dict:
        nonlocal last
        now = COMPILE.seconds, COMPILE.compiles
        (s, n), last = (now[0] - last[0], now[1] - last[1]), now
        return {"compile_s": s, "programs_built": n}
    return lap


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def train_args(train, *extra):
    return train.parse_args(["--arch", TRAIN_ARCH, "--seq-len", str(TRAIN_SEQ),
                             "--global-batch", str(TRAIN_BATCH), *extra])


def check_losses(losses):
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")


def check_tokens(tokens, vocab, shape):
    if tokens.shape != shape:
        raise AssertionError(f"tokens {tokens.shape} != {shape}")
    if tokens.min() < 0 or tokens.max() >= vocab:
        raise AssertionError(
            f"tokens outside [0, {vocab}): {tokens.min()}..{tokens.max()}")


def event_records(events):
    return [{"step": e.step, "action": e.action, "from": e.from_procs,
             "to": e.to_procs, "bytes_moved": e.transfer.bytes_moved,
             "transfer_s": e.transfer.seconds, "compile_s": e.compile_s,
             "swap_s": e.recompile_s} for e in events]


def one_chip(lap, dev):
    from repro.configs import get_config
    from repro.launch import train

    steps = WARMUP + TIMED
    out = train.run(train_args(train, "--steps", str(steps), "--min", "1",
                               "--max", "1", "--pref", "1"), devices=[dev])
    check_losses(out["losses"])
    del out["state"]
    emit({"phase": "train", "arch": TRAIN_ARCH, "seq": TRAIN_SEQ,
          "global_batch": TRAIN_BATCH, "workers": 1,
          **lap(), "first_step_s": out["step_s"][0],
          "timed_step_s": out["step_s"][WARMUP:], "losses": out["losses"],
          "peak_bytes_in_use": peak_bytes(dev)})

    cfg = get_config(SERVE_ARCH)
    res = serve_run([dev], None)
    check_tokens(res["tokens"], cfg.vocab_size, (SERVE_BATCH, DECODE))
    emit({"phase": "serve", "arch": SERVE_ARCH, "batch": SERVE_BATCH,
          "prompt": PROMPT, "decode_tokens": DECODE, "cache_len": CACHE,
          **lap(),
          "prefill_s_incl_compile": res["prefill_s"],
          "decode_s_per_token": res["decode_s"] / DECODE,
          "peak_bytes_in_use": peak_bytes(dev)})


def state_device_ids(tree):
    return sorted({d.id for x in jax.tree.leaves(tree)
                   for d in x.sharding.device_set})


def four_chips(lap, devs):
    from repro.launch import train

    resizes = [a for s, n in TRAIN_RESIZES.items()
               for a in ("--resize-at", f"{s}:{n}")]
    ela = train.run(train_args(train, "--steps", str(TRAIN_STEPS_4),
                               "--min", "1", "--max", "4", "--pref", "1",
                               *resizes), devices=devs)
    del ela["state"]
    ref = train.run(train_args(train, "--steps", str(TRAIN_STEPS_4), "--min",
                               "1", "--max", "1", "--pref", "1"),
                    devices=devs[:1])
    del ref["state"]
    check_losses(ela["losses"] + ref["losses"])
    rel = [abs(a - b) / abs(b) for a, b in zip(ela["losses"], ref["losses"])]
    emit({"phase": "train_resize", "arch": TRAIN_ARCH, "seq": TRAIN_SEQ,
          "global_batch": TRAIN_BATCH, "workers": ela["workers"],
          "state_devices": ela["state_devices"],
          "losses": ela["losses"], "fixed_losses": ref["losses"],
          "max_rel_diff": max(rel), "rtol": LOSS_RTOL,
          "events": event_records(ela["events"]), **lap()})
    if [e.to_procs for e in ela["events"]] != list(TRAIN_RESIZES.values()):
        raise AssertionError(f"resizes {event_records(ela['events'])}")
    if ela["state_devices"] != ela["workers"]:
        raise AssertionError("state does not span the runner's workers: "
                             f"{ela['state_devices']} vs {ela['workers']}")
    if max(rel) > LOSS_RTOL:
        raise AssertionError(f"resized losses differ by {max(rel)} > "
                             f"{LOSS_RTOL}")

    grown = serve_run(devs, DECODE_RESIZES)
    ids = state_device_ids(grown.pop("state"))
    fixed = serve_run(devs[:1], None)
    del fixed["state"]
    same = grown["tokens"] == fixed["tokens"]
    sizes = [n for _, n in grown["sizes"]]
    emit({"phase": "decode_resize", "arch": SERVE_ARCH, "batch": SERVE_BATCH,
          "prompt": PROMPT, "decode_tokens": DECODE, "cache_len": CACHE,
          "sizes": sizes, "state_device_ids": ids,
          "tokens_identical": bool(same.all()),
          "identical_tokens": int(same.sum()), "tokens": int(same.size),
          "first_diff_per_seq": [int(np.argmin(r)) if not r.all() else None
                                 for r in same],
          "decode_s_per_token": grown["decode_s"] / DECODE,
          "fixed_decode_s_per_token": fixed["decode_s"] / DECODE,
          "events": event_records(grown["events"]), **lap()})
    if sizes != [1, 2, 4]:
        raise AssertionError(f"replica sizes {sizes}")
    if ids != sorted(d.id for d in devs):
        raise AssertionError(f"decode state on devices {ids}")
    if not same.all():
        raise AssertionError(f"{int((~same).sum())} of {same.size} tokens "
                             f"differ from the run with no resize")


def serve_run(devs, schedule):
    from repro.serve import decode_demo
    return decode_demo(SERVE_ARCH, batch=SERVE_BATCH, prompt_len=PROMPT,
                       decode_steps=DECODE, cache_len=CACHE,
                       schedule=schedule, devices=devs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.device import describe, enable_compile_cache

    devices = devices[:args.chips]
    cache = enable_compile_cache()
    device = describe(devices)
    emit({"device": device, "compile_cache": cache, "jax": jax.__version__})
    lap = compile_lap()
    if args.chips == 1:
        one_chip(lap, devices[0])
    else:
        four_chips(lap, devices)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
