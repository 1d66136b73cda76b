"""Batched serving driver — a thin CLI over ``repro.serve``.

Prefills a prompt batch, then greedy-decodes, as a malleable job: the
decode path runs under a ``MalleableRunner`` (``repro.serve.
make_decode_app``) and ``--resize-at``/``--resize-to`` schedule an
elastic resize at a decode-step boundary through ``dmr.reconfig`` —
params re-replicate and the KV/SSM caches re-shard through the
redistribution-pattern registry, with bit-identical tokens before and
after.  The heavy lifting lives in :func:`repro.serve.decode_demo`;
this module only parses flags and prints.

  JAX_PLATFORMS=cpu python -m repro.launch.serve --arch mamba2-370m-smoke \\
      --batch 4 --prompt-len 32 --decode-steps 16 --host-devices 8 \\
      --resize-at 40 --resize-to 8

The replica starts on one device and may grow to the largest power of two
of the devices JAX finds.
"""
import argparse
import os
from typing import Optional, Sequence


def main(argv: Optional[Sequence[str]] = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--decode-steps", type=int, default=16)
    p.add_argument("--cache-len", type=int, default=128)
    p.add_argument("--host-devices", type=int, default=None,
                   help="force N host (CPU) devices")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--resize-at", type=int, action="append", default=None,
                   help="decode-path step index to resize at (repeatable; "
                        "pairs up with --resize-to)")
    p.add_argument("--resize-to", type=int, action="append", default=None,
                   help="worker count to resize to at the matching "
                        "--resize-at step")
    args = p.parse_args(argv)
    if args.host_devices:
        # read when JAX's backend starts, which nothing has triggered yet
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.host_devices}")

    ats, tos = args.resize_at or [], args.resize_to or []
    if len(ats) != len(tos):
        p.error("--resize-at and --resize-to must pair up")
    schedule = dict(zip(ats, tos))

    from repro.launch.device import describe, enable_compile_cache
    from repro.serve import decode_demo

    enable_compile_cache()
    print(f"# device: {describe()}")
    out = decode_demo(args.arch, batch=args.batch,
                      prompt_len=args.prompt_len,
                      decode_steps=args.decode_steps,
                      cache_len=args.cache_len,
                      schedule=schedule, seed=args.seed)

    toks = out["tokens"]
    print(f"# {args.arch}: batch {args.batch}, prompt {args.prompt_len}, "
          f"decoded {args.decode_steps}")
    print(f"# prefill {out['prefill_s']*1e3:.1f} ms, decode "
          f"{out['decode_s']/args.decode_steps*1e3:.2f} ms/token")
    for step, size in out["sizes"]:
        print(f"# step {step}: {size} workers")
    for ev in out["events"]:
        print(f"# resize @ step {ev.step}: {ev.action} "
              f"{ev.from_procs}->{ev.to_procs} "
              f"({ev.transfer.bytes_moved/1e6:.1f} MB moved, "
              f"compile {ev.compile_s*1e3:.0f} ms, "
              f"swap {ev.recompile_s*1e3:.1f} ms)")
    for b in range(min(args.batch, 4)):
        print(f"seq[{b}]: {toks[b].tolist()}")


if __name__ == "__main__":
    main()
