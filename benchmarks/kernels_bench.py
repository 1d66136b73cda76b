"""Kernel microbenchmarks (interpret mode on CPU: correctness-grade timing;
real performance comes from the roofline analysis of the compiled dry-run)."""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import report, timer, write_csv
from repro.kernels import ops, ref


def _t(fn, *args, iters=3):
    fn(*args)                       # compile / warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run():
    rng = np.random.default_rng(0)
    rows = []
    with timer() as t:
        B, H, S, D = 1, 4, 512, 64
        q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
        t_kern = _t(lambda a, b, c: ops.flash_attention(a, b, c, causal=True,
                                                         interpret=True),
                    q, k, v)
        t_ref = _t(lambda a, b, c: ref.attention_reference(a, b, c,
                                                           causal=True),
                   q, k, v)
        rows.append({"kernel": "flash_attention", "shape": f"{B}x{H}x{S}x{D}",
                     "interpret_ms": round(t_kern * 1e3, 2),
                     "ref_ms": round(t_ref * 1e3, 2)})

        B, H, S, P, N = 1, 4, 512, 32, 32
        xdt = jnp.asarray(rng.standard_normal((B, H, S, P)) * .3, jnp.float32)
        a = -jnp.abs(jnp.asarray(rng.standard_normal((B, H, S)), jnp.float32))
        bm = jnp.asarray(rng.standard_normal((B, S, N)) * .3, jnp.float32)
        cm = jnp.asarray(rng.standard_normal((B, S, N)) * .3, jnp.float32)
        rows.append({"kernel": "ssd_scan", "shape": f"{B}x{H}x{S}x{P}x{N}",
                     "interpret_ms": round(_t(partial(ops.ssd_scan, interpret=True),
                                              xdt, a, bm, cm) * 1e3, 2),
                     "ref_ms": round(_t(ref.ssd_reference, xdt, a, bm,
                                        cm) * 1e3, 2)})

        src = jnp.asarray(rng.standard_normal((256, 64, 128)), jnp.float32)
        idx = jnp.asarray(rng.permutation(256), jnp.int32)
        rows.append({"kernel": "blockcyclic_repack", "shape": "256x64x128",
                     "interpret_ms": round(_t(partial(ops.repack, interpret=True),
                                              src, idx) * 1e3, 2),
                     "ref_ms": round(_t(ref.repack_reference, src,
                                        idx) * 1e3, 2)})
    path = write_csv("kernel_microbench", rows)
    report("kernel_microbench", t.seconds,
           f"kernels=3;all_validated_interpret=True;csv={path}")


if __name__ == "__main__":
    run()
