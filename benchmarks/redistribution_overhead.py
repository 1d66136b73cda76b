"""§3.2 / §2 — reconfiguration overhead: in-memory redistribution vs on-disk
checkpoint/restart, as a function of state size.

Reproduces the paper's findings: overhead is dominated by data size; the
in-memory path (the DMR family's approach, §2.2) beats C/R (§2.1) by the
disk-vs-memory bandwidth gap. A 4 -> 8 worker resharding of 128 MB is
measured on the first eight devices (``main`` asks for an 8-device host
farm).
"""
from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.common import (force_host_devices, report, require_devices,
                               timer, write_csv)
from repro.checkpoint import restore_state, save_state
from repro.core.redistribute import redistribute_state
from repro.parallel.mesh import make_job_mesh

SIZES_MB = [1, 8, 32, 128]


def _reshard_4_to_8(devs):
    m4, m8 = make_job_mesh(devs[:4]), make_job_mesh(devs[:8])
    x = jnp.zeros((64, 1 << 19), jnp.float32)          # 128 MB
    x = jax.device_put(x, NamedSharding(m4, P("data", None)))
    jax.block_until_ready(x)
    _, stats = redistribute_state(x, NamedSharding(m8, P("data", None)),
                                  donate=False)
    return stats


def run():
    devs = require_devices(8, "redistribution_overhead")
    rows = []
    with timer() as t:
        for mb in SIZES_MB:
            n = mb * (1 << 20) // 4
            state = {"x": jnp.arange(n, dtype=jnp.float32)}
            jax.block_until_ready(state)
            sh = jax.tree.map(
                lambda _: jax.sharding.SingleDeviceSharding(devs[0]),
                state)
            _, st = redistribute_state(state, sh, donate=False)
            with tempfile.TemporaryDirectory() as d:
                t0 = time.perf_counter()
                save_state(d, state, 0)
                _, _ = restore_state(d, state)
                cr_s = time.perf_counter() - t0
            rows.append({
                "state_mb": mb,
                "inmemory_ms": round(st.seconds * 1e3, 2),
                "ondisk_cr_ms": round(cr_s * 1e3, 2),
                "speedup": round(cr_s / max(st.seconds, 1e-9), 1),
            })
        stats = _reshard_4_to_8(devs)
    path = write_csv("redistribution_overhead", rows)
    big = rows[-1]
    report("redistribution_overhead", t.seconds,
           f"inmem_vs_cr_128mb={big['speedup']}x;reshard_4to8="
           f"{stats.bytes_moved}B/{stats.seconds:.4f}s;csv={path}")


if __name__ == "__main__":
    force_host_devices(8)
    run()
