"""Mixed train+serve pools and the two serving scale-up paths.

Two claims from the unified tenant contract (``MalleableTenant`` from
``ReplicaSet`` down to ``dmr.Cluster``), measured:

1. **Resize-in-place vs replica-add latency** (live JAX, host device
   farm).  A malleable replica granted headroom resizes its decode mesh
   through ``dmr.reconfig``; once a size's programs have been exercised
   (the steady state of a fleet breathing with the diurnal cycle), a
   grow costs only the state transfer — milliseconds — while a replica
   cold start always pays app init + device placement + first-step
   compilation on its fresh mesh.  Asserted: steady-state in-place grow
   is faster than replica cold start; the first-ever grow (compile
   caches cold) is reported alongside, not asserted.

2. **Shared vs partitioned pools** (host model).  A batch workload plus
   a diurnal serving fleet on ONE 16-device ``dmr.Cluster`` (the fleet
   submitted as a composite tenant) against the classic split: 8
   devices walled off for batch, 8 for a standalone capped fleet.
   Sharing lets the fleet swell past its partition at the diurnal peak
   (blocked expands surface as published demand; co-tenants shrink
   toward it) and lets batch jobs soak the trough.  Asserted: the
   shared pool beats the partitioned split on BOTH serving goodput
   under SLO and batch jobs/s, and the shared trail audits clean.

Results land in ``experiments/bench/mixed_pool.csv`` and merge into
``BENCH_serving.json`` under ``"mixed_pool"``; ``--trail-out`` dumps the
shared cluster's trail for the analysis job's audit gate.

    PYTHONPATH=src python -m benchmarks.mixed_pool            # full
    PYTHONPATH=src python -m benchmarks.mixed_pool --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks.common import (force_host_devices, report, require_devices,
                               write_csv)

BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serving.json")

SEED = 1
POOL = 16                            # shared pool; partitions are 8 + 8
FULL = dict(n_jobs=12, max_steps=24, n_requests=6000, horizon_s=120.0)
SMOKE = dict(n_jobs=6, max_steps=12, n_requests=1500, horizon_s=40.0)


def _serve_config(max_replicas: int):
    from repro.serve import ServeConfig
    return ServeConfig(devices_per_replica=2, min_replicas=1,
                       max_replicas=max_replicas, initial_replicas=2,
                       max_devices_per_replica=4,
                       cold_start_ticks=4, grow_ticks=1)


# ----------------------------------------------------------------------
# part 1 — live scale-up latency
# ----------------------------------------------------------------------

def _scale_latency(devices, n_trials: int = 3) -> dict:
    from repro.configs import get_config
    from repro.core.params import MalleabilityParams
    from repro.core.policy import Action
    from repro.dmr.runner import MalleableRunner
    from repro.serve import make_decode_app

    cfg = get_config("mamba2-370m-smoke")
    factory = lambda: make_decode_app(cfg, batch=2, cache_len=32)

    def cold_start(devs):
        t0 = time.perf_counter()
        r = MalleableRunner(factory(), MalleabilityParams(2, 4, 2),
                            devices=devs, initial_procs=2,
                            allow_partial=True)
        s = r.init()
        r.step(s, 0)
        return time.perf_counter() - t0

    # throwaway build: absorb the one-time jax/backend warmup so the
    # cold-start samples measure replica bring-up, not process init
    cold_start(devices[:2])

    # a malleable replica holding grow headroom: mesh at 2 of 4 devices
    r = MalleableRunner(factory(), MalleabilityParams(2, 4, 2),
                        devices=devices[:4], initial_procs=2,
                        allow_partial=True)
    state = r.init()
    r.prewarm()
    state, _ = r.step(state, 0)

    def cycle(i, s):
        t0 = time.perf_counter()
        s = r.apply_resize(s, i, Action("expand", 4))
        s, _ = r.step(s, i)
        dt = time.perf_counter() - t0
        s = r.apply_resize(s, i + 1, Action("shrink", 2))
        s, _ = r.step(s, i + 1)
        return dt, s

    first_grow_s, state = cycle(1, state)    # compile caches still cold
    grows = []
    for k in range(n_trials):
        dt, state = cycle(3 + 2 * k, state)
        grows.append(dt)
    in_place_s = sum(grows) / len(grows)

    colds = [cold_start(devices[2 * (1 + k):2 * (2 + k)])
             for k in range(n_trials)]
    cold_s = sum(colds) / len(colds)

    assert in_place_s < cold_s, \
        (f"steady-state in-place grow must beat replica cold start: "
         f"{in_place_s:.4f}s >= {cold_s:.4f}s")
    return {"in_place_grow_s": in_place_s, "replica_cold_start_s": cold_s,
            "first_grow_s": first_grow_s,
            "speedup": cold_s / in_place_s,
            "transfer_bytes": r.events[-1].transfer.bytes_moved}


# ----------------------------------------------------------------------
# part 2 — shared vs partitioned pools
# ----------------------------------------------------------------------

def _batch_specs(n_jobs, max_steps, seed):
    from repro.rms.workload import materialize_live
    return materialize_live("bursty", n_jobs=n_jobs,
                            device_count=POOL // 2, max_steps=max_steps,
                            seed=seed)


def _fleet_spec(n_requests, horizon_s, seed, max_replicas):
    from repro.serve.tenant import ServeTenantSpec
    return ServeTenantSpec(jid=1000, config=_serve_config(max_replicas),
                           scenario="diurnal", n_requests=n_requests,
                           horizon_s=horizon_s, seed=seed)


def _batch_jps(result, jids):
    ticks = max(r.end_tick for r in result.records if r.jid in jids)
    return len(jids) / (ticks * result.tick_s) if ticks > 0 else 0.0


def _pool_grid(p, seed):
    import repro.dmr as dmr
    from repro.analysis.trail import audit_trail, job_metadata
    from repro.serve import ReplicaSet

    batch = _batch_specs(p["n_jobs"], p["max_steps"], seed)
    batch_jids = {s.jid for s in batch}

    # shared: one pool, the fleet rides as a composite tenant and may
    # swell to 6 replicas at the peak (a partition would cap it at 4)
    fleet = _fleet_spec(p["n_requests"], p["horizon_s"], seed,
                        max_replicas=6)
    shared = dmr.Cluster.sched_only(list(batch) + [fleet],
                                    n_devices=POOL, record_trail=True)
    shared_res = shared.run()
    serve_tenant = next(t for t in shared.tenants
                        if getattr(t, "composite", False))
    shared_serve = serve_tenant.result.summary()
    violations = audit_trail(shared.trail, shared._pool_ids,
                             jobs=job_metadata(shared))

    # partitioned: batch on its own 8 devices, the fleet standalone on
    # the other 8 (pool-capped at 4 replicas)
    part_batch = dmr.Cluster.sched_only(
        _batch_specs(p["n_jobs"], p["max_steps"], seed),
        n_devices=POOL // 2)
    part_batch_res = part_batch.run()
    spec = _fleet_spec(p["n_requests"], p["horizon_s"], seed,
                       max_replicas=4)
    part_fleet = ReplicaSet(spec.make_requests(), devices=POOL // 2,
                            policy=spec.policy, config=spec.config,
                            record_trail=True)
    part_serve = part_fleet.run().summary()

    rows = [
        {"pool": "shared", "devices": POOL,
         "goodput_rps": shared_serve["goodput_rps"],
         "slo_attainment": shared_serve["slo_attainment"],
         "p99_s": shared_serve["p99_s"],
         "batch_jobs_per_s": _batch_jps(shared_res, batch_jids),
         "trail_violations": len(violations)},
        {"pool": "partitioned", "devices": f"{POOL // 2}+{POOL // 2}",
         "goodput_rps": part_serve["goodput_rps"],
         "slo_attainment": part_serve["slo_attainment"],
         "p99_s": part_serve["p99_s"],
         "batch_jobs_per_s": _batch_jps(part_batch_res, batch_jids),
         "trail_violations": 0},
    ]

    # time-to-capacity of the two scale-up paths, from the shared
    # fleet's scale decisions (the service-model complement of part 1)
    ready = {}
    for ev in serve_tenant.result.scale_events or []:
        ready.setdefault(ev["kind"], []).append(
            ev["ready_tick"] - ev["tick"])
    ticks_to_capacity = {k: sum(v) / len(v) for k, v in ready.items()}

    sh, pt = rows[0], rows[1]
    assert not violations, \
        f"shared-pool trail must audit clean: {violations[:5]}"
    assert sh["goodput_rps"] > pt["goodput_rps"], \
        (f"shared pool must beat the partition on serving goodput: "
         f"{sh['goodput_rps']:.2f} <= {pt['goodput_rps']:.2f} rps")
    assert sh["batch_jobs_per_s"] > pt["batch_jobs_per_s"], \
        (f"shared pool must beat the partition on batch jobs/s: "
         f"{sh['batch_jobs_per_s']:.5f} <= {pt['batch_jobs_per_s']:.5f}")
    return rows, ticks_to_capacity, shared


def run(smoke: bool = False, seed: int = SEED, trail_path=None):
    devices = require_devices(8, "mixed_pool")
    from repro.analysis.trail import dump_trail

    t_start = time.perf_counter()
    p = dict(SMOKE if smoke else FULL)
    latency = _scale_latency(devices)
    rows, ticks_to_capacity, shared = _pool_grid(p, seed)
    if trail_path:
        dump_trail(shared, trail_path)

    payload = {
        "scale_latency": latency,
        "ticks_to_capacity": ticks_to_capacity,
        "pools": rows,
        "workload": dict(p, seed=seed, pool_devices=POOL),
        "smoke": smoke,
    }
    # merge into the serving benchmark's CI artifact
    existing = {}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            existing = json.load(f)
    existing["mixed_pool"] = payload
    with open(BENCH_JSON, "w") as f:
        json.dump(existing, f, indent=1)
    path = write_csv("mixed_pool", rows)
    report("mixed_pool", time.perf_counter() - t_start,
           f"in_place={latency['in_place_grow_s'] * 1e3:.1f}ms"
           f";cold={latency['replica_cold_start_s'] * 1e3:.1f}ms"
           f";shared_goodput={rows[0]['goodput_rps']:.2f}rps"
           f";part_goodput={rows[1]['goodput_rps']:.2f}rps"
           f";shared_jps={rows[0]['batch_jobs_per_s']:.4f}"
           f";part_jps={rows[1]['batch_jobs_per_s']:.4f}"
           f";json={BENCH_JSON};csv={path}")
    return payload


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized workload")
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--trail-out", default=None,
                    help="dump the shared cluster's trail JSON here "
                         "(analysis-job audit artifact)")
    args = ap.parse_args()
    force_host_devices(8)
    print("name,us_per_call,derived")
    run(smoke=args.smoke, seed=args.seed, trail_path=args.trail_out)


if __name__ == "__main__":
    main()
