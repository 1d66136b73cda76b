"""Live multi-tenant elastic cluster — the paper's cluster-level claim,
executed for real instead of simulated.

A whole workload of real malleable JAX jobs (``dmr.Cluster`` +
``materialize_live``) is co-scheduled on one shared 8-device pool across
a policy x submission-mode grid and compared against the rigid-static
baseline on the paper's metrics: allocation rate, completed jobs/s (on
the cluster-tick clock; wall time reported separately), estimated energy
(Appendix-B wattage), and per-job live resize logs.  The same smoke
workload is then replayed in ``decisions="cosim"`` mode and every
runner's resize trail is cross-checked against the discrete-event
``Simulator``'s resize_log — under both engines.

Every malleable config must beat the rigid-static baseline on completed
jobs/s (asserted).  Metrics land in ``experiments/bench/live_cluster.csv``
and ``BENCH_live_cluster.json`` (the CI artifact).

``--replay`` switches to the trace-scale scheduling benchmark: an SWF
trace (synthetic via ``generate_synthetic_swf``, or ``--trace path.swf``)
is parsed with ``parse_swf``, materialized with ``materialize_live`` and
driven through ``Cluster.sched_only`` — no JAX anywhere — measuring the
event engine against ``ReferenceCluster`` (asserting bit-identical
results and recording the speedup + peak RSS), a cosim crosscheck
replay, and an event-engine-only run at 1M jobs.  Results merge into
``BENCH_live_cluster.json`` under ``"replay"``.

    PYTHONPATH=src python -m benchmarks.live_cluster                # default
    PYTHONPATH=src python -m benchmarks.live_cluster --smoke        # CI-sized
    PYTHONPATH=src python -m benchmarks.live_cluster --replay       # 100k/1M
    PYTHONPATH=src python -m benchmarks.live_cluster --replay-smoke # CI-sized
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import resource
import time

from benchmarks.common import (force_host_devices, report, require_devices,
                               timer, write_csv)


POLICY_NAMES = ("algorithm2", "throughput-greedy")
MODES = ("rigid", "moldable")
SCENARIO = "steady"
BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_live_cluster.json")


def _devices():
    return require_devices(8, "live_cluster")


def _row(policy, mode, s, base):
    return {
        "policy": policy, "mode": mode,
        "makespan_ticks": round(s["makespan_s"], 0),
        "jobs_per_s": round(s["throughput_jps"], 5),
        "alloc_rate_pct": round(100 * s["alloc_rate"], 2),
        "energy_kwh": round(s["energy_kwh"], 6),
        "n_resizes": s["n_resizes"],
        "wall_s": round(s["wall_s"], 2),
        "throughput_vs_static":
            round(s["throughput_jps"] / base["throughput_jps"], 2),
    }


def _per_job(res):
    return [{"jid": r.jid, "app": r.name, "submit": r.submit_step,
             "start": r.start_tick, "end": r.end_tick,
             "start_procs": r.start_procs, "final_procs": r.final_procs,
             "resizes": [list(x) for x in r.resizes]}
            for r in res.records]


def _grid(n_jobs, max_steps, seed):
    import repro.dmr as dmr
    from repro.rms import materialize_live

    devices = _devices()

    # job worker limits are clamped to HALF the pool, mirroring the
    # paper's §5 ratio (32-worker max requests on a 128-node cluster): a
    # rigid job that requests the whole pool could never be unblocked by
    # any shrink, which would make malleability structurally useless
    # arrivals compressed to half the default span: the queue must stay
    # contended through the tail, or the last arrival dominates makespan
    # identically in every config
    def specs(mode, malleable):
        return materialize_live(SCENARIO, n_jobs=n_jobs,
                                device_count=len(devices) // 2,
                                max_steps=max_steps, mode=mode,
                                malleable=malleable, seed=seed,
                                arrival_span=n_jobs * max_steps // 6)

    rows, per_job = [], {}
    base_res = dmr.Cluster(specs("rigid", False), devices=devices,
                           policy="algorithm2").run()
    base = base_res.summary()
    rows.append(_row("static", "rigid", base, base))
    per_job["static/rigid"] = _per_job(base_res)
    for policy in POLICY_NAMES:
        for mode in MODES:
            res = dmr.Cluster(specs(mode, True), devices=devices,
                              policy=policy).run()
            rows.append(_row(policy, mode, res.summary(), base))
            per_job[f"{policy}/{mode}"] = _per_job(res)
    return rows, per_job


def _crosscheck(n_jobs, max_steps, seed):
    """Replay the smoke workload from the simulator's decisions and verify
    every runner's resize trail against resize_log — both engines."""
    import repro.dmr as dmr
    from repro.rms import ReferenceSimulator, Simulator, materialize_live

    devices = _devices()
    counts = {}
    for engine in (Simulator, ReferenceSimulator):
        specs = materialize_live(SCENARIO, n_jobs=n_jobs,
                                 device_count=len(devices) // 2,
                                 max_steps=max_steps, seed=seed)
        cl = dmr.Cluster(specs, devices=devices, policy="algorithm2",
                         decisions="cosim", engine=engine)
        res = cl.run()
        cl.crosscheck(res)                       # raises on any divergence
        counts[engine.__name__] = len(cl.simwl.resize_log)
    assert counts["Simulator"] == counts["ReferenceSimulator"], counts
    return counts


def run(n_jobs=10, max_steps=16, seed=0):
    _devices()                          # fail fast, before any work
    with timer() as t:
        rows, per_job = _grid(n_jobs, max_steps, seed)
        xc = _crosscheck(n_jobs, max_steps, seed)
    base = rows[0]
    for r in rows[1:]:
        assert r["jobs_per_s"] > base["jobs_per_s"], (
            f"{r['policy']}/{r['mode']} did not beat the rigid-static "
            f"baseline on completed jobs/s: {r['jobs_per_s']} <= "
            f"{base['jobs_per_s']}")
    path = write_csv("live_cluster", rows)
    with open(BENCH_JSON, "w") as f:
        json.dump({"n_jobs": n_jobs, "max_steps": max_steps, "seed": seed,
                   "grid": rows, "per_job_resize_logs": per_job,
                   "crosscheck_resizes": xc}, f, indent=2)
    worst = min(rows[1:], key=lambda r: r["throughput_vs_static"])
    report("live_cluster", t.seconds,
           f"worst_vs_static={worst['throughput_vs_static']}x"
           f";crosscheck_ok={xc['Simulator']}resizes"
           f";json={BENCH_JSON};csv={path}")
    return rows


# ----------------------------------------------------------------------
# trace-scale replay (scheduling only, no JAX): event vs reference
# ----------------------------------------------------------------------

def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _replay_specs(n_jobs, seed, *, trace=None, max_steps=4):
    """SWF trace -> parse_swf -> materialize_live, ready for sched_only."""
    from repro.rms.workload import (generate_synthetic_swf, materialize_live,
                                    parse_swf)
    source = trace if trace else generate_synthetic_swf(n_jobs, seed=seed)
    jobs, overrides = parse_swf(source, max_jobs=n_jobs)
    nodes = overrides["nodes"]
    # compressed arrival span: the queue must stay contended — an idle
    # scheduler measures tenant stepping, not the queue indexes
    specs = materialize_live(jobs, device_count=nodes, max_steps=max_steps,
                             arrival_span=max(1, len(jobs) * max_steps // 12))
    return specs, nodes


def _replay_once(engine_cls, specs, nodes, **kw):
    import repro.dmr as dmr
    cls = {"event": dmr.Cluster, "reference": dmr.ReferenceCluster}[engine_cls]
    cl = cls.sched_only([dataclasses.replace(s) for s in specs],
                        n_devices=nodes, policy="algorithm2",
                        record_timeline=False, audit=False,
                        max_ticks=50_000_000, **kw)
    t0 = time.perf_counter()
    res = cl.run()
    return cl, res, time.perf_counter() - t0


def _replay_identical(a, b):
    sa, sb = a.summary(), b.summary()
    sa.pop("wall_s"), sb.pop("wall_s")
    recs = lambda r: [(x.jid, x.start_tick, x.end_tick, x.start_procs,
                       x.final_procs, tuple(x.resizes)) for x in r.records]
    return sa == sb and recs(a) == recs(b)


def run_replay(speedup_jobs=100_000, million_jobs=1_000_000,
               crosscheck_jobs=20_000, seed=0, trace=None, trail_path=None):
    """The tentpole benchmark: event-cluster trace replay.

    * ``speedup_jobs``: both engines replay the same materialized trace
      with ``record_trail=True`` (same overhead on both sides, so the
      ratio stays fair); results must be bit-identical and the
      wall-clock ratio is the headline speedup.  The event engine's
      schedule trail is dumped to ``trail_path`` and re-audited from
      disk with ``repro.analysis`` — the race-detector CI artifact.
    * ``crosscheck_jobs``: the event engine replays the simulator's
      decisions (``decisions="cosim"``) and every resize trail is
      verified against the simulator's resize_log.
    * ``million_jobs``: event engine only, end-to-end scale proof
      (``0`` skips it — the smoke configuration).
    """
    from repro.analysis import audit_trail_file, dump_trail

    t_start = time.perf_counter()
    payload = {}

    specs, nodes = _replay_specs(speedup_jobs, seed, trace=trace)
    ev_cl, ev_res, ev_s = _replay_once("event", specs, nodes,
                                       record_trail=True)
    rf_cl, rf_res, rf_s = _replay_once("reference", specs, nodes,
                                       record_trail=True)
    assert _replay_identical(ev_res, rf_res), (
        "cluster engines diverged — run tests/test_cluster_equivalence")
    assert ev_cl.trail == rf_cl.trail, (
        "engines agreed on results but not on the schedule trail")
    payload["engine_speedup"] = {
        "n_jobs": len(specs), "nodes": nodes,
        "event_s": round(ev_s, 3), "reference_s": round(rf_s, 3),
        "speedup": round(rf_s / ev_s, 1),
        "jobs_per_s": round(len(specs) / ev_s, 1),
        "makespan_ticks": ev_res.makespan_ticks,
        "n_resizes": ev_res.n_resizes,
        "bit_identical": True,
    }
    derived = [f"speedup:{payload['engine_speedup']['speedup']}x"
               f"@{len(specs)}jobs"]

    # dump the event engine's trail and audit the artifact from disk —
    # the same gate CI runs via `python -m repro.analysis audit`
    trail_path = trail_path or os.path.join(
        os.path.dirname(BENCH_JSON), "experiments", "bench",
        "live_cluster_trail.json")
    os.makedirs(os.path.dirname(trail_path), exist_ok=True)
    dump_trail(ev_cl, trail_path)
    t0 = time.perf_counter()
    violations = audit_trail_file(trail_path)
    audit_s = time.perf_counter() - t0
    assert not violations, "\n".join(str(v) for v in violations)
    payload["trail_audit"] = {
        "n_events": len(ev_cl.trail), "violations": 0,
        "audit_s": round(audit_s, 3), "path": trail_path,
    }
    derived.append(f"trail:{len(ev_cl.trail)}events"
                   f"_audited_{round(audit_s, 2)}s")

    xs, xn = _replay_specs(crosscheck_jobs, seed, trace=trace)
    xcl, xres, _ = _replay_once("event", xs, xn, decisions="cosim")
    xcl.crosscheck(xres)                         # raises on any divergence
    payload["cosim_crosscheck"] = {
        "n_jobs": len(xs),
        "n_resizes_verified": len(xcl.simwl.resize_log),
    }
    derived.append(f"crosscheck_ok={len(xcl.simwl.resize_log)}resizes"
                   f"@{len(xs)}jobs")

    if million_jobs:
        ms, mn = _replay_specs(million_jobs, seed, trace=trace)
        _, mres, m_s = _replay_once("event", ms, mn)
        payload["million_job_replay"] = {
            "n_jobs": len(ms), "nodes": mn, "event_s": round(m_s, 1),
            "jobs_per_s": round(len(ms) / m_s, 1),
            "makespan_ticks": mres.makespan_ticks,
            "n_resizes": mres.n_resizes,
        }
        derived.append(f"{len(ms)}jobs:{round(m_s, 1)}s")

    payload["peak_rss_mb"] = round(_peak_rss_mb(), 1)
    # merge under "replay" so the JAX grid's results are preserved
    merged = {}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            merged = json.load(f)
    merged["replay"] = payload
    with open(BENCH_JSON, "w") as f:
        json.dump(merged, f, indent=2)
    derived.append(f"rss={payload['peak_rss_mb']}mb;json={BENCH_JSON}")
    report("cluster_replay", time.perf_counter() - t_start,
           ";".join(derived))
    return payload


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: 6 jobs, 10 steps each")
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replay", action="store_true",
                    help="trace-scale sched-only replay: 100k speedup vs "
                    "reference + cosim crosscheck + 1M event-only")
    ap.add_argument("--replay-smoke", action="store_true",
                    help="CI-sized replay: 2k-job speedup + crosscheck")
    ap.add_argument("--replay-jobs", type=int, default=None,
                    help="override the replay speedup size")
    ap.add_argument("--trace", default=None,
                    help="replay a real SWF file instead of synthetic")
    ap.add_argument("--trail-out", default=None,
                    help="where to dump the audited schedule trail "
                    "(default experiments/bench/live_cluster_trail.json)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.replay or args.replay_smoke:
        if args.replay_smoke:
            run_replay(speedup_jobs=args.replay_jobs or 2_000,
                       million_jobs=0, crosscheck_jobs=1_000,
                       seed=args.seed, trace=args.trace,
                       trail_path=args.trail_out)
        else:
            run_replay(speedup_jobs=args.replay_jobs or 100_000,
                       seed=args.seed, trace=args.trace,
                       trail_path=args.trail_out)
        return
    force_host_devices(8)
    n_jobs = args.jobs or (6 if args.smoke else 10)
    max_steps = args.steps or (10 if args.smoke else 16)
    run(n_jobs=n_jobs, max_steps=max_steps, seed=args.seed)


if __name__ == "__main__":
    main()
