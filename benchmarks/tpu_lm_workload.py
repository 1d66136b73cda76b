"""Beyond-paper — DMR malleability for LM pretraining on a TPU cluster.

The 10 assigned architectures become malleable pretraining jobs on a
512-chip (2-pod) cluster. Per-job execution model: analytic model FLOPs for
train_4k / (chips x 197 TFLOP/s x MFU(p)), with MFU anchored to the dry-run
roofline table when present (experiments/dryrun/*.json) and an ICI-efficiency
rolloff for larger slices. Slice-granular allocation (multiples of 64 chips).
"""
from __future__ import annotations

import glob
import json
import os

import numpy as np

from benchmarks.common import report, timer, write_csv
from repro.configs import SHAPES_BY_NAME, all_configs
from repro.core.params import MalleabilityParams
from repro.launch.roofline import chip_peaks, model_flops
from repro.rms import SimConfig, Simulator
from repro.rms.workload import AppProfile, Job, feitelson_arrivals

CHIPS = 512
SLICE = 64
STEPS = 500                     # pretraining segment per job
FALLBACK_MFU = 0.30
PEAK_FLOPS = chip_peaks("TPU v5 lite").flops


def _anchored_mfu(arch: str) -> float:
    pats = glob.glob(f"experiments/dryrun/{arch}__train_4k__pod16x16.json")
    if pats:
        with open(pats[0]) as f:
            return max(json.load(f)["roofline"]["mfu"], 0.01)
    return FALLBACK_MFU


def make_lm_profiles():
    shape = SHAPES_BY_NAME["train_4k"]
    profiles = {}
    for name, cfg in all_configs().items():
        mf = model_flops(cfg, shape)
        mfu256 = _anchored_mfu(name)
        # t(p) with ICI rolloff: eff(p) = 1 / (1 + 0.15*log2(p/64))
        def exec_time(p, mf=mf, mfu=mfu256):
            eff = 1.0 / (1.0 + 0.15 * max(np.log2(p / 64), 0))
            return STEPS * mf / (p * PEAK_FLOPS * mfu * eff)
        t64 = exec_time(64)
        t128, t256 = exec_time(128), exec_time(256)
        # fit the AppProfile power-law through (64, 256)
        alpha = float(np.log(t64 / t256) / np.log(256 / 64))
        profiles[name] = AppProfile(
            name=name, t1=t64 * 64 ** alpha, f=1.0, alpha=alpha, c=0.0,
            min_start=SLICE,
            params=MalleabilityParams(64, 512, 256, sched_period_s=30.0),
            state_mb=16.0 * 2 ** 30 / 1e6 * 0.6,   # ~60% HBM of a chip, per chip
            iterations=STEPS)
    return profiles


def run(n_jobs=120):
    profiles = make_lm_profiles()
    rows = []
    rng = np.random.default_rng(0)
    names = list(profiles)
    with timer() as t:
        summaries = {}
        for mold, mall, label in ((False, False, "fixed"),
                                  (True, True, "flexible")):
            arrivals = feitelson_arrivals(n_jobs, rng=np.random.default_rng(7),
                                          mean_s=120.0)
            jobs = []
            picks = np.random.default_rng(3).integers(0, len(names), n_jobs)
            for i in range(n_jobs):
                jobs.append(Job(jid=i, app=profiles[names[picks[i]]],
                                submit_time=float(arrivals[i]),
                                moldable=mold, malleable=mall))
            cfg = SimConfig(nodes=CHIPS, idle_w=55.0, loaded_w=170.0,
                            bandwidth_gbps=400.0, record_timeline=False)
            s = Simulator(jobs, cfg).run().summary()
            summaries[label] = s
            rows.append(dict(workload=label, **{k: round(v, 3)
                                                for k, v in s.items()}))
    path = write_csv("tpu_lm_workload", rows)
    spd = summaries["fixed"]["mean_completion_s"] / \
        summaries["flexible"]["mean_completion_s"]
    esave = 1 - summaries["flexible"]["energy_kwh"] / \
        summaries["fixed"]["energy_kwh"]
    report("tpu_lm_workload", t.seconds,
           f"completion_speedup={spd:.2f}x;energy_saved={esave:.1%};csv={path}")


if __name__ == "__main__":
    run()
