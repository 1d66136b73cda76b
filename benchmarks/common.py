"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import csv
import os
import time
from typing import Dict, Iterable, List

OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "bench")


def write_csv(name: str, rows: List[Dict], fieldnames=None):
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.csv")
    if not rows:
        return path
    fieldnames = fieldnames or list(rows[0].keys())
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=fieldnames)
        w.writeheader()
        for r in rows:
            w.writerow(r)
    return path


class timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0


def force_host_devices(n: int = 8) -> None:
    """Ask for an ``n``-device host (CPU) farm. Call it in ``main`` before
    anything touches JAX's backend; it has no effect on a TPU backend."""
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={n}").strip()


def require_devices(n: int, name: str):
    """The first ``n`` devices, or a clear error: a process that already
    holds the devices never re-launches itself to get more."""
    import jax
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"{name} needs {n} devices, JAX found {len(devices)} "
            f"{devices[0].platform} device(s); run it through its own main "
            f"(python -m benchmarks.{name}), which asks for an {n}-device "
            f"host farm before JAX starts")
    return devices[:n]


def report(name: str, seconds: float, derived: str):
    """The harness contract: ``name,us_per_call,derived`` CSV to stdout."""
    print(f"{name},{seconds*1e6:.1f},{derived}")
