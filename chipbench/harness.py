"""The harness: finds a cell's files by name, drives its kind, prints the result.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration's file holds the sizes and the name of its plain reference
(``refs/<arch>.py``); the traffic file names the kind of run that reads it
(``kinds/<kind>.py``); each per-layer metric is read by ``metrics/<name>.py``.
Adding a cell, a mix, a configuration or a metric adds files and entries and
edits none.

A kind's ``run(ctx)`` builds the system under test, warms every shape the
window uses, calls ``ctx.setup_done()``, runs the window inside
``with ctx.window():`` (calling ``ctx.start_trace()`` and
``ctx.end_trace()`` around the part a traced run traces), reads the peak
memory, frees the program's state, and compares what the window produced
with its reference. It returns an :class:`Outcome`.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

#: the benchmark's own tree, and the checkout that holds it
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: traces and other run output; ignored by ``chipbench/.gitignore``
OUT = os.path.join(HERE, "out")

SPAN_WINDOW = "bench.window"


# ----------------------------------------------------------------------
# finding things by name
# ----------------------------------------------------------------------

def load_bench(root: str = ROOT, pending: bool = False) -> Dict:
    """``BENCHMARK.json``; with ``pending``, also the entries of the cells
    measured and not admitted (``chipbench/pending.json``), which only the
    calibration and the tests read."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    if pending:
        more = load_json(os.path.join(root, "chipbench", "pending.json"))
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[group] = bench[group] + more[group]
    return bench


def _by_name(entries: Sequence[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold ``.`` and
    ``-``, so they are loaded by path, not imported by name)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no {kind} file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs, resolved from its names."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def metrics_of_cell(bench: Dict, group: str, cell: str) -> List[Dict]:
    """The metrics of ``group`` that ``cell`` reports: those that list it,
    and those that list no cells at all. A per-layer metric without a list
    goes where the end-to-end metric it moves is reported."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def resolve_cell(bench: Dict, name: str, root: str = ROOT) -> Cell:
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(os.path.join(root, c["file"])),
                traffic=load_json(traffic_path(w["traffic"])),
                end_to_end=metrics_of_cell(bench, "end_to_end", name),
                per_layer=metrics_of_cell(bench, "per_layer", name))


# ----------------------------------------------------------------------
# what a kind returns
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``value <= limit`` passes."""
    name: str
    value: float
    limit: Optional[float]      # None: not set yet, and nothing passes

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    #: end-to-end metrics other than ``setup_s``: name -> value
    metrics: Dict[str, float]
    checks: List[Check]
    memory_peak_bytes: int
    #: what the per-layer readers read: timings, counters, the device peaks
    record: Dict[str, Any]


# ----------------------------------------------------------------------
# the run context handed to a kind
# ----------------------------------------------------------------------

class CompileCounter:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    programs it builds or loads, from its own monitoring events."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.DURATIONS:
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "traces": self.traces, "cache_hits": self.cache_hits}


class Context:
    """What a kind gets: the cell, the seed, the devices, the clocks."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: List, t0: float, peaks: Dict[str, float], *,
                 log=None, trace_dir: Optional[str] = None):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = list(devices)
        self.t0 = t0
        #: the chip's published peaks (``peaks.py``), for the readers
        self.peaks = peaks
        self.log = log or (lambda rec: print(json.dumps(rec), flush=True))
        self.trace_dir = trace_dir or os.path.join(OUT, "trace", cell.name)
        self.compiles = CompileCounter()
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.window_compiles: Dict[str, float] = {}
        self.trace_path: Optional[str] = None
        self._span = None
        #: seconds spent starting and stopping the profiler, which the
        #: window's clocks leave out
        self.trace_pause_s = 0.0
        self._setup_compiles: Dict[str, float] = {}

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (nearly free when off)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t0
        self._setup_compiles = self.compiles.snapshot()

    @contextlib.contextmanager
    def window(self):
        """The measured window, with compile counting. Nothing may compile
        inside it."""
        if self.setup_s is None:
            self.setup_done()
        before = self.compiles.snapshot()
        t = time.perf_counter()
        try:
            yield
        finally:
            self.end_trace()
            self.window_s = time.perf_counter() - t - self.trace_pause_s
            after = self.compiles.snapshot()
            self.window_compiles = {k: after[k] - before[k] for k in after}

    def start_trace(self) -> float:
        """Starts the profiler when ``--trace 1``; returns the seconds that
        took. The traced window runs from here to :meth:`end_trace`: a part
        of the window (one cycle, a few dozen decode steps) small enough
        that every device event fits the profiler's buffers."""
        import jax
        if not self.trace or self._span is not None or self.trace_path:
            return 0.0
        t = time.perf_counter()
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0           # the harness's spans suffice
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = self.span(SPAN_WINDOW)
        self._span.__enter__()
        took = time.perf_counter() - t
        self.trace_pause_s += took
        return took

    def end_trace(self) -> float:
        """Stops the profiler; returns the seconds that took."""
        import jax
        if self._span is None:
            return 0.0
        t = time.perf_counter()
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        self.trace_path = find_xplane(self.trace_dir)
        took = time.perf_counter() - t
        self.trace_pause_s += took
        return took

    def setup_line(self) -> Dict:
        return {"setup_s": self.setup_s, "window_s": self.window_s,
                "setup_compile": self._setup_compiles,
                "window_compile": self.window_compiles}


def find_xplane(d: str) -> Optional[str]:
    found = []
    for base, _, files in os.walk(d):
        found += [os.path.join(base, f) for f in files
                  if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip, as JAX reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------

def describe(devices) -> Dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def read_per_layer(cell: Cell, outcome: Outcome, trace) -> Dict[str, Dict]:
    """Each per-layer metric's reader; a reader that finds nothing returns
    ``None`` and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = load_module("metrics", m["name"])
        value = reader.read(outcome.record, trace)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: List, t0: float, peaks: Dict[str, float], *, log=None,
             trace_dir: Optional[str] = None) -> Dict:
    """Runs one cell once and returns the result object (the last line)."""
    from chipbench import trace as tr
    kind = load_module("kinds", cell.traffic["kind"])
    ctx = Context(cell, seed, seconds, trace, devices, t0, peaks, log=log,
                  trace_dir=trace_dir)
    outcome: Outcome = kind.run(ctx)
    ctx.log({"setup": ctx.setup_line()})

    device = describe(devices)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in outcome.checks) and bool(outcome.checks),
        "attempted": outcome.attempted, "failed": outcome.failed}
    if trace:
        t = time.perf_counter()
        reduced = tr.load(ctx.trace_path, n_chips=len(devices))
        ctx.log({"trace": {"path": os.path.relpath(ctx.trace_path, ROOT),
                           "planes": reduced.seen, "dropped": reduced.dropped,
                           "read_s": time.perf_counter() - t}})
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        result["metrics"] = read_per_layer(cell, outcome, reduced)
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
    else:
        metrics = {m["name"]: {"value": outcome.metrics[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    return result


def emit(result: Dict) -> None:
    """The checks as the last lines of stderr, then the result as the last
    line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def parse_args(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="chipbench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None, t0: Optional[float] = None
         ) -> int:
    from chipbench.peaks import peaks_for
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    bench = load_bench()
    cell = resolve_cell(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devices[0].platform}); "
              f"nothing run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = dataclasses.asdict(peaks_for(devices[0].device_kind))
    from repro.launch.device import enable_compile_cache
    cache = enable_compile_cache()
    # every program of a cell, however small, is found again by its next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(json.dumps({"device": describe(devices[:cell.chips]),
                      "compile_cache": cache, "jax": jax.__version__}),
          flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], t0, peaks)
    emit(result)
    return 0
