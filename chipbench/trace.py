"""The reduction from a profiler trace to the numbers the benchmark reports.

A trace is reduced to plain intervals first (:class:`Trace`), so that the
arithmetic can be checked on a small recorded trace without a chip:

* device operations per chip: the events of each ``/device:TPU:<n>``
  plane's ``XLA Ops`` line (named by their HLO instruction, ``while.27``),
  and its ``XLA Modules`` line for whole programs;
* host spans: the harness's own ``bench.*`` annotations;
* where the profiler's buffers overflowed (``Trace Buffers Dropped`` on a
  device plane), the traced window ends: past it device events are missing.

Busy time is the union of a chip's operation intervals inside the traced
window, averaged over the chips; idle gaps are the window's stretches with
no operation on a chip, each put down to the innermost harness span open at
its middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[int, int]                 # start_ns, end_ns


@dataclasses.dataclass
class Event:
    name: str
    start: int                             # ns
    end: int                               # ns


@dataclasses.dataclass
class Trace:
    """``ops[chip]`` and ``modules[chip]``: device events; ``spans``: the
    harness's host spans, ``bench.window`` among them."""
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    spans: List[Event]
    #: plane name -> its lines' names and event counts, as read
    seen: Dict[str, List] = dataclasses.field(default_factory=dict)
    #: start of the first dropped stretch of device events, if any
    dropped: Optional[int] = None
    _inner: Optional[List] = dataclasses.field(default=None, repr=False)
    _starts: Optional[List[int]] = dataclasses.field(default=None,
                                                     repr=False)

    # -- the window ----------------------------------------------------
    def window(self) -> Interval:
        w = [s for s in self.spans if s.name == WINDOW]
        if not w:
            raise ValueError("the trace holds no bench.window span")
        end = w[0].end if self.dropped is None else min(w[0].end,
                                                        self.dropped)
        return w[0].start, end

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) * 1e-9

    # -- busy and idle ---------------------------------------------------
    def busy_intervals(self, chip: int) -> List[Interval]:
        a, b = self.window()
        return union([(max(e.start, a), min(e.end, b))
                      for e in self.ops.get(chip, [])
                      if e.end > a and e.start < b])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(covered(self.busy_intervals(c))
                   for c in self.ops) * 1e-9 / len(self.ops)

    def idle_gaps(self, chip: int) -> List[Interval]:
        a, b = self.window()
        gaps, t = [], a
        for s, e in self.busy_intervals(chip):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < b:
            gaps.append((t, b))
        return gaps

    def span_at(self, t: int) -> str:
        """The harness span open at ``t``: the host runs one at a time inside
        the window, which is the answer when none is open."""
        if self._inner is None:
            self._inner = sorted((s.start, s.end, s.name) for s in self.spans
                                 if s.name != WINDOW)
            self._starts = [s[0] for s in self._inner]
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self._inner[i][1]:
            return self._inner[i][2]
        return WINDOW

    def idle_by_span(self, top: int = 10) -> List[List]:
        """Idle seconds per chip, averaged over the chips, by the span the
        host was in; the ``top`` largest."""
        tot: Dict[str, float] = collections.defaultdict(float)
        for c in self.ops:
            for s, e in self.idle_gaps(c):
                tot[self.span_at((s + e) // 2)] += (e - s) * 1e-9
        n = max(len(self.ops), 1)
        return [[k, v / n] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def top_ops(self, top: int = 10) -> List[List]:
        """Device seconds per operation name inside the window, averaged
        over the chips; the ``top`` largest. An operation that holds others
        (a ``while`` loop and its body) counts its own time only."""
        a, b = self.window()
        tot: Dict[str, float] = collections.defaultdict(float)
        for evs in self.ops.values():
            inside = sorted(((max(e.start, a), min(e.end, b), e.name)
                             for e in evs if e.end > a and e.start < b),
                            key=lambda x: (x[0], -x[1]))
            for name, self_ns in self_times(inside):
                tot[name] += self_ns * 1e-9
        n = max(len(self.ops), 1)
        return [[k, v / n] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_span()}

    def module_runs(self, chip: int = 0) -> Dict[str, List[Event]]:
        """Whole-program executions on ``chip`` inside the window, by
        program name."""
        a, b = self.window()
        out: Dict[str, List[Event]] = collections.defaultdict(list)
        for e in self.modules.get(chip, []):
            if e.start >= a and e.end <= b:
                out[e.name].append(e)
        return dict(out)

    # -- a small trace kept as JSON (the tests' recorded trace) ----------
    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)

        def evs(lst):
            return [Event(n, int(s), int(e)) for n, s, e in lst]
        return cls(ops={int(c): evs(v) for c, v in d["ops"].items()},
                   modules={int(c): evs(v) for c, v in d["modules"].items()},
                   spans=evs(d["spans"]))


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def self_times(events):
    """``(name, own ns)`` of nested events sorted by start, longest first:
    each event's length less that of the events directly inside it."""
    stack: List[List] = []                 # [end, name, own]
    for start, end, name in events:
        while stack and stack[-1][0] <= start:
            top = stack.pop()
            yield top[1], top[2]
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    for top in reversed(stack):
        yield top[1], top[2]


def op_name(text: str) -> str:
    """``%while.27 = (s32[], ...) while(...)`` -> ``while.27``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, n_chips: Optional[int] = None) -> Trace:
    """Reads an ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    seen: Dict[str, List] = {}
    dropped: Optional[int] = None
    for plane in data.planes:
        seen[plane.name] = [[l.name, sum(1 for _ in l.events)]
                            for l in plane.lines]
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if n_chips is not None and chip >= n_chips:
                continue
            for line in plane.lines:
                if line.name == "XLA TraceMe":
                    for e in line.events:
                        if e.name == "Trace Buffers Dropped":
                            t = int(e.start_ns)
                            dropped = t if dropped is None else min(dropped,
                                                                    t)
                    continue
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                name = op_name if line.name == "XLA Ops" else str
                dest.setdefault(chip, []).extend(
                    Event(name(e.name), int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, int(e.start_ns),
                                   int(e.start_ns + e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans, seen=seen,
                 dropped=dropped)
