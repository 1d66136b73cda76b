"""The program's own marks in a profiler trace: its ``dmr.*`` host spans and
the named scope of each device operation.

The program names its host spans ``dmr.*`` (``repro.core.spans``) and wraps
the parts of its step in ``jax.named_scope`` (``layers``, ``attend``, ...).
XLA keeps the scope path in each instruction's ``op_name`` metadata
(``jit(_advance)/layers/while/body/kv_write/dynamic_update_slice``).

Where the trace holds it: on a TPU v5e under JAX 0.9.0, an ``XLA Ops``
event carries no op_name. Its stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, and its name is the
instruction's HLO text without metadata. The profiler keeps each program's
optimized HLO instead, as the ``Hlo Proto`` stat of an event metadata entry
of the ``/host:metadata`` plane, named as the program's ``XLA Modules``
events are (``jit__advance(<id>)``); :func:`hlo_op_names` reads those
protos, which JAX's ``ProfileData`` does not expose, straight from the
protobuf wire format.

:class:`ScopedTrace` is a :class:`chipbench.trace.Trace` that also holds
those marks:

* ``span_at`` answers the innermost span open at a time, ``bench.*`` or
  ``dmr.*``, so idle gaps go to the program's span where one is open;
* ``top_ops`` names each operation by its innermost model scope,
  ``kv_write/dynamic_update_slice.26`` (bare where it has none);
* ``scope_table`` splits the step program's device time by innermost scope.

The harness reduces a traced run with ``chipbench.trace``; the readers of
the program's marks find the same ``.xplane.pb`` again with :func:`of`.

    python3 chipbench/scopes.py <trace.xplane.pb>

prints the step program's per-scope table, the top scoped operations and
the idle time by innermost span, as one JSON object.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace as tr  # noqa: E402

SPAN_PREFIXES = ("bench.", "dmr.")
#: the model's named scopes (``models/``, ``optim/``)
SCOPES = ("layers", "attn_qkv", "kv_write", "attend", "attn_out", "mlp",
          "norm", "embed", "unembed", "sample", "ssd_scan", "optimizer")
#: scopes that compute; the step's other time moves or copies the cache
COMPUTE = frozenset({"attn_qkv", "attend", "attn_out", "mlp", "norm",
                     "embed", "unembed", "sample"})
#: ``transpose(jvp(layers))`` -> ``layers``: a scope under differentiation
_WRAPPED = re.compile(r"^(?:[\w.]+\()+|\)+$")


@dataclasses.dataclass
class Op(tr.Event):
    #: the op_name path of the instruction, "" where the trace has none
    scope: str = ""


def innermost(path: str) -> str:
    """The innermost model scope of an op_name path, "" for none."""
    for part in reversed(path.split("/")):
        part = _WRAPPED.sub("", part)
        if part in SCOPES:
            return part
    return ""


def span_name(name: str) -> str:
    """``dmr.step#step=3#`` -> ``dmr.step``: arguments a profiler encodes
    into the name are not part of it."""
    return name.split("#", 1)[0]


@dataclasses.dataclass
class ScopedTrace(tr.Trace):
    """A trace whose ``spans`` hold the program's ``dmr.*`` spans beside the
    harness's, and whose operations are :class:`Op`s with their op_name."""
    _segments: Optional[Tuple[List[int], List[str]]] = dataclasses.field(
        default=None, repr=False)
    _tables: Dict[int, Dict[str, float]] = dataclasses.field(
        default_factory=dict, repr=False)

    # -- spans -----------------------------------------------------------
    def span_at(self, t: int) -> str:
        """The innermost span open at ``t`` (the latest started of those
        open), ``bench.window`` where none is."""
        if self._segments is None:
            self._segments = self._timeline()
        times, names = self._segments
        i = bisect.bisect_right(times, t) - 1
        return names[i] if i >= 0 else tr.WINDOW

    def _timeline(self) -> Tuple[List[int], List[str]]:
        """Times at which the innermost open span changes, and the span
        from each on. At one time, spans close before others open."""
        marks = []
        for k, s in enumerate(self.spans):
            if s.name != tr.WINDOW:
                marks += [(s.start, 1, s.start, k), (s.end, 0, s.start, k)]
        marks.sort()
        #: open spans as (start, -end, k), sorted: the innermost is last
        open_: List[Tuple[int, int, int]] = []
        times: List[int] = []
        names: List[str] = []
        for t, opens, start, k in marks:
            entry = (start, -self.spans[k].end, k)
            if opens:
                bisect.insort(open_, entry)
            else:
                open_.remove(entry)
            name = self.spans[open_[-1][2]].name if open_ else tr.WINDOW
            if times and times[-1] == t:
                names[-1] = name
            else:
                times.append(t)
                names.append(name)
        return times, names

    def spans_named(self, name: str) -> List[tr.Event]:
        """The spans called ``name`` inside the traced window."""
        a, b = self.window()
        return [s for s in self.spans
                if s.name == name and a <= s.start and s.end <= b]

    # -- operations ------------------------------------------------------
    def scoped(self) -> bool:
        return any(e.scope for evs in self.ops.values() for e in evs)

    def _own(self, events) -> List[Tuple[Op, int]]:
        inside = sorted(((e.start, e.end, e) for e in events),
                        key=lambda x: (x[0], -x[1]))
        return list(tr.self_times(inside))

    def top_ops(self, top: int = 10) -> List[List]:
        """As :meth:`chipbench.trace.Trace.top_ops`, each operation named
        ``<innermost scope>/<name>``."""
        a, b = self.window()
        tot: Dict[str, float] = collections.defaultdict(float)
        for evs in self.ops.values():
            clipped = [Op(e.name, max(e.start, a), min(e.end, b), e.scope)
                       for e in evs if e.end > a and e.start < b]
            for e, own in self._own(clipped):
                scope = innermost(e.scope)
                tot[f"{scope}/{e.name}" if scope else e.name] += own * 1e-9
        n = max(len(self.ops), 1)
        return [[k, v / n] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def step_runs(self, chip: int = 0) -> List[tr.Event]:
        """The runs of the step program: the program with most device time
        in the window (as ``mfu_roofline.decode`` picks it)."""
        runs = list(self.module_runs(chip).values())
        if not runs:
            return []
        return max(runs, key=lambda evs: sum(e.end - e.start for e in evs))

    def step_ops(self, chip: int = 0) -> List[Tuple[Op, int]]:
        """``(op, own ns)`` of every operation inside a run of the step
        program."""
        runs = sorted((e.start, e.end) for e in self.step_runs(chip))
        starts = [s for s, _ in runs]
        inside = []
        for e in self.ops.get(chip, []):
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= runs[i][1]:
                inside.append(e)
        return self._own(inside)

    def scope_table(self, chip: int = 0) -> Dict[str, float]:
        """Own device seconds of the step program's operations by innermost
        scope ("" for operations with none), largest first."""
        if chip not in self._tables:
            tot: Dict[str, float] = collections.defaultdict(float)
            for e, own in self.step_ops(chip):
                tot[innermost(e.scope)] += own * 1e-9
            self._tables[chip] = dict(sorted(tot.items(),
                                             key=lambda kv: -kv[1]))
        return self._tables[chip]

    def step_device_s(self, chip: int = 0) -> float:
        return sum(e.end - e.start for e in self.step_runs(chip)) * 1e-9

    # -- a small trace kept as JSON (the tests' recorded trace) ----------
    @classmethod
    def from_json(cls, text: str) -> "ScopedTrace":
        """As :meth:`chipbench.trace.Trace.from_json`; an operation may add
        its op_name path as a fourth entry."""
        d = json.loads(text)

        def evs(lst, kind=tr.Event):
            return [kind(e[0], int(e[1]), int(e[2]), *e[3:]) for e in lst]
        return cls(ops={int(c): evs(v, Op) for c, v in d["ops"].items()},
                   modules={int(c): evs(v) for c, v in d["modules"].items()},
                   spans=evs(d["spans"]))


# -- the HLO protos of the /host:metadata plane (protobuf wire format) ----
# field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map
# entry: key 1, value 2), .stat_metadata 5; XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: Optional[int] = None):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``(start, end)`` span for a length-delimited field, None for
    a fixed-width one."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _field(buf: bytes, span, number: int):
    return next((v for n, v in _fields(buf, *span) if n == number), None)


def hlo_protos(buf: bytes) -> Dict[str, Tuple[int, int]]:
    """Program name -> the span of its ``HloProto`` in ``buf``, from the
    ``/host:metadata`` plane of a serialized XSpace."""
    out: Dict[str, Tuple[int, int]] = {}
    for n, plane in _fields(buf):
        if n != 1:
            continue
        name = _field(buf, plane, 2)       # written before the big fields
        if name is None or _text(buf, name) != "/host:metadata":
            continue
        entries = list(_fields(buf, *plane))
        hlo_ids = {_field(buf, v, 1) for n, e in entries if n == 5
                   for k, v in _fields(buf, *e) if k == 2
                   and _text(buf, _field(buf, v, 2) or (0, 0)) == "Hlo Proto"}
        for n, e in entries:
            meta = _field(buf, e, 2) if n == 4 else None
            if meta is None:
                continue
            prog, proto = "", None
            for k, v in _fields(buf, *meta):
                if k == 2:
                    prog = _text(buf, v)
                elif k == 5 and _field(buf, v, 1) in hlo_ids:
                    proto = _field(buf, v, 6)
            if proto is not None:
                out[prog] = proto
    return out


def hlo_op_names(buf: bytes, span: Tuple[int, int]) -> Dict[str, str]:
    """HLO instruction name -> its op_name, over every computation of the
    ``HloProto`` at ``span``. An instruction without one (a fusion XLA
    built) takes that of the root of the computation it calls."""
    insts: Dict[int, Tuple[str, str, List[int]]] = {}   # id -> name, op, calls
    roots: Dict[int, int] = {}                           # computation -> root
    module = _field(buf, span, 1)
    for n, comp in _fields(buf, *(module or (0, 0))):
        if n != 3:
            continue
        comp_id = root = None
        for k, v in _fields(buf, *comp):
            if k == 2:
                name, op, calls, iid = "", "", [], None
                for j, w in _fields(buf, *v):
                    if j == 1:
                        name = _text(buf, w)
                    elif j == 7:
                        op = _text(buf, _field(buf, w, 2) or (0, 0))
                    elif j == 35:
                        iid = w
                    elif j == 38 and isinstance(w, tuple):   # packed
                        i = w[0]
                        while i < w[1]:
                            c, i = _varint(buf, i)
                            calls.append(c)
                    elif j == 38:
                        calls.append(w)
                insts[iid] = (name, op, calls)
            elif k == 5:
                comp_id = v
            elif k == 6:
                root = v
        roots[comp_id] = root

    def op_of(iid, depth=0) -> str:
        name, op, calls = insts.get(iid, ("", "", []))
        if op or depth > 4:
            return op
        return next((o for c in calls
                     if (o := op_of(roots.get(c), depth + 1))), "")
    return {name: op_of(iid) for iid, (name, _, _) in insts.items()}


def load(path: str, n_chips: Optional[int] = None) -> ScopedTrace:
    """Reads an ``.xplane.pb`` as :func:`chipbench.trace.load` does, with
    the ``dmr.*`` spans and each operation's op_name path, from the HLO of
    the program whose run it lies in."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    protos = hlo_protos(raw)
    programs: Dict[str, Dict[str, str]] = {}

    def op_names(program: str) -> Dict[str, str]:
        if program not in programs:
            programs[program] = (hlo_op_names(raw, protos[program])
                                 if program in protos else {})
        return programs[program]

    data = ProfileData.from_serialized_xspace(raw)
    ops: Dict[int, List[Op]] = {}
    modules: Dict[int, List[tr.Event]] = {}
    spans: List[tr.Event] = []
    seen: Dict[str, List] = {}
    dropped: Optional[int] = None
    for plane in data.planes:
        lines = {l.name: l for l in plane.lines}
        seen[plane.name] = [[n, sum(1 for _ in l.events)]
                            for n, l in lines.items()]
        m = tr._DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if n_chips is not None and chip >= n_chips:
                continue
            if "XLA TraceMe" in lines:
                for e in lines["XLA TraceMe"].events:
                    if e.name == "Trace Buffers Dropped":
                        t = int(e.start_ns)
                        dropped = t if dropped is None else min(dropped, t)
            mods = [tr.Event(str(e.name), int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                    for e in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else [])]
            modules.setdefault(chip, []).extend(mods)
            order = sorted(mods, key=lambda e: e.start)
            starts = [e.start for e in order]
            out = ops.setdefault(chip, [])
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else []):
                name, start = tr.op_name(e.name), int(e.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                scope = op_names(order[i].name).get(name, "") if i >= 0 \
                    else ""
                out.append(Op(name, start, int(start + e.duration_ns),
                              scope))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(tr.Event(span_name(e.name), int(e.start_ns),
                                      int(e.start_ns + e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return ScopedTrace(ops=ops, modules=modules, spans=spans, seen=seen,
                       dropped=dropped)


_LOADED: Dict[Tuple[str, float], ScopedTrace] = {}


def of(trace) -> Optional[ScopedTrace]:
    """The program's marks for the trace a reader is handed: the trace
    itself where it holds them, else the newest trace the harness wrote
    (``chipbench/out/trace``), read once, if its window is the same."""
    if trace is None or isinstance(trace, ScopedTrace):
        return trace
    from chipbench import harness
    path = harness.find_xplane(os.path.join(harness.OUT, "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path, n_chips=len(trace.ops) or None)
    scoped = _LOADED[key]
    try:
        same = scoped.window() == trace.window()
    except ValueError:
        return None
    return scoped if same else None


def report(t: ScopedTrace, chip: int = 0) -> Dict:
    """The step program's per-scope table, its operations with their own
    seconds and op_name paths, and the scoped breakdown."""
    table = t.scope_table(chip)
    step_s = t.step_device_s(chip)
    own: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for e, ns in t.step_ops(chip):
        own[(e.name, e.scope)] += ns * 1e-9
    return {"step_device_s": step_s, "step_runs": len(t.step_runs(chip)),
            "scopes": {k or "(none)": [v, 100.0 * v / step_s if step_s
                                       else None]
                       for k, v in table.items()},
            "scopes_sum_s": sum(table.values()),
            "step_ops": [[n, v, path] for (n, path), v in
                         sorted(own.items(), key=lambda kv: -kv[1])[:40]],
            "device_ops": t.top_ops(30),
            "idle_gaps": t.idle_by_span(),
            "spans": {n: [len(t.spans_named(n)),
                          sum(s.end - s.start for s in t.spans_named(n))
                          * 1e-9]
                      for n in sorted({s.name for s in t.spans})}}


if __name__ == "__main__":
    print(json.dumps(report(load(sys.argv[1]))))
