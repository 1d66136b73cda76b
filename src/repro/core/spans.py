"""Host spans for the profiler, and the process's compile time.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler runs it records a host event on the profiler's host plane, on the
same clock as the device planes; while none runs it costs next to nothing.
Every name starts with ``dmr.``. The arguments carry the step index, so the
spans of one step or one resize share it; nesting gives the parent.

    dmr.reconfig     the RMS query of a DMR_RECONFIG point that queries
    dmr.resize       ``MalleableRunner.apply_resize``, holding
      dmr.redistribute   one per redistribution pattern group (``pattern``)
      dmr.swap           the executable lookup or build for the new mesh
    dmr.step         ``MalleableRunner.step``
    dmr.feed         a decode replica's prompt-token upload
    dmr.advance      a decode replica's step dispatch

``COMPILE`` is the process's :class:`CompileMeter`, listening from import.
"""
from __future__ import annotations

import jax

PREFIX = "dmr."


def span(name: str, **args):
    """A host span named ``name`` (``dmr.*``), with ``args`` as its
    arguments; use it as a context manager."""
    if not name.startswith(PREFIX):
        raise ValueError(f"span names start with {PREFIX!r}: {name!r}")
    return jax.profiler.TraceAnnotation(name, **args)


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling programs in this
    process, and the number of programs XLA compiled or loaded from the
    persistent compile cache, from JAX's own monitoring events.
    ``ResizeEvent.compile_s`` reads the seconds; ``chip_smoke.py`` prints
    both for each of its phases."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event in self.DURATIONS:
            self.seconds += duration
            if event == self.BACKEND:
                self.compiles += 1


COMPILE = CompileMeter()
jax.monitoring.register_event_duration_secs_listener(COMPILE.on_duration)
