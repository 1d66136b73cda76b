"""The traced decode steps' least time over their measured device time.

Each step's least time is the larger of its model FLOPs over peak FLOP/s and
the bytes it has to move over peak bytes/s: the bf16 weights once, each
sequence's cache up to its position and the new row
(``chipbench/arith.py``). The device time is that of the step's program in
the trace: the program that took the most device time, each of whose runs
is one traced step, in order. Where the profiler's buffers overflowed, the
steps before the cut count.
"""


def read(record, trace):
    if record.get("kind") != "decode" or trace is None:
        return None
    runs = list(trace.module_runs(0).values())
    if not runs:
        return None
    step = max(runs, key=lambda evs: sum(e.end - e.start for e in evs))
    least = record["traced_least_s"]
    if len(step) > len(least):
        return None
    device_s = sum(e.end - e.start for e in step) * 1e-9
    return 100.0 * sum(least[:len(step)]) / device_s
