"""The reduction from a trace to busy time, idle gaps by host span, and the
top operations: on a small trace kept in this tree with known answers, and
on one recorded on the CPU here (whose host spans the reader must find; the
CPU has no device plane, so nothing here is a device number)."""
import os

import pytest

from chipbench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")


@pytest.fixture
def small():
    with open(DATA) as f:
        return tr.Trace.from_json(f.read())


def test_union_and_cover():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert tr.covered(tr.union([(0, 10), (5, 15), (20, 21)])) == 16


def test_busy_is_the_union_of_op_intervals_in_the_window(small):
    assert small.window() == (0, 100)
    assert small.busy_intervals(0) == [(10, 30), (50, 60)]   # 120-130 is out
    assert small.busy_intervals(1) == [(0, 100)]
    assert small.busy_s() == pytest.approx((30 + 100) / 2 * 1e-9)
    assert small.window_s() == pytest.approx(100e-9)


def test_idle_gaps_go_to_the_span_open_at_their_middle(small):
    assert small.idle_gaps(0) == [(0, 10), (30, 50), (60, 100)]
    assert small.idle_gaps(1) == []
    assert small.span_at(5) == "bench.step"
    assert small.span_at(57) == "bench.window"
    got = dict((k, v) for k, v in small.idle_by_span())
    assert got == pytest.approx({"bench.step": 5e-9,
                                 "bench.token_sync": 10e-9,
                                 "bench.reconfig": 20e-9})


def test_top_ops_count_own_time_averaged_over_chips(small):
    top = dict((k, v) for k, v in small.top_ops())
    # while.1 spans 10-30 and holds fusion.1 (10-20) and fusion.2 (22-30)
    assert top == pytest.approx({"all-reduce.4": 50e-9, "fusion.1": 5e-9,
                                 "copy.3": 5e-9, "fusion.2": 4e-9,
                                 "while.1": 1e-9})
    b = small.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}


def test_dropped_events_end_the_window(small):
    small.dropped = 55
    assert small.window() == (0, 55)
    assert small.busy_intervals(0) == [(10, 30), (50, 55)]


def test_op_names():
    assert tr.op_name("%while.27 = (s32[], bf16[8]) while(%t), body=%b") \
        == "while.27"
    assert tr.op_name("fusion.3") == "fusion.3"


def test_module_runs_inside_the_window(small):
    runs = small.module_runs(0)
    assert {k: len(v) for k, v in runs.items()} == {"jit_step": 2,
                                                    "jit_put": 1}


def test_roofline_reader_times_the_step_program(small):
    from chipbench import harness
    reader = harness.load_module("metrics", "mfu_roofline.decode")
    # jit_step, the program with most device time, ran twice in the window
    assert reader.read({"kind": "decode", "traced_least_s": [1e-9]},
                       small) is None
    rec = {"kind": "decode", "traced_least_s": [5e-9, 3e-9, 1.0]}
    assert reader.read(rec, small) == pytest.approx(100 * 8e-9 / 30e-9)
    small.dropped = 40               # only the first traced step counts
    assert reader.read(rec, small) == pytest.approx(100 * 5e-9 / 20e-9)


def test_reads_host_spans_from_a_cpu_recording(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    from chipbench.harness import find_xplane
    t = tr.load(find_xplane(str(tmp_path)))
    names = [s.name for s in t.spans]
    assert names.count("bench.step") == 3 and tr.WINDOW in names
    a, b = t.window()
    assert b > a
    assert all(a <= s.start and s.end <= b for s in t.spans
               if s.name == "bench.step")
    assert t.ops == {}        # no device plane on the CPU
    assert t.busy_s() == 0.0
