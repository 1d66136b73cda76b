"""The program's marks in a trace (``chipbench/scopes.py``): the innermost
open span, operations named by their scope, the step program's per-scope
time and the three readers on them, on a small recorded trace with known
answers; the readers' silence on a trace without the marks; and the marks
found again in a traced tiny cell on the CPU (which has no device plane, so
nothing here is a device number)."""
import os

import pytest

from chipbench import harness, scopes
from chipbench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def _read(name):
    with open(os.path.join(DATA, name)) as f:
        return f.read()


@pytest.fixture
def scoped():
    return scopes.ScopedTrace.from_json(_read("scoped_trace.json"))


@pytest.fixture
def bare():
    """The harness's recorded trace, as the parent program leaves one: no
    scopes and no program spans."""
    return scopes.ScopedTrace.from_json(_read("small_trace.json"))


def reader(name):
    return harness.load_module("metrics", name)


DECODE = {"kind": "decode"}


@pytest.mark.parametrize("t, span", [
    (0, "bench.window"), (1, "dmr.reconfig"), (2, "bench.window"),
    (3, "bench.step"), (5, "dmr.step"), (10, "dmr.advance"),
    (20, "dmr.step"), (44, "bench.step"), (45, "bench.window"),
    (49, "dmr.feed"), (50, "dmr.advance"), (60, "dmr.step"),
    (81, "bench.step"), (90, "bench.token_sync"), (99, "bench.window")])
def test_span_at_is_the_innermost_open_span(scoped, t, span):
    assert scoped.span_at(t) == span


def test_a_closed_inner_span_leaves_its_parent_open(scoped):
    # dmr.advance (8-12) has closed inside dmr.step (4-44): the last span
    # started is not the one open
    plain = tr.Trace(ops=scoped.ops, modules=scoped.modules,
                     spans=scoped.spans)
    assert plain.span_at(20) == "bench.window"
    assert scoped.span_at(20) == "dmr.step"


def test_idle_goes_to_the_program_span_open(scoped):
    got = dict((k, v) for k, v in scoped.idle_by_span())
    assert got == pytest.approx({"dmr.step": 10e-9, "bench.window": 10e-9,
                                 "bench.token_sync": 17e-9})


def test_top_ops_are_named_by_innermost_scope(scoped):
    top = dict((k, v) for k, v in scoped.top_ops())
    assert top == pytest.approx({
        "attend/fusion.3": 18e-9, "copy.4": 12e-9,
        "attn_qkv/fusion.1": 11e-9, "kv_write/dynamic_update_slice.2": 9e-9,
        "sample/fusion.5": 8e-9, "copy.9": 3e-9, "layers/while.1": 2e-9})


def test_unscoped_ops_keep_their_bare_names(bare):
    plain = tr.Trace.from_json(_read("small_trace.json"))
    assert bare.top_ops() == plain.top_ops()
    assert bare.idle_by_span() == plain.idle_by_span()
    assert not bare.scoped()


def test_scope_table_sums_to_the_step_program(scoped):
    table = scoped.scope_table()
    assert table == pytest.approx({"attend": 18e-9, "": 12e-9,
                                   "attn_qkv": 11e-9, "kv_write": 9e-9,
                                   "sample": 8e-9, "layers": 2e-9})
    assert [e.name for e in scoped.step_runs()] == ["jit_step"] * 2
    assert sum(table.values()) == pytest.approx(scoped.step_device_s())
    assert scoped.step_device_s() == pytest.approx(60e-9)


def test_readers_on_the_scoped_trace(scoped):
    # outside every compute scope: layers 2 + kv_write 9 + none 12 of 60
    assert reader("cache_move_share.decode").read(DECODE, scoped) \
        == pytest.approx(100 * 23 / 60)
    assert reader("attend_share.decode").read(DECODE, scoped) \
        == pytest.approx(100 * 18 / 60)
    # dmr.reconfig spans of 1 and 3 ns
    assert reader("reconfig_us.decode").read(DECODE, scoped) \
        == pytest.approx(2e-3)
    for name in ("cache_move_share.decode", "attend_share.decode",
                 "reconfig_us.decode"):
        assert reader(name).read({"kind": "train"}, scoped) is None


NEW = ("cache_move_share.decode", "attend_share.decode", "reconfig_us.decode")


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_without_the_marks(bare, name):
    assert reader(name).read(DECODE, bare) is None
    assert reader(name).read(DECODE, None) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_find_no_trace_of_another_window(tmp_path, monkeypatch,
                                                 name):
    """Handed the harness's reduction, a reader looks for the trace file
    the harness wrote; without one, it reads nothing."""
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    plain = tr.Trace.from_json(_read("small_trace.json"))
    assert reader(name).read(DECODE, plain) is None


def test_existing_readers_read_the_same_from_the_scoped_trace(scoped):
    plain = tr.Trace(ops=scoped.ops, modules=scoped.modules,
                     spans=scoped.spans)
    rec = {"kind": "decode", "traced_least_s": [5e-9, 3e-9]}
    for name in ("mfu_roofline.decode", "idle_share.decode"):
        assert reader(name).read(rec, scoped) == reader(name).read(rec, plain)


def test_names_and_paths():
    assert scopes.innermost("jit(_advance)/layers/while/body/kv_write/"
                            "dynamic_update_slice") == "kv_write"
    assert scopes.innermost("jit(train_step)/transpose(jvp(layers))/while/"
                            "body/closed_call/ssd_scan/while") == "ssd_scan"
    assert scopes.innermost("transpose(jvp(layers))/while") == "layers"
    assert scopes.innermost("jit(f)/jit(main)/dot_general") == ""
    assert scopes.innermost("") == ""
    assert scopes.span_name("dmr.step#step=3#") == "dmr.step"
    assert scopes.span_name("dmr.step") == "dmr.step"


def test_a_traced_tiny_cell_reports_its_program_spans(tmp_path, monkeypatch):
    """The decode cell at a tiny size, traced on the CPU: the reader of the
    program's spans finds them in the trace the harness wrote; the CPU has
    no device plane, so the readers of device scopes read nothing."""
    from chipbench.tests import tiny
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    res = tiny.run("granite-decode-chat", trace=True)
    assert res["correct"]
    got = res["metrics"]
    assert got["reconfig_us.decode"]["unit"] == "us"
    assert got["reconfig_us.decode"]["value"] > 0
    assert "cache_move_share.decode" not in got
    assert "attend_share.decode" not in got
    path = harness.find_xplane(str(tmp_path))
    t = scopes.load(path)
    names = {s.name for s in t.spans}
    assert {"dmr.reconfig", "dmr.step", "dmr.advance", "bench.step"} <= names
    # the window traces steps [6, 10): the last two prompt steps feed
    assert len(t.spans_named("dmr.reconfig")) == 4
    assert len(t.spans_named("dmr.feed")) == 2
    step = t.spans_named("dmr.step")[0]
    assert t.span_at((step.start + step.end) // 2) in ("dmr.step",
                                                       "dmr.feed",
                                                       "dmr.advance")


def test_op_names_come_from_the_programs_hlo_in_the_trace(tmp_path):
    """The profiler keeps each program's optimized HLO in the trace; every
    operation the CPU ran is found there with its scope path."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("layers"):
            y = x @ x
        with jax.named_scope("attend"):
            return jnp.tanh(y).sum()

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = harness.find_xplane(str(tmp_path))
    with open(path, "rb") as fh:
        raw = fh.read()
    protos = scopes.hlo_protos(raw)
    program = next(p for p in protos if p.startswith("jit_f("))
    names = scopes.hlo_op_names(raw, protos[program])
    from jax.profiler import ProfileData
    ran = {dict(e.stats)["hlo_op"]
           for plane in ProfileData.from_serialized_xspace(raw).planes
           for line in plane.lines for e in line.events
           if dict(e.stats).get("hlo_module") == "jit_f"}
    assert ran and ran <= set(names)
    assert {scopes.innermost(names[op]) for op in ran} \
        == {"layers", "attend"}
