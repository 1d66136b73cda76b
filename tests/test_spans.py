"""Host spans, named scopes and the compile meter: a CPU profiler recording
of a resize through ``MalleableRunner``, ``ResizeEvent.compile_s`` on a new
and on a known size, and the scope names a compiled decode step carries."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.spans import COMPILE, span
from repro.models import model as M
from repro.models.train import make_serve_step, make_train_step
from repro.optim import AdamW
from tests.util import run_devices


def test_span_names_start_with_dmr():
    with span("dmr.test", step=1):
        pass
    with pytest.raises(ValueError, match="dmr."):
        span("step")


def test_compile_meter_counts_a_new_program():
    x = jnp.ones((17,))
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + x.shape[0])
    before = COMPILE.seconds, COMPILE.compiles
    f(x).block_until_ready()
    assert COMPILE.seconds > before[0]
    assert COMPILE.compiles == before[1] + 1
    again = COMPILE.seconds, COMPILE.compiles
    f(x).block_until_ready()                    # cached: nothing compiles
    assert (COMPILE.seconds, COMPILE.compiles) == again


RESIZE = r"""
import glob, json, tempfile
import jax, jax.numpy as jnp
from jax.profiler import ProfileData
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import dmr

app = dmr.App(name="toy")

@app.shardings
def shardings(mesh):
    return {"x": NamedSharding(mesh, P(("data", "model")))}

@app.init
def init(mesh):
    return jax.device_put({"x": jnp.arange(8.0)}, shardings(mesh))

@app.step
def step(mesh):
    f = jax.jit(lambda s: {"x": s["x"] * 2.0 + 1.0},
                out_shardings=shardings(mesh))
    return lambda state, i: (f(state), None)

runner = dmr.MalleableRunner(app, dmr.set_parameters(1, 2, 1),
                             rms={1: 2, 3: 1, 5: 2})
state = runner.init()
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
for i in range(7):
    state = dmr.reconfig(runner, state, i)
    state, _ = runner.step(state, i)
jax.profiler.stop_trace()
spans = []
data = ProfileData.from_file(glob.glob(d + "/**/*.xplane.pb",
                                       recursive=True)[0])
for plane in data.planes:
    for line in plane.lines:
        spans += [[e.name, e.start_ns, e.end_ns, dict(e.stats)]
                  for e in line.events if e.name.startswith("dmr.")]
print("RESULT", json.dumps({
    "events": [[e.step, e.to_procs, e.compile_s, e.recompile_s]
               for e in runner.events],
    "x": jax.device_get(state["x"]).tolist(), "spans": spans}))
"""


@pytest.fixture(scope="module")
def resized():
    import json
    out = run_devices(RESIZE, n_devices=2)
    line = next(l for l in out.splitlines() if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_resize_compile_s_counts_the_first_step_on_a_new_size(resized):
    (s1, n1, c1, _), (s3, n3, c3, _), (s5, n5, c5, _) = resized["events"]
    assert (s1, n1, s3, n3, s5, n5) == (1, 2, 3, 1, 5, 2)
    assert c1 > 0                     # size 2 never ran: its step compiled
    assert c3 == 0.0 and c5 == 0.0    # both sizes had run
    x = 0.0
    for _ in range(7):
        x = x * 2 + 1
    assert resized["x"] == [i * 128.0 + x for i in range(8)]


def test_resize_spans_nest_and_carry_the_step(resized):
    spans = resized["spans"]
    steps = [s for s in spans if s[0] == "dmr.step"]
    assert [s[3]["step"] for s in steps] == list(range(7))
    assert [s[3]["step"] for s in spans if s[0] == "dmr.reconfig"] \
        == list(range(7))
    resizes = [s for s in spans if s[0] == "dmr.resize"]
    assert [(s[3]["step"], s[3]["procs"]) for s in resizes] \
        == [(1, 2), (3, 1), (5, 2)]
    for name, a, b, args in resizes:
        inside = [s for s in spans if a <= s[1] and s[2] <= b
                  and s[0] != "dmr.resize"]
        assert sorted(s[0] for s in inside) == ["dmr.redistribute",
                                                "dmr.swap"]
        assert [s[3] for s in inside if s[0] == "dmr.redistribute"] \
            == [{"pattern": "default"}]
        assert [s[3]["step"] for s in inside if s[0] == "dmr.swap"] \
            == [args["step"]]
        # the resize's first step runs after it, outside it
        assert not [s for s in steps if a <= s[1] < b]


def _toy_runner(scale: float):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import dmr
    app = dmr.App(name=f"toy{scale}")

    @app.shardings
    def shardings(mesh):
        return {"x": NamedSharding(mesh, P())}

    @app.init
    def init(mesh):
        return jax.device_put({"x": jnp.arange(8.0)}, shardings(mesh))

    @app.step
    def step(mesh):
        f = jax.jit(lambda s: {"x": jnp.cos(s["x"]) * scale})
        return lambda state, i: (f(state), None)
    return dmr.MalleableRunner(app, dmr.set_parameters(1, 1, 1), rms={})


def test_resize_compile_s_leaves_out_another_runners_compile():
    from repro.core.policy import Action
    a, b = _toy_runner(2.0), _toy_runner(3.0)
    state_a = a.init()
    before = COMPILE.seconds
    state_a = a.apply_resize(state_a, 0, Action("shrink", 1), force=True)
    in_resize = COMPILE.seconds - before
    before = COMPILE.seconds          # runner b compiles its first step
    b.step(b.init(), 0)
    in_b = COMPILE.seconds - before
    before = COMPILE.seconds
    a.step(state_a, 0)                # a's first step on the new mesh
    in_step = COMPILE.seconds - before
    (event,) = a.events
    assert event.action == "migrate"
    assert in_b > 0 and in_step > 0
    assert event.compile_s == pytest.approx(in_resize + in_step, abs=1e-12)
    a.step(state_a, 1)                # later steps add nothing
    assert event.compile_s == pytest.approx(in_resize + in_step, abs=1e-12)


def _op_names(hlo: str):
    return re.findall(r'op_name="([^"]*)"', hlo)


def _scopes(names):
    """Every part of the op names' paths, with the transforms that wrap a
    scope under differentiation (``transpose(jvp(layers))``) taken off."""
    return {re.sub(r"^(?:\w+\()+|\)+$", "", part)
            for n in names for part in n.split("/")}


def test_decode_step_hlo_carries_its_scopes():
    cfg = get_config("granite-3-2b-smoke")
    params = M.abstract_params(cfg)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 16))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    step = jax.jit(make_serve_step(cfg, row_stable=True))
    names = _op_names(step.lower(params, cache, tok, pos).compile().as_text())
    scopes = _scopes(names)
    assert {"layers", "kv_write", "attend", "attn_qkv", "attn_out", "mlp",
            "norm", "embed", "unembed", "sample"} <= scopes
    assert any("/layers/" in n and "/kv_write/" in n for n in names)


def test_train_step_hlo_carries_ssd_scan_and_optimizer():
    from repro.configs.base import SMOKE_SHAPE
    from repro.data.pipeline import make_batch
    from repro.models.train import abstract_state
    cfg = get_config("mamba2-370m-smoke")
    opt = AdamW(learning_rate=1e-3)
    batch = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype),
                         make_batch(cfg, SMOKE_SHAPE))
    lowered = jax.jit(make_train_step(cfg, opt)).lower(
        abstract_state(cfg, opt), batch)
    scopes = _scopes(_op_names(lowered.compile().as_text()))
    assert {"layers", "ssd_scan", "optimizer", "norm", "embed"} <= scopes
