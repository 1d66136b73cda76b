"""The hybrid cell on the CPU: at a tiny size against its plain reference,
and caught when a fault is planted; the hybrid step's least bytes against
a hand count at the cell's size; ``ssm_state_share.decode`` on a small
recorded trace with a known answer, and silent where the marks are
missing. Nothing here is a device number."""
import os

import pytest

from chipbench import arith, arith_hybrid, harness, scopes
from chipbench.peaks import PEAKS
from chipbench.refs import granite_hybrid
from chipbench.tests import faults, tiny, tiny_hybrid

DATA = os.path.join(os.path.dirname(__file__), "data")
DECODE = {"kind": "decode"}


def _checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


@pytest.mark.parametrize("fault", ["none", "state_unchanged",
                                   "token_altered"])
def test_hybrid_cell(fault):
    if fault == "none":
        result = tiny_hybrid.run(tiny_hybrid.HYBRID)
        assert result["correct"], _checks(result)
        assert set(result["metrics"]) == {"decode_tokens_per_s",
                                          "itl_p95_ms", "setup_s"}
    else:
        with faults.FAULTS[fault]():
            result = tiny_hybrid.run(tiny_hybrid.HYBRID)
        assert not result["correct"], _checks(result)
    assert result["attempted"] == 4 and result["failed"] == 0


def test_the_reference_is_the_configuration_as_published():
    c = tiny.full(tiny_hybrid.HYBRID).config
    assert c["reduced"] == [] and c["departures"] == {}
    assert granite_hybrid.counts(c) == {"mamba": 36, "attention": 4}
    per = granite_hybrid.period(c)
    assert per == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    from chipbench.kinds import common
    cfg = common.program_config(c, granite_hybrid)
    assert cfg.layer_types == tuple(c["layer_types"])
    assert cfg.ssm.state_dtype == c["dtype"]["ssm_state"] == "float32"


def test_the_program_runs_the_state_in_the_stated_precision():
    """A configuration that states a bfloat16 SSM state is refused by the
    float32 program, and run by one told so: a cache that halves the
    state's bytes is a change of the configuration, not of the program
    alone (``chipbench/calibrate_state.py`` runs it so)."""
    import copy
    from chipbench.kinds import common
    c = copy.deepcopy(tiny.full(tiny_hybrid.HYBRID).config)
    c["dtype"]["ssm_state"] = "bfloat16"
    with pytest.raises(ValueError, match="ssm.state_dtype"):
        common.program_config(c, granite_hybrid)
    c["program"].setdefault("overrides", {})["ssm.state_dtype"] = "bfloat16"
    cfg = common.program_config(c, granite_hybrid)
    from repro.models import model as M
    import jax
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 8))
    assert cache["mamba"]["state"].dtype == "bfloat16"


def test_hybrid_step_least_bytes():
    c = tiny.full(tiny_hybrid.HYBRID).config
    lay = granite_hybrid.layout(c)
    d, di, n, h, hd, f, V = 2048, 4096, 128, 64, 64, 8192, 100352
    mamba = (d * (2 * di + 2 * n + h) + di * d      # projections
             + 4 * (di + 2 * n) + (di + 2 * n)      # conv and its bias
             + 3 * h + di + d)                      # A, D, dt_bias, norms
    attention = 2 * d * 32 * hd + 2 * d * 8 * hd + d
    mlp = 3 * d * f + d
    params = 36 * mamba + 4 * attention + 40 * mlp + V * d + d
    assert arith.param_count(lay) == params
    assert 3.19e9 < params < 3.20e9
    state = 36 * h * hd * n * 4                     # float32
    conv = 36 * 3 * (di + 2 * n) * 2
    assert arith_hybrid.state_bytes(c) == state == 75_497_472
    assert arith_hybrid.conv_bytes(c) == conv
    assert arith_hybrid.kv_row_bytes(c) == 2 * 4 * 8 * hd * 2 == 8192
    matmul = params - 36 * (3 * h + di + d + (di + 2 * n)) \
        - 4 * d - 40 * d - d
    assert arith.matmul_param_count(lay) == matmul
    for batch, pos in ((96, 0), (96, 127), (96, 254), (8, 2047)):
        step = arith_hybrid.decode_step(c, lay, batch, pos)
        assert step["bytes"] == 2 * params + batch * (
            2 * state + 2 * conv + (pos + 1) * 8192)
        assert step["flops"] == batch * (
            2 * matmul + 4 * (pos + 1) * 32 * hd * 4 + 5 * h * hd * n * 36)
    # the cell's batch of 96: bound by bytes, 69% of them the state's
    p = PEAKS["TPU v5 lite"]
    step = arith_hybrid.decode_step(c, lay, 96, 127)
    least = arith_hybrid.decode_least_s(c, 96, 127,
                                        {"flops": p.flops,
                                         "hbm_bytes_per_s": p.hbm_bytes_per_s})
    assert least == step["bytes"] / p.hbm_bytes_per_s
    assert 25.8e-3 < least < 25.9e-3
    assert 0.68 < 96 * 2 * state / step["bytes"] < 0.70


@pytest.fixture
def hybrid_trace():
    with open(os.path.join(DATA, "hybrid_trace.json")) as f:
        return scopes.ScopedTrace.from_json(f.read())


def _reader(name):
    return harness.load_module("metrics", name)


def test_ssm_state_share_on_the_recorded_trace(hybrid_trace):
    share = _reader("ssm_state_share.decode")
    # 12 + 4 ns of each 40 ns run under ssm_state
    assert share.read(DECODE, hybrid_trace) == pytest.approx(40.0)
    assert share.read({"kind": "train"}, hybrid_trace) is None
    # scopes.py knows no Mamba scope: the accepted readers find the Mamba
    # layers' work under ``layers`` alone, and count it as moving the
    # cache, beside the loop's own time, the KV row write and the copy
    assert _reader("cache_move_share.decode").read(DECODE, hybrid_trace) \
        == pytest.approx(100 * 30 / 40)
    # attention's own read of the cache, and nothing of the Mamba layers
    assert _reader("attend_share.decode").read(DECODE, hybrid_trace) \
        == pytest.approx(100 * 2 / 40)
    inner = share.innermost
    assert inner("jit(_advance)/layers/while/body/ssm_state/add") \
        == "ssm_state"
    assert inner("jit(_advance)/layers/ssm_conv/mul") == "ssm_conv"
    assert inner("jit(_advance)/layers/ssm_in/dot_general") == "ssm_in"
    assert inner("jit(_advance)/layers/while/body/attend/dot") == "attend"
    assert inner("jit(_advance)/copy") == ""


@pytest.mark.parametrize("trace", ["scoped_trace.json", "small_trace.json",
                                   None])
def test_ssm_state_share_is_silent_without_the_scope(trace):
    """A trace of a program with no Mamba layers, one with no scopes at
    all (the parent's: the program that lacks the scope), and none."""
    if trace is not None:
        with open(os.path.join(DATA, trace)) as f:
            trace = scopes.ScopedTrace.from_json(f.read())
    assert _reader("ssm_state_share.decode").read(DECODE, trace) is None
