"""The benchmark's arithmetic at the shipped configurations' sizes: model
FLOPs of a training step, least bytes of a decode step, and the peak table.
Nothing here runs a model or reads a device."""
import math

import pytest

from chipbench import arith
from chipbench.peaks import PEAKS, peaks_for
from chipbench.refs import dense_gqa, mamba2
from chipbench.tests import tiny


def config(name):
    return tiny.full(name).config


def test_mamba2_370m_train_step_model_flops():
    c = config("mamba2-train-resize")
    lay = mamba2.layout(c)
    n = arith.param_count(lay)
    # 48 layers of 6,556,064 and a tied table of 50,304 x 1,024 rows
    assert n == 48 * (2 * 1024 * 2048 + 2 * 1024 * 128 + 1024 * 32
                      + 4 * 2048 + 2 * 4 * 128 + 3 * 32 + 2048
                      + 2048 * 1024 + 1024) + 50304 * 1024 + 1024
    assert 3.6e8 < n < 3.7e8
    tokens = 16 * 2048
    f = arith.train_flops(c, lay, tokens)
    assert f["params"] == 6.0 * n * 32768
    assert f["params"] == pytest.approx(7.2e13, rel=0.01)
    # the SSD transform: 3 x (2QN + 2QHP + 4HPN) per token and layer
    ssd = 3 * (2 * 256 * 128 + 2 * 256 * 2048 + 4 * 2048 * 128) * 48 * tokens
    assert f["ssd"] == ssd
    assert f["total"] == f["params"] + ssd


def test_granite_3_2b_decode_least_bytes():
    c = config("granite-decode-chat")
    lay = dense_gqa.layout(c)
    d, kv, hd, f, L = 2048, 8, 64, 8192, 40
    per_layer = d * 32 * hd * 2 + 2 * d * kv * hd + 3 * d * f + 2 * d
    n = L * per_layer + 49280 * d + d
    assert arith.param_count(lay) == n
    assert arith.kv_row_bytes(c) == 2 * L * kv * hd * 2 == 81920
    for batch, pos in ((256, 0), (256, 127), (256, 254), (16, 2047)):
        step = arith.decode_step(c, lay, batch, pos)
        assert step["bytes"] == 2 * n + batch * (pos + 1) * 81920
        matmul = n - L * 2 * d - d
        assert step["flops"] == batch * (2 * matmul
                                         + 4 * (pos + 1) * 32 * hd * L)
    # the cell's batch of 256: bound by bytes mid-sequence (5.07 GB of
    # weights and 2.68 GB of cache), by operations at its first position
    p = PEAKS["TPU v5 lite"]
    step = arith.decode_step(c, lay, 256, 127)
    t = arith.least_s(step["flops"], step["bytes"], p.flops,
                      p.hbm_bytes_per_s)
    assert t == step["bytes"] / p.hbm_bytes_per_s
    assert 9.4e-3 < t < 9.5e-3
    step = arith.decode_step(c, lay, 256, 0)
    t = arith.least_s(step["flops"], step["bytes"], p.flops,
                      p.hbm_bytes_per_s)
    assert t == step["flops"] / p.flops
    assert 6.5e-3 < t < 6.7e-3


def test_peak_lookup():
    assert peaks_for("TPU v5 lite").flops == 197e12
    assert peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_least_time_is_the_larger_bound():
    assert arith.least_s(4.0, 1.0, 2.0, 1.0) == 2.0
    assert arith.least_s(1.0, 9.0, 2.0, 3.0) == 3.0
    assert math.isclose(arith.least_s(0.0, 0.0, 1.0, 1.0), 0.0)
