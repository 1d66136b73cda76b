"""Config registry + shape applicability."""
import pytest

from repro.configs import (SHAPES, all_configs, get_config, list_archs,
                           live_cells, reduced, shape_applicable)
from repro.configs.base import phys_vocab

EXPECTED_ARCHS = {
    "zamba2-2.7b", "internlm2-20b", "granite-3-2b", "phi4-mini-3.8b",
    "qwen2.5-32b", "pixtral-12b", "seamless-m4t-medium", "mixtral-8x7b",
    "qwen3-moe-235b-a22b", "mamba2-370m", "granite-4.0-h-micro",
}


def test_all_archs_present():
    assert set(list_archs()) == EXPECTED_ARCHS


def test_exact_dims():
    c = get_config("qwen3-moe-235b-a22b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads) == \
        (94, 4096, 64, 4)
    assert c.moe.num_experts == 128 and c.moe.experts_per_token == 8
    assert c.moe.d_ff == 1536 and c.vocab_size == 151936
    c = get_config("zamba2-2.7b")
    assert c.ssm.state_size == 64 and c.d_ff == 10240 and c.is_hybrid
    c = get_config("mixtral-8x7b")
    assert c.attention == "swa" and c.window == 4096
    c = get_config("qwen2.5-32b")
    assert c.qkv_bias and c.d_ff == 27648
    c = get_config("mamba2-370m")
    assert c.is_ssm and c.ssm.state_size == 128 and c.attention == "none"
    c = get_config("seamless-m4t-medium")
    assert c.is_encdec and c.encoder_layers == 12 and c.vocab_size == 256206


def test_cell_matrix():
    cells = live_cells()
    assert len(cells) == 44
    live = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(live) == 37 and len(skipped) == 7
    # long_500k runs only for sub-quadratic archs
    for arch, shape, ok, why in cells:
        if shape == "long_500k":
            expect = arch in ("zamba2-2.7b", "mixtral-8x7b", "mamba2-370m",
                              "granite-4.0-h-micro")
            assert ok == expect, (arch, ok, why)


def test_reduced_configs_are_small():
    for name in list_archs():
        r = reduced(get_config(name))
        assert r.num_layers <= 2 and r.d_model == 64
        assert r.vocab_size == 256
        assert r.family == get_config(name).family


def test_phys_vocab():
    assert phys_vocab(49155) % 128 == 0 and phys_vocab(49155) >= 49155
    assert phys_vocab(32000) == 32000


def test_shapes():
    names = {s.name: s for s in SHAPES}
    assert names["train_4k"].global_batch == 256
    assert names["long_500k"].seq_len == 524_288
    assert names["decode_32k"].kind == "decode"
