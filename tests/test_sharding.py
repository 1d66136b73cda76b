"""Sharding-rule unit tests over an AbstractMesh (no devices needed)."""
import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.parallel.sharding import DEFAULT_RULES, rules_for, spec_for_axes

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_basic_mapping():
    s = spec_for_axes(("embed", "mlp"), DEFAULT_RULES, MESH, (2048, 8192))
    assert s == P("data", "model")


def test_divisibility_fallback_drops_axis():
    # phi4: 24 q_heads on a 16-way model axis -> replicate
    s = spec_for_axes(("embed", "q_heads", "head_dim"), DEFAULT_RULES, MESH,
                      (3072, 24, 128))
    assert s == P("data", None, None)


def test_vocab_padding_keeps_sharding():
    from repro.configs.base import phys_vocab
    v = phys_vocab(49155)
    s = spec_for_axes(("vocab", "embed"), DEFAULT_RULES, MESH, (v, 2048))
    assert s == P("model", "data")


def test_multi_axis_batch_filtered_by_mesh():
    s = spec_for_axes(("batch", None), DEFAULT_RULES, MESH, (256, 10))
    assert s == P("data", None)                    # "pod" absent -> dropped
    s3 = spec_for_axes(("batch", None), DEFAULT_RULES, MESH3, (256, 10))
    assert s3 == P(("pod", "data"), None)


def test_batch_indivisible_replicates():
    s = spec_for_axes(("batch",), DEFAULT_RULES, MESH, (1,))
    assert s == P(None)


def test_arch_overrides():
    r = rules_for(get_config("mixtral-8x7b"))
    assert r["experts"] is None and r["expert_mlp"] == ("model",)
    r2 = rules_for(get_config("qwen3-moe-235b-a22b"))
    assert r2["experts"] == ("model",)


def test_explicit_override_wins():
    r = rules_for(get_config("granite-3-2b"), {"mlp": None})
    assert r["mlp"] is None
