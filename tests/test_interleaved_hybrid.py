"""The interleaved Mamba-2 / attention hybrid (granite-4.0-h) at a small
size on the CPU: two periods of its layer pattern (5 Mamba, 1 attention, 4
Mamba), small widths, its multipliers, NoPE and the conv bias, float32, on
seeded random weights from the benchmark's generator.

* ``forward`` (the chunked SSD) gives the plain reference's logits
  (``chipbench/refs/granite_hybrid.py``);
* prefill by decode through ``decode_step``, then greedy decoding, gives
  the reference's full-forward logits at every position.

``tests/test_models_smoke.py`` holds the decode scan to a per-layer loop,
bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights as W
from chipbench.refs import granite_hybrid as ref
from chipbench.tests import tiny_hybrid
from repro.configs import get_config
from repro.configs.base import ArchConfig, SSMConfig
from repro.models import model as M

SEED = 11
#: float32 program against the float32 reference, all on the CPU: what is
#: left is the order of the sums (the program's chunked SSD and its
#: recurrence against the reference's minimal SSD; the chunked attention
#: against the plain softmax), a few float32 roundings over 20 layers on
#: logits of order 1.
ATOL, RTOL = 2e-4, 2e-4


@pytest.fixture(scope="module")
def model():
    c = tiny_hybrid.cell(tiny_hybrid.HYBRID).config
    from chipbench.kinds import common
    cfg = common.program_config(c, ref)
    params = W.make(ref.layout(c), SEED, jnp.float32)
    return c, cfg, params


def _tokens(c, n, S, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, c["vocab_size"], (n, S), dtype=np.int32)


def test_the_small_size_keeps_the_pattern_and_multipliers(model):
    c, cfg, _ = model
    full = get_config("granite-4.0-h-micro")
    assert cfg.num_layers == 20 and cfg.mixer_period == full.mixer_period
    assert cfg.layer_types == full.layer_types[:20]
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == \
        (12.0, 0.015625, 0.22, 8.0)
    assert cfg.position_embedding == "nope" and cfg.ssm.conv_bias


def test_forward_matches_the_reference(model):
    c, cfg, params = model
    tokens = _tokens(c, 2, 64)       # two whole chunks of the program's SSD
    got, _ = jax.jit(lambda p, t: M.forward(p, cfg, {"tokens": t}))(
        params, jnp.asarray(tokens))
    want = ref.logits(c, W.base_key(SEED), tokens, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL, rtol=RTOL)


def _decode(cfg, params, prompt, gen, cache_len):
    """Prefill by decode, then greedy decode: the tokens fed and the
    logits of every step."""
    step = jax.jit(lambda p, t, c, i: M.decode_step(p, cfg, t, c, i))
    n, P = prompt.shape
    cache = M.init_cache(cfg, n, cache_len)
    fed, logits = [], []
    tok = jnp.asarray(prompt[:, :1])
    for i in range(P + gen - 1):
        fed.append(np.asarray(tok)[:, 0])
        out, cache = step(params, tok, cache, jnp.int32(i))
        logits.append(np.asarray(out)[:, 0])
        tok = jnp.asarray(prompt[:, i + 1:i + 2]) if i + 1 < P else \
            jnp.argmax(out[:, -1, :cfg.vocab_size], -1)[:, None]
    return np.stack(fed, 1), np.stack(logits, 1)


def test_decode_matches_the_reference_at_every_position(model):
    c, cfg, params = model
    prompt = _tokens(c, 3, 12)
    fed, got = _decode(cfg, params, prompt, 12, 32)
    np.testing.assert_array_equal(fed[:, :12], prompt)
    want = np.asarray(ref.logits(c, W.base_key(SEED), fed, jnp.float32))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)
    # the greedy tokens are the reference's best wherever it has no near tie
    top2 = np.sort(want[:, 11:-1], -1)[..., -2:]
    clear = top2[..., 1] - top2[..., 0] > 10 * ATOL
    chosen = fed[:, 12:] == want[:, 11:-1].argmax(-1)
    assert np.all(chosen | ~clear) and clear.any()


def _reused_and_fresh(cfg, params, first, second):
    """``second``'s decode logits, position by position, from a cache that
    ``first`` filled and from a fresh one."""
    B, S = second.shape
    step = jax.jit(lambda p, t, c, i: M.decode_step(p, cfg, t, c, i))

    def feed(tokens, cache):
        out = []
        for i in range(tokens.shape[1]):
            logits, cache = step(params, jnp.asarray(tokens[:, i:i + 1]),
                                 cache, jnp.int32(i))
            out.append(np.asarray(logits))
        return np.stack(out), cache

    fresh, _ = feed(second, M.init_cache(cfg, B, S + 6))
    _, used = feed(first, M.init_cache(cfg, B, S + 6))
    reused, _ = feed(second, used)
    return reused, fresh


def test_a_slot_reused_from_position_0_carries_no_state(model):
    """A replica serves static batches in the same slots, resetting only the
    position: a sequence decoded after another in its slot gets the logits
    it gets in a fresh cache (its SSM state and conv windows start at zero;
    its KV rows are masked by position)."""
    c, cfg, params = model
    reused, fresh = _reused_and_fresh(cfg, params, _tokens(c, 3, 10, seed=5),
                                      _tokens(c, 3, 10, seed=6))
    np.testing.assert_array_equal(reused, fresh)


def test_a_mamba2_slot_reused_from_position_0_carries_no_state():
    """The same for a Mamba-2 model, whose decode shares the SSM step."""
    cfg = get_config("mamba2-370m-smoke")
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(4)
    first, second = (rng.integers(0, cfg.vocab_size, (2, 8), dtype=np.int32)
                     for _ in range(2))
    reused, fresh = _reused_and_fresh(cfg, params, first, second)
    np.testing.assert_array_equal(reused, fresh)


def test_the_cache_holds_both_kinds_of_state():
    cfg = get_config("granite-4.0-h-micro")
    shapes = jax.eval_shape(lambda: M.init_cache(cfg, 96, 256))
    assert shapes["mamba"]["state"].shape == (36, 96, 64, 64, 128)
    assert shapes["mamba"]["state"].dtype == jnp.float32
    assert shapes["mamba"]["conv_x"].shape == (36, 96, 3, 4096)
    assert shapes["attention"]["k"].shape == (4, 96, 256, 8, 64)
    stacks = jax.eval_shape(lambda: M.abstract_params(cfg))["layers"]
    assert stacks["mamba"]["ssm"]["conv_x_bias"].shape == (36, 4096)
    assert stacks["attention"]["attn"]["wq"].shape == (4, 2048, 32, 64)
    assert stacks["mlp"]["mlp"]["wi_gate"].shape == (40, 2048, 8192)


def test_row_stable_decode_names_the_hybrid():
    cfg = get_config("granite-4.0-h-micro-smoke")
    assert not M.row_stable_decode(cfg)
    with pytest.raises(NotImplementedError,
                       match="interleaved Mamba-2/attention hybrid"):
        M.decode_step(M.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                      jnp.zeros((2, 1), jnp.int32), M.init_cache(cfg, 2, 16),
                      0, row_stable=True)


@pytest.mark.parametrize("bad", [
    dict(num_layers=15),                           # no whole period
    dict(mixer_period=("mamba", "mlp")),           # no such mixer
    dict(ssm=None),                                # no SSM to interleave
    dict(mixer_period=(), residual_multiplier=0.5),  # not run elsewhere
    dict(mixer_period=(), residual_multiplier=1.0,   # no whole number of
         shared_attention_every=6),                  # shared-block groups
])
def test_the_pattern_is_checked_when_configured(bad):
    kw = dict(name="h", family="hybrid", num_layers=20, d_model=64,
              num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
              vocab_size=256, ssm=SSMConfig(state_size=16, head_dim=16),
              mixer_period=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
              residual_multiplier=0.22)
    ArchConfig(**kw)
    with pytest.raises(ValueError):
        ArchConfig(**{**kw, **bad})
