"""Per-arch reduced-config smoke: forward/train-step shapes + finiteness,
and a one-token decode. (Assignment: every arch gets a smoke test that runs
one forward/train step on CPU asserting output shapes + no NaNs.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.configs.base import SMOKE_SHAPE, phys_vocab
from repro.data.pipeline import make_batch
from repro.models import model as M
from repro.models.train import init_state, make_serve_step, make_train_step
from repro.optim import AdamW

OPT = AdamW(learning_rate=1e-3)


@pytest.fixture(scope="module")
def batches():
    return {}


@pytest.mark.parametrize("arch", list_archs())
def test_forward_and_train_step(arch):
    cfg = get_config(arch + "-smoke")
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, SMOKE_SHAPE).items()}
    logits, aux = M.forward(init_state(cfg, OPT, 0).params, cfg, batch)
    B = SMOKE_SHAPE.global_batch
    S = SMOKE_SHAPE.seq_len
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        assert logits.shape == (B, S, phys_vocab(cfg.vocab_size))
    else:
        assert logits.shape == (B, S, phys_vocab(cfg.vocab_size))
    assert bool(jnp.isfinite(logits).all())

    st = init_state(cfg, OPT, 0)
    step = jax.jit(make_train_step(cfg, OPT))
    st, m = step(st, batch)
    st, m = step(st, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(st.step) == 2


@pytest.mark.parametrize("arch", list_archs())
def test_decode_step(arch):
    cfg = get_config(arch + "-smoke")
    st = init_state(cfg, OPT, 0)
    cache = M.init_cache(cfg, 2, 32, enc_len=32)
    serve = jax.jit(make_serve_step(cfg))
    tok = jnp.zeros((2, 1), jnp.int32)
    for i in range(3):
        tok, cache = serve(st.params, cache, tok, jnp.int32(i))
    assert tok.shape == (2, 1)
    assert int(tok.max()) < cfg.vocab_size        # padded ids masked


ROW_STABLE = [a for a in list_archs()
              if M.row_stable_decode(get_config(a + "-smoke"))]


def _decode(cfg, params, rows, row_stable, steps=3):
    """Logits of ``steps`` greedy decode steps for prompts ``rows``."""
    step = jax.jit(lambda p, t, c, i: M.decode_step(p, cfg, t, c, i,
                                                    row_stable=row_stable))
    cache = M.init_cache(cfg, len(rows), 16, enc_len=16)
    tok = jnp.asarray(rows, jnp.int32)[:, None]
    out = []
    for i in range(steps):
        logits, cache = step(params, tok, cache, jnp.int32(i))
        out.append(np.asarray(logits))
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
    return np.stack(out), cache


@pytest.mark.parametrize("arch", ROW_STABLE)
def test_row_stable_decode(arch):
    """The row-stable step (batch padded to ROW_TILE rows, the cache read
    one sequence at a time) gives the plain step's logits and cache, and a
    sequence's logits do not depend on its batch-mates."""
    cfg = get_config(arch + "-smoke")
    params = init_state(cfg, OPT, 0).params
    rows = [3, 1, 4]                     # 3 rows: 5 padding rows in play
    stable, cache = _decode(cfg, params, rows, True)
    plain, plain_cache = _decode(cfg, params, rows, False)
    assert stable.shape == plain.shape
    np.testing.assert_allclose(stable, plain, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)
    split = np.concatenate([_decode(cfg, params, rows[:1], True)[0],
                            _decode(cfg, params, rows[1:], True)[0]], axis=1)
    np.testing.assert_array_equal(split, stable)


def _reference_decode(params, cfg, tokens, cache, cache_index, row_stable):
    """``decode_step`` as a Python loop over the layers: each layer's cache
    is sliced out of the stack, gets its new row, and is put back."""
    from repro.models import blocks
    from repro.models.layers import embed, rmsnorm, unembed
    B = tokens.shape[0]
    if row_stable:
        tokens = jnp.pad(tokens, ((0, -B % M.ROW_TILE), (0, 0)))
    x, kv = embed(params["embed"], tokens, cfg), cache["layers"]
    for l in range(cfg.num_layers):
        at = lambda t: t[l]                                 # noqa: E731
        x, c = blocks.decoder_block_decode(
            jax.tree.map(at, params["layers"]), x, cfg, jax.tree.map(at, kv),
            cache_index=cache_index, row_stable=row_stable,
            cross_cache=jax.tree.map(at, cache["cross"])
            if cfg.is_encdec else None)
        kv = jax.tree.map(lambda t, n: t.at[l].set(n), kv, c)
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return unembed(params["embed"], x, cfg)[:B], {**cache, "layers": kv}


@pytest.mark.parametrize("arch,row_stable", [
    ("granite-3-2b", True), ("granite-3-2b", False),
    ("mixtral-8x7b", False),              # SWA (window 16) and MoE
    ("qwen3-moe-235b-a22b", False),
    ("seamless-m4t-medium", False), ("seamless-m4t-medium", True)])
def test_decode_step_matches_per_layer_reference(arch, row_stable):
    """The layer scan that carries the stacked cache and writes one row per
    layer in place gives the per-layer reference's logits, tokens and cache
    bit for bit: 32 steps fill a 32-deep cache, or wrap mixtral's 16-slot
    rolling buffer twice."""
    cfg = get_config(arch + "-smoke")
    params = init_state(cfg, OPT, 0).params
    cache = M.init_cache(cfg, 3, 32, enc_len=16)
    assert cfg.attention != "swa" or cache["layers"]["k"].shape[2] == 16
    step = jax.jit(lambda p, t, c, i: M.decode_step(p, cfg, t, c, i,
                                                    row_stable=row_stable))
    ref = jax.jit(lambda p, t, c, i: _reference_decode(p, cfg, t, c, i,
                                                       row_stable))
    tok = ref_tok = jnp.asarray([3, 1, 4], jnp.int32)[:, None]
    ref_cache = cache
    for i in range(32):
        logits, cache = step(params, tok, cache, jnp.int32(i))
        ref_logits, ref_cache = ref(params, ref_tok, ref_cache, jnp.int32(i))
        np.testing.assert_array_equal(logits, ref_logits)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
        ref_tok = jnp.argmax(ref_logits[:, -1, :cfg.vocab_size], -1)[:, None]
        np.testing.assert_array_equal(tok, ref_tok)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("arch", ["mamba2-370m", "zamba2-2.7b",
                                  "granite-4.0-h-micro"])
def test_ssm_decode_step_matches_the_xla_state_update(arch, monkeypatch):
    """The decode step through the one-pass state kernel gives the logits
    and cache of the same step with the state updated and read out by XLA
    ops (``kernels/ref.py``), within float32 rounding, over 12 steps:
    per-layer leaves (mamba2, zamba2's Mamba layers) and the interleaved
    hybrid's stack."""
    from repro.kernels import ops, ref
    cfg = get_config(arch + "-smoke")
    params = init_state(cfg, OPT, 0).params

    def run():
        step = jax.jit(lambda p, t, c, i: M.decode_step(p, cfg, t, c, i))
        cache = M.init_cache(cfg, 3, 16)
        tok = jnp.asarray([3, 1, 4], jnp.int32)[:, None]
        out = []
        for i in range(12):
            logits, cache = step(params, tok, cache, jnp.int32(i))
            out.append(np.asarray(logits))
            tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
        return np.stack(out), cache

    got, cache = run()
    monkeypatch.setattr(
        ops, "ssm_state_update",
        lambda *args, interpret: ref.ssm_state_update_reference(*args))
    want, want_cache = run()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-5, rtol=1e-5)


def test_row_stable_decode_refuses_shared_state():
    cfg = get_config("mamba2-370m-smoke")
    assert not M.row_stable_decode(cfg)
    with pytest.raises(NotImplementedError, match="row-stable"):
        M.decode_step(init_state(cfg, OPT, 0).params, cfg,
                      jnp.zeros((2, 1), jnp.int32), M.init_cache(cfg, 2, 16),
                      0, row_stable=True)


def test_vlm_prefix_loss_span():
    cfg = get_config("pixtral-12b-smoke")
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, SMOKE_SHAPE).items()}
    # text span = seq - patch tokens
    assert batch["tokens"].shape[1] == \
        SMOKE_SHAPE.seq_len - cfg.frontend.tokens_per_sample


def test_train_microbatch_equivalence():
    """mb=2 gradient accumulation matches mb=1 loss closely."""
    import dataclasses
    cfg = get_config("granite-3-2b-smoke")
    cfg2 = dataclasses.replace(cfg, train_microbatches=2)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, SMOKE_SHAPE).items()}
    s1, _ = jax.jit(make_train_step(cfg, OPT))(init_state(cfg, OPT, 0), batch)
    s2, _ = jax.jit(make_train_step(cfg2, OPT))(init_state(cfg2, OPT, 0), batch)
    a = jax.tree.leaves(s1.params)
    b = jax.tree.leaves(s2.params)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-3, atol=2e-5)
