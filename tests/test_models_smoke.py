"""Per-arch reduced-config smoke: forward/train-step shapes + finiteness,
and a one-token decode. (Assignment: every arch gets a smoke test that runs
one forward/train step on CPU asserting output shapes + no NaNs.)"""
import dataclasses

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


def _reference_layers(cfg):
    """Every decode layer in order: its kind, its index among the layers
    whose cache it shares, and its place in the depth."""
    if cfg.is_interleaved:
        kinds = cfg.layer_types
    elif cfg.is_hybrid:
        groups = cfg.num_layers // cfg.shared_attention_every
        kinds = (("mamba",) * cfg.shared_attention_every + ("shared",)) \
            * groups
    else:
        kinds = ("mamba" if cfg.is_ssm else "decoder",) * cfg.num_layers
    seen = {}
    for depth, kind in enumerate(kinds):
        seen[kind] = seen.get(kind, -1) + 1
        yield kind, seen[kind], depth


def _reference_decode(params, cfg, tokens, cache, cache_index, row_stable):
    """``decode_step`` as a Python loop over the layers: each layer gets
    its cache as a stack of one, at index 0, sliced out of the model's
    stack, and its new cache is put back."""
    from repro.models import attention as attn, blocks, ssm
    from repro.models.layers import embed, mlp, rmsnorm, unembed
    B = tokens.shape[0]
    if row_stable:
        tokens = jnp.pad(tokens, ((0, -B % M.ROW_TILE), (0, 0)))
    x = embed(params["embed"], tokens, cfg)
    layers, cache = params["layers"], dict(cache)
    # the kind's parameter stack and the key of its cache stack
    where = {"decoder": (layers, "layers"), "mamba": (layers, "layers"),
             "shared": (None, "shared_kv")}
    if cfg.is_interleaved:
        where = {k: (layers[k], k) for k in ("mamba", "attention")}

    def residual(x, y):
        if cfg.residual_multiplier != 1.0:
            y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
        return x + y

    for kind, i, depth in _reference_layers(cfg):
        stack, key = where[kind]
        lp = None if stack is None else jax.tree.map(lambda t: t[i], stack)
        own = jax.tree.map(lambda t: t[i:i + 1], cache[key])
        if kind == "decoder":
            x, own = blocks.decoder_block_decode(
                lp, x, cfg, own, cache_index=cache_index, layer=0,
                row_stable=row_stable, cross_cache=jax.tree.map(
                    lambda t: t[i:i + 1], cache["cross"])
                if cfg.is_encdec else None)
        elif kind == "shared":
            shared = params["shared_attn"]
            a, own = attn.decode_attn_apply(
                shared["attn"], rmsnorm(shared["ln1"], x, cfg.norm_eps),
                cfg, own, cache_index=cache_index, layer=0)
            x = x + a
            x = x + mlp(shared["mlp"], rmsnorm(shared["ln2"], x,
                                               cfg.norm_eps), cfg)
        else:
            h = rmsnorm(lp["ln"], x, cfg.norm_eps)
            if kind == "mamba":
                y, own = ssm.ssm_decode_step(lp["ssm"], h, cfg, own,
                                             cache_index, layer=0)
            else:
                y, own = attn.decode_attn_apply(
                    lp["attn"], h, cfg, own, cache_index=cache_index,
                    layer=0, row_stable=True)
            x = residual(x, y)
            if cfg.is_interleaved:
                lm = jax.tree.map(lambda t: t[depth], layers["mlp"])
                x = residual(x, mlp(lm["mlp"], rmsnorm(lm["ln"], x,
                                                       cfg.norm_eps), cfg))
        cache[key] = jax.tree.map(lambda t, n: t.at[i:i + 1].set(n),
                                  cache[key], own)
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return unembed(params["embed"], x, cfg)[:B], cache


@pytest.mark.parametrize("arch,row_stable", [
    ("granite-3-2b", True), ("granite-3-2b", False),
    ("mixtral-8x7b", False),              # SWA (window 16) and MoE
    ("qwen3-moe-235b-a22b", False),
    ("seamless-m4t-medium", False), ("seamless-m4t-medium", True),
    ("mamba2-370m", False), ("zamba2-2.7b", False),
    ("granite-4.0-h-micro", False)])
def test_decode_step_matches_per_layer_reference(arch, row_stable):
    """The layer scan that carries the stacked caches and updates each
    layer's slice in place gives the per-layer reference's logits, tokens
    and cache bit for bit: 32 steps fill a 32-deep cache, or wrap
    mixtral's 16-slot rolling buffer twice. zamba2 runs two groups of
    Mamba layers, each closed by the shared block. granite-4.0-h runs its
    smoke period, one Mamba and one attention layer; its published period
    is held by the next test."""
    cfg = get_config(arch + "-smoke")
    if cfg.is_hybrid:
        cfg = dataclasses.replace(
            cfg, num_layers=2 * cfg.shared_attention_every)
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


def test_decode_step_matches_per_layer_reference_at_the_published_period():
    """granite-4.0-h's decode scan at its published period, two periods of
    5 Mamba, 1 attention and 4 Mamba layers (the small size of
    ``test_interleaved_hybrid.py``): runs of several layers, the second
    Mamba run starting at index 5. Over 32 steps, the same tokens fed to
    both, it gives the per-layer reference's tokens, and its logits and
    every cache leaf within 1e-6. Not bit for bit: the CPU compiles a
    layer inside a run's scan a float32 rounding away from the same layer
    unrolled (logits 3.7e-8 apart, the cache 2.7e-7)."""
    from chipbench import weights as W
    from chipbench.kinds import common
    from chipbench.refs import granite_hybrid
    from chipbench.tests import tiny_hybrid
    c = tiny_hybrid.cell(tiny_hybrid.HYBRID).config
    cfg = common.program_config(c, granite_hybrid)
    assert cfg.mixer_period == get_config("granite-4.0-h-micro").mixer_period
    params = W.make(granite_hybrid.layout(c), 11, jnp.float32)
    step = jax.jit(lambda p, t, c, i: M.decode_step(p, cfg, t, c, i))
    ref = jax.jit(lambda p, t, c, i: _reference_decode(p, cfg, t, c, i,
                                                       False))
    cache = ref_cache = M.init_cache(cfg, 3, 32)
    tok = jnp.asarray([3, 1, 4], jnp.int32)[:, None]
    for i in range(32):
        logits, cache = step(params, tok, cache, jnp.int32(i))
        ref_logits, ref_cache = ref(params, tok, ref_cache, jnp.int32(i))
        np.testing.assert_allclose(logits, ref_logits, atol=1e-6, rtol=0)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
        np.testing.assert_array_equal(
            tok, jnp.argmax(ref_logits[:, -1, :cfg.vocab_size], -1)[:, None])
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


@pytest.mark.parametrize("arch", ["mamba2-370m", "zamba2-2.7b",
                                  "granite-4.0-h-micro"])
def test_ssm_decode_step_matches_the_xla_state_update(arch, monkeypatch):
    """The decode step through the one-pass state kernel gives the logits
    and cache of the same step with the state updated and read out by XLA
    ops (``kernels/ref.py``), within float32 rounding, over 12 steps, the
    state updated in its stack at a traced layer in every model."""
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


#: decode positions that write both ends of both 128-position tile columns
#: of a 256-deep cache
KV_ROW_POSITIONS = (0, 1, 127, 128, 129, 255)


@pytest.mark.parametrize("model,rows", [
    ("dense", 8), ("dense", 4), ("dense", 2), ("hybrid_period", 8)])
def test_decode_kv_row_kernel_matches_the_xla_write(model, rows, monkeypatch):
    """At cache 256 the K/V rows are written by the ``kv_row_write`` kernel
    (interpreted here); the same steps with XLA's dynamic-update-slice
    write give the same logits, tokens and cache leaves bit for bit. The
    dense decoder runs the row-stable step over 8, 4 and 2 rows of a batch
    of 8 (a replica on 1, 2 or 4 chips); the interleaved hybrid its
    published period, two periods of 5 Mamba, 1 attention and 4 Mamba
    layers at the small size of ``test_interleaved_hybrid.py``."""
    from repro.kernels import ops
    if model == "dense":
        cfg = get_config("granite-3-2b-smoke")
        params = init_state(cfg, OPT, 0).params
    else:
        from chipbench import weights as W
        from chipbench.kinds import common
        from chipbench.refs import granite_hybrid
        from chipbench.tests import tiny_hybrid
        c = tiny_hybrid.cell(tiny_hybrid.HYBRID).config
        cfg = common.program_config(c, granite_hybrid)
        params = W.make(granite_hybrid.layout(c), 11, jnp.float32)
    row_stable = model == "dense"
    tokens = jnp.arange(3, 3 + 8, dtype=jnp.int32)[:rows, None]

    def run():
        def one(p, t, c, i):
            return M.decode_step(p, cfg, t, c, i, row_stable=row_stable)
        cache = M.init_cache(cfg, rows, 256)
        uses_kernel = "kv_row_write" in str(
            jax.make_jaxpr(one)(params, tokens, cache, jnp.int32(0)))
        step, tok, out = jax.jit(one), tokens, []
        for i in KV_ROW_POSITIONS:
            logits, cache = step(params, tok, cache, jnp.int32(i))
            tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
            out += [np.asarray(logits), np.asarray(tok)]
        return uses_kernel, out, cache

    kernel, got, cache = run()
    monkeypatch.setattr(ops, "lane_major", lambda shape: False)
    xla, want, want_cache = run()
    assert kernel and not xla
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
        np.testing.assert_array_equal(a, b)


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
