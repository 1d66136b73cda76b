"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps.

The compiled kernels are checked against the chip's compiler in
tests/test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


ATTN_CASES = [
    # (B, H, Hkv, Sq, Sk, D, causal, window, dtype)
    (2, 4, 2, 256, 256, 64, True, 0, jnp.float32),
    (1, 8, 8, 128, 128, 128, False, 0, jnp.float32),
    (2, 4, 1, 256, 256, 64, True, 64, jnp.float32),
    (1, 2, 2, 128, 128, 64, True, 0, jnp.bfloat16),
    (1, 4, 2, 64, 64, 32, True, 0, jnp.float32),
]


@pytest.mark.parametrize("B,H,Hkv,Sq,Sk,D,causal,window,dtype", ATTN_CASES)
def test_flash_attention(B, H, Hkv, Sq, Sk, D, causal, window, dtype):
    q = _rand((B, H, Sq, D), dtype)
    k = _rand((B, Hkv, Sk, D), dtype)
    v = _rand((B, Hkv, Sk, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    exp = ref.attention_reference(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


DECODE_CASES = [
    # (B, H, Hkv, Sk, D) — single-token query (Sq=1) against a growing
    # KV cache, the serving decode step.  Non-power-of-two batches mixed
    # in: serving batches track request admission, not tiling.
    (3, 4, 2, 128, 64),
    (3, 4, 2, 256, 64),
    (3, 4, 2, 384, 64),      # growing cache length across these three
    (5, 8, 1, 256, 64),      # non-pow2 batch, MQA
    (7, 2, 2, 192, 32),      # non-pow2 batch and cache length
    (1, 4, 4, 512, 128),
]


@pytest.mark.parametrize("B,H,Hkv,Sk,D", DECODE_CASES)
def test_flash_attention_decode_step(B, H, Hkv, Sk, D):
    """Decode-shaped attention: one query token attending over the whole
    cache (no mask — every cached position is in the past)."""
    q = _rand((B, H, 1, D), jnp.float32)
    k = _rand((B, Hkv, Sk, D), jnp.float32)
    v = _rand((B, Hkv, Sk, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                              interpret=True)
    exp = ref.attention_reference(q, k, v, causal=False, window=0)
    assert out.shape == (B, H, 1, D)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_decode_consistent_as_cache_grows():
    """The decode step over a prefix cache must equal the same-position
    row of a full-sequence causal pass (cache semantics)."""
    B, H, S, D = 2, 4, 256, 64
    q_full = _rand((B, H, S, D), jnp.float32)
    k = _rand((B, H, S, D), jnp.float32)
    v = _rand((B, H, S, D), jnp.float32)
    full = ops.flash_attention(q_full, k, v, causal=True,
                               block_q=64, block_k=64, interpret=True)
    for pos in (64, 128, 192):
        step = ops.flash_attention(q_full[:, :, pos - 1:pos, :],
                                   k[:, :, :pos, :], v[:, :, :pos, :],
                                   causal=False, block_q=64, block_k=64,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(step[:, :, 0, :]),
                                   np.asarray(full[:, :, pos - 1, :]),
                                   atol=2e-5, rtol=2e-5)


SSD_CASES = [
    (2, 4, 256, 32, 16, 64, jnp.float32),
    (1, 2, 128, 64, 128, 32, jnp.float32),
    (1, 2, 128, 32, 16, 128, jnp.float32),   # single chunk
    (2, 2, 64, 16, 16, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("B,H,S,P,N,Q,dtype", SSD_CASES)
def test_ssd_scan(B, H, S, P, N, Q, dtype):
    xdt = _rand((B, H, S, P), dtype) * 0.3
    a = -jnp.abs(_rand((B, H, S), jnp.float32)) * 0.4
    bm = _rand((B, S, N), dtype) * 0.3
    cm = _rand((B, S, N), dtype) * 0.3
    out = ops.ssd_scan(xdt, a, bm, cm, chunk=Q, interpret=True)
    exp = ref.ssd_reference(xdt, a, bm, cm)
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


def test_ssd_matches_model_chunked():
    """Kernel == the model's pure-JAX chunked path (same contract)."""
    from repro.models.ssm import ssd_chunked
    B, H, S, P, N = 2, 4, 128, 16, 32
    x = _rand((B, S, H, P), jnp.float32) * 0.3
    dt = jnp.abs(_rand((B, S, H), jnp.float32)) * 0.5 + 0.1
    A = -jnp.abs(_rand((H,), jnp.float32)) - 0.5
    bm = _rand((B, S, N), jnp.float32) * 0.3
    cm = _rand((B, S, N), jnp.float32) * 0.3
    y_model, _ = ssd_chunked(x, dt, A, bm, cm, chunk=32)
    xdt = jnp.moveaxis(x * dt[..., None], 1, 2)              # (B,H,S,P)
    a = jnp.moveaxis(dt * A[None, None, :], 1, 2)
    y_kernel = ops.ssd_scan(xdt, a, bm, cm, chunk=32, interpret=True)
    np.testing.assert_allclose(np.moveaxis(np.asarray(y_kernel), 1, 2),
                               np.asarray(y_model), atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("nblocks,block,width,nout", [
    (16, 8, 32, 10), (8, 16, 16, 8), (32, 8, 128, 32)])
def test_repack(nblocks, block, width, nout):
    src = _rand((nblocks, block, width), jnp.float32)
    idx = jnp.asarray(RNG.permutation(nblocks)[:nout], jnp.int32)
    out = ops.repack(src, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.repack_reference(src, idx)))


SSM_STATE_CASES = [
    # (L, B, H, P, N): a tiny stack, and the published Mamba-2 head shape
    # (granite-4.0-h-micro, mamba2) at a small batch
    (3, 2, 4, 8, 16),
    (3, 2, 64, 64, 128),
]


@pytest.mark.parametrize("fresh", [False, True], ids=["carried", "fresh"])
@pytest.mark.parametrize("L,B,H,P,N", SSM_STATE_CASES)
def test_ssm_state_update(L, B, H, P, N, fresh):
    """The one-pass update and read-out of the middle layer of a stack
    equals the XLA expression within float32 rounding (the sum over N may
    run in another order), and leaves every other layer bit for bit."""
    state = _rand((L, B, H, P, N), jnp.float32)
    a = jnp.exp(-jnp.abs(_rand((B, H), jnp.float32)))
    xdt = _rand((B, H, P), jnp.float32)
    bm, cm = _rand((B, N), jnp.float32), _rand((B, N), jnp.float32)
    layer = L // 2
    got, y = ops.ssm_state_update(state, jnp.int32(layer), jnp.bool_(fresh),
                                  a, xdt, bm, cm, interpret=True)
    want, y_want = ref.ssm_state_update_reference(
        state, layer, fresh, a, xdt, bm, cm)
    assert got.dtype == y.dtype == jnp.float32
    for g, w in ((got[layer], want[layer]), (y, y_want)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.max(np.abs(g - w)) <= 1e-6 * np.max(np.abs(w))
    others = [i for i in range(L) if i != layer]
    np.testing.assert_array_equal(np.asarray(got)[others],
                                  np.asarray(state)[others])


@pytest.mark.parametrize("heads,p,n,want", [
    (64, 64, 128, 64),        # a sequence's 2 MiB of state a block
    (32, 64, 128, 32),
    (128, 64, 256, 32),
    (4, 8, 16, 4),            # all the heads of a small model
    (48, 64, 256, 24),        # a multiple of 8, not all of them
    (24, 256, 512, 8),        # none fits: the least the tiling allows
])
def test_ssm_state_block_heads(heads, p, n, want):
    from repro.kernels.ssm_state import block_heads
    assert block_heads(heads, p, n) == want
