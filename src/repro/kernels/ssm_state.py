"""Mamba-2 decode state update Pallas TPU kernel: one pass over the state.

One token of a Mamba-2 layer updates each head's (P, N) state and reads it
out (``selective_state_update`` in mamba_ssm):

    h' = h * exp(dt*A) + (x*dt) (outer) B        y = sum_n h'[..., n] * C[n]

As XLA ops the read-out is a second pass that reads the new state back from
HBM. Here each block of the state is read once, updated in VMEM, written
back in place and reduced against C while it is there: the state crosses
HBM once in and once out, the least a step can move.

The state is a stack of layers, (L, B, H, P, N), aliased to the output. The
layer, a traced scalar, rides in scalar prefetch and picks the blocks, so
only that layer's blocks are read and written and the others keep their
values. ``fresh`` (nonzero at a sequence's first position) takes the old
state as zero. The arithmetic is float32 whatever the state is stored in,
and the read-out reads the new state before it is rounded to that.

Grid (B, H // block_heads): a block is ``block_heads`` heads of one
sequence, at most 2 MiB of float32 state: at (H, P, N) = (64, 64, 128) a
sequence's whole state. In and out, double-buffered, with the new state
and its product with C, that is ~12 MiB of VMEM, inside the default scope.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of float32 state in one block, at most
BLOCK_BYTES = 2 << 20


def _state_kernel(layer_ref, fresh_ref, a_ref, x_ref, b_ref, c_ref, s_ref,
                  s_out, y_ref):
    del layer_ref                                # consumed by the index_maps
    h = s_ref[0, 0].astype(jnp.float32)          # (Hb, P, N)
    h = jnp.where(fresh_ref[0] != 0, 0.0, h)
    a = a_ref[0]                                 # (Hb, P): exp(dt*A), per head
    x = x_ref[0]                                 # (Hb, P): x*dt
    new = h * a[:, :, None] + x[:, :, None] * b_ref[0][None]
    s_out[0, 0] = new.astype(s_out.dtype)
    y_ref[0] = jnp.sum(new * c_ref[0][None], axis=-1)


def block_heads(heads: int, p: int, n: int) -> int:
    """The most heads whose float32 state fits ``BLOCK_BYTES``: a divisor of
    ``heads`` that is all of them or a multiple of 8, as the chip's tiling
    asks of a (heads, P) block."""
    valid = [hb for hb in range(1, heads + 1)
             if heads % hb == 0 and (hb % 8 == 0 or hb == heads)]
    fit = [hb for hb in valid if 4 * hb * p * n <= BLOCK_BYTES]
    return max(fit) if fit else min(valid)


def ssm_state_update_fwd(state, layer, fresh, a, xdt, bm, cm, *,
                         interpret: bool = False):
    """One token's state update and read-out of layer ``layer``.

    state: (L, B, H, P, N) the stack of every layer's state
    layer: int32 scalar, the layer to update
    fresh: bool scalar, the old state is taken as zero
    a:     (B, H)    the decay exp(dt*A)
    xdt:   (B, H, P) the input times dt
    bm, cm: (B, N)   the input and output mixers
    Returns (the stack with layer ``layer`` updated, y (B, H, P) float32).
    """
    L, B, H, P, N = state.shape
    hb = block_heads(H, P, N)
    f32 = jnp.float32
    a = jnp.broadcast_to(a.astype(f32)[..., None], (B, H, P))
    xdt = xdt.astype(f32)
    bm = bm.astype(f32).reshape(B, 1, N)
    cm = cm.astype(f32).reshape(B, 1, N)

    heads = pl.BlockSpec((1, hb, P), lambda b, j, l, f: (b, j, 0))
    mixer = pl.BlockSpec((1, 1, N), lambda b, j, l, f: (b, 0, 0))
    blocks = pl.BlockSpec((1, 1, hb, P, N),
                          lambda b, j, l, f: (l[0], b, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // hb),
        in_specs=[heads, heads, mixer, mixer, blocks],
        out_specs=[blocks, heads],
    )
    return pl.pallas_call(
        _state_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, P), f32)],
        input_output_aliases={6: 0},
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(fresh, (1,)).astype(jnp.int32), a, xdt, bm, cm, state)
