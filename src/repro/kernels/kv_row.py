"""One-position K/V row write Pallas TPU kernel: the new token's K and V
rows of one layer, written into the stacked cache where the chip keeps them.

A decode step writes one position of every sequence's K and V: a (B, Hkv,
hd) row into the (L, B, S, Hkv, hd) stack at (layer, :, slot). Where the
head dim fills no lane tile (hd % 128 != 0) and S fills whole ones, the
chip lays the stack out with the positions in the lanes, as (L, B, Hkv, hd,
S) row-major. A position is then one lane of every tile, and XLA's
dynamic-update-slice pads the row to 128 lanes and rewrites every tile it
touches, masked, at a fraction of the chip's bandwidth.

Here the stacks are viewed as (L, B, Hkv, hd, S), a bitcast of that layout,
and aliased to the outputs. The layer and the slot ride in scalar prefetch
and pick the blocks: ``block_seqs`` sequences' 128-position tile column
that holds the slot, of that layer alone. Each block is read, the new row
is selected into lane ``slot % 128`` in VMEM, and the block is written back
in place; every other column, layer and position keeps its bytes.

Grid (B // block_seqs,): K and V in one call, each block at most 1 MiB, so
in and out, K and V, double-buffered hold ~8 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: bytes of one block of the stack, at most
BLOCK_BYTES = 1 << 20


def lane_major(shape) -> bool:
    """Whether the chip keeps a (.., S, Hkv, hd) stack with the positions in
    the lanes, the layout this kernel writes: the head dim fills no lane
    tile and S fills whole ones."""
    *_, s, _, hd = shape
    return hd % LANES != 0 and s % LANES == 0


def block_seqs(batch: int, heads: int, hd: int, itemsize: int) -> int:
    """The most sequences, a divisor of ``batch``, whose tile column of one
    layer fits ``BLOCK_BYTES``; at least one."""
    per_seq = heads * hd * LANES * itemsize
    return max(d for d in range(1, batch + 1)
               if batch % d == 0 and (d == 1 or d * per_seq <= BLOCK_BYTES))


def _row_kernel(last, layer_ref, slot_ref, kn_ref, vn_ref, k_ref, v_ref,
                k_out, v_out):
    del layer_ref                                # consumed by the index_maps
    lane = jnp.minimum(slot_ref[0], last) % LANES
    at = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 4) == lane
    for new, old, out in ((kn_ref, k_ref, k_out), (vn_ref, v_ref, v_out)):
        # the row moves from the lanes to the sublanes as float32, which
        # holds every bf16 value exactly: the chip has no bf16 form of it
        row = new[...].astype(jnp.float32)[None, ..., None]
        out[...] = jnp.where(at, row.astype(out.dtype), old[...])


def kv_row_write_fwd(k, v, k_new, v_new, layer, slot, *,
                     interpret: bool = False):
    """Write the rows ``k_new``, ``v_new`` into layer ``layer``, position
    ``slot`` of the stacks ``k``, ``v``.

    k, v:         (L, B, S, Hkv, hd), ``lane_major`` shapes
    k_new, v_new: (B, Hkv, hd), cast to the stacks' dtype
    layer, slot:  int32 scalars, ``0 <= layer < L``, ``0 <= slot < S``
    Returns the two stacks, changed at (layer, :, slot) alone. A slot past
    the end is clamped to the last position, as dynamic_update_slice does.
    """
    L, B, S, H, D = k.shape
    assert lane_major(k.shape), k.shape
    bb = block_seqs(B, H, D, k.dtype.itemsize)
    view = (0, 1, 3, 4, 2)                       # (L, B, Hkv, hd, S)
    rows = pl.BlockSpec((bb, H, D), lambda b, l, s: (b, 0, 0))
    # a slot past the end is the last one, as in dynamic_update_slice
    blocks = pl.BlockSpec(
        (1, bb, H, D, LANES),
        lambda b, l, s: (l[0], b, 0, 0, jnp.minimum(s[0], S - 1) // LANES))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // bb,),
        in_specs=[rows, rows, blocks, blocks],
        out_specs=[blocks, blocks],
    )
    kt, vt = (jnp.transpose(t, view) for t in (k, v))
    kt, vt = pl.pallas_call(
        functools.partial(_row_kernel, S - 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        input_output_aliases={4: 0, 5: 1},
        name="kv_row_write",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(slot, (1,)).astype(jnp.int32),
      k_new.astype(k.dtype), v_new.astype(v.dtype), kt, vt)
    back = (0, 1, 4, 2, 3)
    return jnp.transpose(kt, back), jnp.transpose(vt, back)
