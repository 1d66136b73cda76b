"""Weights from a seed, the benchmark's own: the program never makes them.

A model's layout (``refs/<arch>.py``'s ``layout``) is a pytree of
:class:`Leaf`. Leaf ``i`` of the flattened tree draws from
``fold_in(key(seed), i)``; a stacked leaf draws layer ``l`` from
``fold_in(that key, l)``, so a reference can make one layer's slice alone
and get the same numbers as the whole stack. Values are drawn in float32 and
rounded once to the dtype they are served or trained in.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]       # of one layer's slice when ``layers``
    init: str = "normal"         # normal | uniform | ones | zeros |
                                 # log_uniform | dt_bias
    scale: float = 0.02          # normal: std; uniform: bound
    lo: float = 0.0              # log_uniform / dt_bias range
    hi: float = 0.0
    layers: int = 0              # > 0: a stack of this many layers

    @property
    def full_shape(self) -> Tuple[int, ...]:
        return ((self.layers,) if self.layers else ()) + tuple(self.shape)


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def base_key(seed: int):
    """A key from any whole number, also one wider than 32 bits."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def leaf_keys(layout, key):
    """The key of every leaf, as a tree shaped like ``layout``; ``key`` is
    ``base_key(seed)``, passed in so that no program bakes in the seed."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=is_leaf)
    return jax.tree.unflatten(
        treedef, [jax.random.fold_in(key, i) for i in range(len(leaves))])


def draw(leaf: Leaf, key) -> jnp.ndarray:
    """One layer's slice (or the whole of an unstacked leaf), float32."""
    shp, f32 = tuple(leaf.shape), jnp.float32
    if leaf.init == "zeros":
        return jnp.zeros(shp, f32)
    if leaf.init == "ones":
        return jnp.ones(shp, f32)
    if leaf.init == "normal":
        return jax.random.normal(key, shp, f32) * leaf.scale
    if leaf.init == "uniform":
        return jax.random.uniform(key, shp, f32, -leaf.scale, leaf.scale)
    if leaf.init == "log_uniform":        # log of U(lo, hi): Mamba-2's A_log
        return jnp.log(jax.random.uniform(key, shp, f32, leaf.lo, leaf.hi))
    if leaf.init == "dt_bias":            # softplus^-1 of log-uniform dt
        dt = jnp.exp(jax.random.uniform(key, shp, f32, jnp.log(leaf.lo),
                                        jnp.log(leaf.hi)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {leaf.init!r}")


def leaf_value(leaf: Leaf, key, dtype) -> jnp.ndarray:
    """The whole leaf, rounded to ``dtype``."""
    if leaf.layers:
        out = jax.vmap(lambda l: draw(leaf, jax.random.fold_in(key, l)))(
            jnp.arange(leaf.layers))
    else:
        out = draw(leaf, key)
    return out.astype(dtype)


def layer_value(leaf: Leaf, key, layer, dtype) -> jnp.ndarray:
    """Layer ``layer`` of a stacked leaf, rounded to ``dtype`` — the same
    numbers as ``leaf_value(...)[layer]``."""
    return draw(leaf, jax.random.fold_in(key, layer)).astype(dtype)


def values(layout, key, dtype) -> Any:
    """Every leaf, traced: call inside ``jax.jit`` with ``base_key(seed)``."""
    keys = leaf_keys(layout, key)
    return jax.tree.map(lambda l, k: leaf_value(l, k, dtype), layout, keys,
                        is_leaf=is_leaf)


def make(layout, seed: int, dtype, shardings: Optional[Any] = None):
    """All weights on the device in one jitted call, placed by
    ``shardings`` (a tree like ``layout``) when given."""
    fn = jax.jit(lambda k: values(layout, k, dtype), out_shardings=shardings)
    return fn(base_key(seed))
