"""AdamW with decoupled weight decay and global-norm clipping (pure JAX).

Optimizer moments live in the ``TrainState`` pytree, so a malleability resize
redistributes them exactly like parameters — the paper's "robust restart"
(§3, Fig. 2) covers the full job state, not just model weights.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    mu: Any          # first moment  (pytree like params)
    nu: Any          # second moment (pytree like params)
    count: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class AdamW:
    learning_rate: Callable[[jnp.ndarray], jnp.ndarray] | float = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    moment_dtype: str = "float32"   # bf16 for the 235B-class archs (DESIGN.md)

    def _lr(self, count):
        if callable(self.learning_rate):
            return self.learning_rate(count)
        return jnp.asarray(self.learning_rate, jnp.float32)

    def init(self, params) -> OptState:
        dt = jnp.dtype(self.moment_dtype)
        zeros = lambda p: jnp.zeros(p.shape, dt)
        return OptState(mu=jax.tree.map(zeros, params),
                        nu=jax.tree.map(zeros, params),
                        count=jnp.zeros((), jnp.int32))

    @jax.named_scope("optimizer")
    def update(self, grads, state: OptState, params):
        count = state.count + 1
        cf = count.astype(jnp.float32)

        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if self.clip_norm is not None:
            gnorm = global_norm(grads)
            scale = jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        else:
            gnorm = global_norm(grads)

        mdt = jnp.dtype(self.moment_dtype)
        mu = jax.tree.map(
            lambda m, g: (self.b1 * m.astype(jnp.float32)
                          + (1 - self.b1) * g).astype(mdt),
            state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: (self.b2 * v.astype(jnp.float32)
                          + (1 - self.b2) * jnp.square(g)).astype(mdt),
            state.nu, grads)

        bc1 = 1 - self.b1 ** cf
        bc2 = 1 - self.b2 ** cf
        lr = self._lr(count)

        def upd(p, m, v):
            mhat = m.astype(jnp.float32) / bc1
            vhat = v.astype(jnp.float32) / bc2
            step = mhat / (jnp.sqrt(vhat) + self.eps)
            if self.weight_decay and p.ndim >= 2:   # no decay on norms/biases
                step = step + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, OptState(mu=mu, nu=nu, count=count), gnorm


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))
