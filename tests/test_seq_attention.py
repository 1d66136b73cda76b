"""Sequence-parallel shard_map attention == reference path (fwd + grad).

Triggered when q-head count is not divisible by the model axis (phi4 24H,
qwen2.5 40H on model=16); here 4 heads on model=8 forces the same path.
"""
from tests.util import run_devices

SCRIPT = r"""
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import attention as A
from repro.models.params import init as pinit
from repro.parallel.context import sharding_context
from repro.parallel.mesh import make_mesh
from repro.parallel.sharding import rules_for

cfg = get_config("qwen2.5-32b-smoke")    # 4 heads, kv=2, qkv_bias=True
mesh = make_mesh((1, 8), ("data", "model"))
params = pinit(A.attention_schema(cfg), jax.random.PRNGKey(0))
x = jnp.asarray(np.random.default_rng(0).standard_normal(
    (2, 64, cfg.d_model)), jnp.float32)
pos = jnp.arange(64)[None, :]

ref = A.attn_apply(params, x, cfg, positions=pos, causal=True)
with sharding_context(mesh, rules_for(cfg)):
    out = jax.jit(lambda p, xx: A.attn_apply(p, xx, cfg, positions=pos,
                                             causal=True))(params, x)
assert float(jnp.max(jnp.abs(out - ref))) < 1e-5

def loss(p, use_ctx):
    if use_ctx:
        with sharding_context(mesh, rules_for(cfg)):
            return jnp.sum(A.attn_apply(p, x, cfg, positions=pos,
                                        causal=True) ** 2)
    return jnp.sum(A.attn_apply(p, x, cfg, positions=pos, causal=True) ** 2)

g1 = jax.grad(lambda p: loss(p, False))(params)
g2 = jax.jit(jax.grad(lambda p: loss(p, True)))(params)
errs = [float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2))]
assert max(errs) < 2e-3, errs
print("SEQ_ATTN_OK")
"""


def test_seq_parallel_attention_matches_reference():
    out = run_devices(SCRIPT, n_devices=8)
    assert "SEQ_ATTN_OK" in out
