"""shard_map MoE dispatch == global reference (8-device child interpreter).

With a generous capacity factor nothing is dropped, so the EP (all-to-all)
and expert-TP (psum) paths must match the mesh-agnostic reference exactly
(up to f32 reduction order).
"""
from tests.util import run_devices

SCRIPT = r"""
import warnings; warnings.filterwarnings("ignore")
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import moe as MOE
from repro.models.params import init as pinit
from repro.parallel.context import sharding_context
from repro.parallel.mesh import make_mesh
from repro.parallel.sharding import rules_for

mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)

for arch, ep in [("qwen3-moe-235b-a22b", True), ("mixtral-8x7b", False)]:
    cfg = get_config(arch + "-smoke")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=8,
                                     capacity_factor=8.0))
    params = pinit(MOE.moe_schema(cfg), jax.random.PRNGKey(0))
    x = jnp.asarray(rng.standard_normal((4, 16, cfg.d_model)), jnp.float32)

    y_ref, aux_ref = MOE.moe_apply_reference(params, x, cfg)

    rules = rules_for(cfg)
    if not ep:
        rules = dict(rules, experts=None, expert_mlp=("model",))
    # go through moe_apply under a real context so the merged activation
    # rules (seq-sharded residual!) are exercised — the TP-mode token-mixing
    # bug was invisible with weight-only rules.
    with sharding_context(mesh, rules):
        y_sm, aux_sm = jax.jit(
            lambda p, xx: MOE.moe_apply(p, xx, cfg))(params, x)
    err = float(jnp.max(jnp.abs(y_sm - y_ref)))
    aerr = abs(float(aux_sm) - float(aux_ref))
    mode = "EP" if ep else "TP"
    print(f"{arch} [{mode}] err={err:.2e} aux_err={aerr:.2e}")
    assert err < 5e-5, (arch, err)
    # aux load-balance loss is a per-shard estimator pmean'd over shards:
    # sum(density*density_prob) is nonlinear in the per-shard means, so it
    # differs from the global estimator at O(cross-shard variance) — the
    # standard GShard-style local balance loss. Outputs above are exact.
    assert aerr < 5e-3, (arch, aerr)
print("MOE_SHARDMAP_OK")
"""


def test_moe_shardmap_matches_reference():
    out = run_devices(SCRIPT, n_devices=8)
    assert "MOE_SHARDMAP_OK" in out
