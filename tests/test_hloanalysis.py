"""Trip-count-aware HLO analysis: verified against a hand-computable scan."""
import json

from tests.util import run_devices

SCRIPT = r"""
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hloanalysis import analyze_hlo
from repro.parallel.mesh import make_mesh

mesh = make_mesh((8,), ("d",))

def model(x, w):
    def body(c, wi):
        return jnp.tanh(c @ wi), None
    y, _ = jax.lax.scan(body, x, w)
    return y.sum()

for L in (4, 16):
    c = jax.jit(model, in_shardings=(
        NamedSharding(mesh, P("d", None)),
        NamedSharding(mesh, P(None, None, None)))).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((L, 256, 256), jnp.float32)).compile()
    an = analyze_hlo(c.as_text(), 8)
    expect = 2 * (128 / 8) * 256 * 256 * L
    ratio = an.dot_flops / expect
    assert 0.99 < ratio < 1.01, (L, ratio)       # trip count folded in
    assert an.n_whiles >= 1
    assert an.collective_wire_bytes > 0          # the final psum
print("HLO_OK")
"""


def test_hlo_analysis_trip_counts():
    out = run_devices(SCRIPT, n_devices=8)
    assert "HLO_OK" in out
