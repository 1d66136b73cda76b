"""The Pallas kernels compile for a described TPU v5e chip at model widths.

Nothing runs: the chip's compiler is installed here and compiles for a chip
that is described, not attached. It refuses block shapes that break the
tiling rules and kernels that overflow fast memory, which interpret mode
(tests/test_kernels.py) cannot see. The widths are granite-3-2b's attention
(D=64, 32 query / 8 KV heads) and mamba2-370m's SSD (32 heads, P=64, N=128,
chunk 256).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers each import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch,sq", [(1, 2048), (8, 1)],
                         ids=["prefill_2048", "decode_sq1"])
def test_flash_attention_compiles(one_chip, batch, sq):
    q = _spec(one_chip, (batch, 32, sq, 64))
    kv = _spec(one_chip, (batch, 8, 2048, 64))
    _assert_kernel(ops.flash_attention.lower(q, kv, kv, causal=sq > 1))


def test_ssd_scan_compiles(one_chip):
    B, H, S, P, N = 1, 32, 2048, 64, 128
    _assert_kernel(ops.ssd_scan.lower(
        _spec(one_chip, (B, H, S, P)),
        _spec(one_chip, (B, H, S), jnp.float32),
        _spec(one_chip, (B, S, N)), _spec(one_chip, (B, S, N)), chunk=256))


def test_ssm_state_update_compiles(one_chip):
    """granite-4.0-h-micro's stacked state at batch 96: 36 layers of
    (96, 64, 64, 128) float32, updated at a traced layer."""
    L, B, H, P, N = 36, 96, 64, 64, 128
    f32 = jnp.float32
    _assert_kernel(ops.ssm_state_update.lower(
        _spec(one_chip, (L, B, H, P, N), f32), _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (), jnp.bool_), _spec(one_chip, (B, H), f32),
        _spec(one_chip, (B, H, P), f32), _spec(one_chip, (B, N), f32),
        _spec(one_chip, (B, N), f32)))


def test_repack_compiles(one_chip):
    _assert_kernel(ops.repack.lower(
        _spec(one_chip, (64, 8, 2048), jnp.float32),
        _spec(one_chip, (64,), jnp.int32)))


def _serve_step(cfg, sharding, rows, cache_len, row_stable=True, **jit_kw):
    """The chip's compiled decode step (row-stable by default) over ``rows``
    sequences and a ``cache_len``-deep cache."""
    from repro.models import model as M
    from repro.models.train import make_serve_step

    def spec(a):
        return _spec(sharding, a.shape, a.dtype)

    params = jax.tree.map(lambda a: _spec(sharding, a.shape),
                          M.abstract_params(cfg))
    cache = jax.tree.map(spec, jax.eval_shape(
        lambda: M.init_cache(cfg, rows, cache_len)))
    return jax.jit(make_serve_step(cfg, row_stable=row_stable),
                   **jit_kw).lower(
        params, cache, _spec(sharding, (rows, 1), jnp.int32),
        _spec(sharding, (), jnp.int32)).compile()


def _matmul_shapes(cfg, sharding, rows):
    """Output shapes of the matmuls (lowered to convolutions) in the chip's
    program for a row-stable decode step over ``rows`` sequences."""
    text = _serve_step(cfg, sharding, rows, 128).as_text()
    return sorted(re.findall(r"= (\w+\[[\d,]*\])\S* convolution\(", text))


def test_row_stable_decode_runs_the_same_matmuls(one_chip):
    """A granite-3-2b replica's device holds 8, 4 or 2 of its 8 sequences on
    1, 2 or 4 chips. The row-stable step runs every matmul at the same shape
    and dtype whatever the share (the compiler must not move the row slice
    into a projection), so the chip computes each sequence the same way."""
    from repro.configs import get_config
    cfg = get_config("granite-3-2b")
    shapes = {rows: _matmul_shapes(cfg, one_chip, rows) for rows in (8, 4, 2)}
    assert shapes[8] and shapes[4] == shapes[8] and shapes[2] == shapes[8]


def test_decode_step_writes_the_cache_in_place(one_chip):
    """granite-3-2b's row-stable step at batch 256 and cache 256, the cache
    donated: the stacked (40, 256, 256, 8, 64) K and V caches are carried
    through the layer scan and only the new row of each layer is written.
    No whole-cache or layer-sized cache copy or slice is left, the
    temporaries are far below one cache (2.5 GiB each), and the output
    aliases the donated cache."""
    from repro.configs import get_config
    cfg = get_config("granite-3-2b")
    compiled = _serve_step(cfg, one_chip, 256, 256, donate_argnums=(1,))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 256 * 2**20
    cache_bytes = 2 * 40 * 256 * 256 * 8 * 64 * 2
    assert mem.alias_size_in_bytes >= cache_bytes

    text = compiled.as_text()
    shapes = dict(re.findall(r"(%[\w.-]+) = (\w+\[[\d,]*\])", text))
    cache_sized = re.findall(
        r"= (bf16\[(?:\d+,)?256,256,8,64\])\S* ([\w-]+)\(([^)]*)\)", text)
    assert cache_sized
    for shape, op, operands in cache_sized:
        assert op in ("parameter", "get-tuple-element",
                      "dynamic-update-slice"), (shape, op)
        if op == "dynamic-update-slice":
            update = shapes[operands.split(", ")[1]]
            assert shape == "bf16[40,256,256,8,64]"
            assert update == "bf16[1,256,1,8,64]", update


def test_hybrid_decode_step_carries_its_state_in_place(one_chip,
                                                        monkeypatch):
    """granite-4.0-h-micro's step at batch 96 and cache 256, the cache
    donated: the (36, 96, 64, 64, 128) float32 SSM state, the conv windows
    and the (4, 96, 256, 8, 64) K and V are carried through the layer scan
    and each layer's slice is updated where it lies. No state-sized copy or
    dynamic-slice is left, each Mamba layer's state is read by one
    operation (the kernel that updates it in place and reads it out), the
    temporaries are under 0.1 GiB, the output aliases the donated cache,
    and the whole step fits one chip's 15.75 GiB."""
    from repro.configs import get_config
    # the model asks the backend, here the CPU, whether to interpret its
    # kernel: the described chip compiles it
    monkeypatch.setattr(ops, "interpret_here", lambda: False)
    cfg = get_config("granite-4.0-h-micro")
    compiled = _serve_step(cfg, one_chip, 96, 256, row_stable=False,
                           donate_argnums=(1,))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1 * 2**30
    state_bytes = 36 * 96 * 64 * 64 * 128 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2**30

    # buffers: instructions outside the fusions' own computations (inside
    # one, a dynamic-slice is a read fused into its consumer)
    text = compiled.as_text()
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.-]+)", text))
    bodies = re.split(r"\n(?=\S)", text)
    buffers = "\n".join(b for b in bodies
                        if b.split(" ", 1)[0] not in fused)
    big = re.findall(r"= ((?:f32\[(?:36,|1,)?96,64,64,128\]|"
                     r"bf16\[(?:4,|1,)?96,256,8,64\]))\S* ([\w-]+)\(",
                     buffers)
    assert big
    for shape, op in big:
        assert op not in ("copy", "dynamic-slice"), (shape, op)
        assert shape.startswith(("f32[36,", "bf16[4,")), (shape, op)

    # the operations that take a state stack as an operand, computation by
    # computation: one in each Mamba run's scan body, the kernel
    shapes = dict(re.findall(r"(%[\w.-]+) = (\w+\[[\d,]*\])", text))
    state = re.compile(r"f32\[(?:36,|1,)?96,64,64,128\]")
    moves = ("parameter", "get-tuple-element", "tuple", "while", "bitcast")
    readers = []
    for body in bodies:
        ops_here = [op for op, operands in re.findall(
            r"\n\s*(?:ROOT )?%[\w.-]+ = .*? ([\w-]+)\(([^)]*)\)", body)
            if op not in moves and any(
                state.fullmatch(shapes.get(o, ""))
                for o in re.findall(r"%[\w.-]+", operands))]
        if ops_here:
            readers.append(ops_here)
    assert readers == [["custom-call"]] * 2, readers
