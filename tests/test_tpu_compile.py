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
import functools
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


def test_kv_row_write_compiles(one_chip):
    """granite-3-2b's stacked (40, 256, 256, 8, 64) bf16 K and V at batch
    256, one layer's rows written at a traced layer and slot."""
    stack, rows = (40, 256, 256, 8, 64), (256, 8, 64)
    write = jax.jit(functools.partial(ops.kv_row_write, interpret=False))
    _assert_kernel(write.lower(
        _spec(one_chip, stack), _spec(one_chip, stack),
        _spec(one_chip, rows), _spec(one_chip, rows),
        _spec(one_chip, (), jnp.int32), _spec(one_chip, (), jnp.int32)))


def _assert_rows_written_in_place(compiled, rows, cache_len):
    """granite-3-2b's stacked (40, rows, cache_len, 8, 64) K and V, donated,
    lie on the chip with the positions in the lanes and are carried through
    the layer scan; the ``kv_row_write`` kernel takes them as bitcasts,
    viewed (40, rows, 8, 64, cache_len), and writes each layer's new rows
    into the tile column that holds the position. Every cache- or
    layer-sized buffer, whatever its leading dim, is a parameter, a tuple
    element or a bitcast, and the kernel's output the one pair of stacks
    written: no such copy, slice or dynamic-update-slice is left, nor a row
    padded to 128 lanes; the temporaries are far below one cache, and the
    output aliases the donated cache."""
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 256 * 2**20
    cache_bytes = 2 * 40 * rows * cache_len * 8 * 64 * 2
    assert mem.alias_size_in_bytes >= cache_bytes

    text = compiled.as_text()
    sized = (rf"bf16\[(?:\d+,)?(?:{rows},{cache_len},8,64|"
             rf"{rows},8,64,{cache_len})\]")
    cache_sized = re.findall(rf"= ({sized})\S* ([\w-]+)\(", text)
    assert cache_sized
    for shape, op in cache_sized:
        assert op in ("parameter", "get-tuple-element", "bitcast"), (shape, op)
    writers = re.findall(
        rf"%([\w-]+?)(?:\.\d+)? = \({sized}\S*, {sized}\S*\) ([\w-]+)\(",
        text)
    assert writers == [("kv_row_write", "custom-call")], writers
    assert f"bf16[1,{rows},1,8,64]" not in text


def test_decode_step_writes_the_cache_in_place(one_chip, monkeypatch):
    """granite-3-2b's row-stable step at batch 256 and cache 256 (its chip
    cell's), the cache donated, writes each layer's rows in place
    (``_assert_rows_written_in_place``): no cache- or layer-sized copy or
    slice, no row padded to bf16[1,256,1,8,64], temporaries under 256 MiB
    against 2.5 GiB a cache."""
    from repro.configs import get_config
    # the model asks the backend, here the CPU, whether to interpret its
    # kernels: the described chip compiles them
    monkeypatch.setattr(ops, "interpret_here", lambda: False)
    cfg = get_config("granite-3-2b")
    compiled = _serve_step(cfg, one_chip, 256, 256, donate_argnums=(1,))
    _assert_rows_written_in_place(compiled, 256, 256)


def test_long_cache_decode_step_writes_the_cache_in_place(one_chip,
                                                         monkeypatch):
    """At decode_32k's depth, a 32768-deep cache, granite-3-2b's stacks at
    batch 2 also lie with the positions in the lanes: the row-stable step
    writes each layer's rows with the kernel, in place, and converts no
    stack on the way in or out."""
    from repro.configs import get_config
    monkeypatch.setattr(ops, "interpret_here", lambda: False)
    cfg = get_config("granite-3-2b")
    compiled = _serve_step(cfg, one_chip, 2, 32768, donate_argnums=(1,))
    _assert_rows_written_in_place(compiled, 2, 32768)


def test_head_dim_128_decode_step_keeps_the_xla_write(one_chip, monkeypatch):
    """phi4-mini's head dim (128) fills the lanes, so its (32, 8, 256, 8,
    128) K and V stacks enter row-major, a position whole (8, 128) tiles:
    the step at batch 8 and cache 256 writes them with XLA's
    dynamic-update-slice, takes no ``kv_row_write``, and its temporaries
    are the XLA write's (268,823,040 bytes)."""
    from repro.configs import get_config
    monkeypatch.setattr(ops, "interpret_here", lambda: False)
    cfg = get_config("phi4-mini-3.8b")
    compiled = _serve_step(cfg, one_chip, 8, 256, donate_argnums=(1,))
    text = compiled.as_text()
    assert not re.search(r"%kv_row_write\S* = ", text)
    entry = text[text.index("\nENTRY"):]
    layouts = re.findall(r"= bf16\[32,8,256,8,128\](\{[^}]*\}) parameter\(",
                         entry)
    assert layouts and set(layouts) == {"{4,3,2,1,0:T(8,128)(2,1)}"}, layouts
    assert "dynamic-update-slice(" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 268_823_040


def _shape(leaf, whole):
    """A regex of the HLO shape of a stacked cache leaf (``whole``) or of
    any buffer that ends in one layer's slice of it."""
    dims = ",".join(map(str, leaf.shape[1:]))
    dtype = {"float32": "f32", "bfloat16": "bf16"}[str(leaf.dtype)]
    lead = f"{leaf.shape[0]}," if whole else r"(?:\d+,)*"
    return rf"{dtype}\[{lead}{dims}\]"


def _bytes(shape):
    dtype, dims = re.fullmatch(r"(\w+)\[([\d,]*)\]", shape).groups()
    n = {"f32": 4, "bf16": 2}[dtype]
    for d in dims.split(","):
        n *= int(d)
    return n


@pytest.mark.parametrize("arch,rows,runs,layout_copies", [
    ("granite-4.0-h-micro", 96, 2, 0),   # 5 Mamba, 1 attention, 4 Mamba
    ("zamba2-2.7b", 8, 1, 2),            # 6 Mamba, the shared block
    ("mamba2-370m", 8, 1, 0)])
def test_hybrid_decode_step_carries_its_state_in_place(
        one_chip, monkeypatch, arch, rows, runs, layout_copies):
    """A decode step of a model with Mamba layers, at its published widths
    and depth with ``rows`` sequences and cache 256, the cache donated
    (granite-4.0-h-micro's is its chip cell's: a (36, 96, 64, 64, 128)
    float32 state and (4, 96, 256, 8, 64) K and V). The SSM state, the conv
    windows and any K and V are carried through the layer scans and each
    layer's slice is updated where it lies: in the scans no state-sized or
    KV-sized copy or dynamic-slice is left, nor a buffer of one layer's
    state or KV; each Mamba layer's state is read by one operation (the
    kernel that updates it in place and reads it out); the output aliases
    the donated cache, and the whole step fits one chip's 15.75 GiB.

    Outside the scans every such buffer is a whole stack, none a copy,
    and the temporaries are under 0.1 GiB, but for ``layout_copies``:
    zamba2's state lies on the chip with its 80 heads in the lanes, while
    its kernel takes the state row-major (N=64 in the lanes), so the step
    converts the whole state stack on the way in and out. Its K and V lie
    with the 256 positions in the lanes, where the batch read and the row
    write (``kv_row_write``) take them."""
    from repro.configs import get_config
    from repro.models import model as M
    # the model asks the backend, here the CPU, whether to interpret its
    # kernel: the described chip compiles it
    monkeypatch.setattr(ops, "interpret_here", lambda: False)
    cfg = get_config(arch)
    compiled = _serve_step(cfg, one_chip, rows, 256, row_stable=False,
                           donate_argnums=(1,))
    mem = compiled.memory_analysis()
    cache = jax.eval_shape(lambda: M.init_cache(cfg, rows, 256))
    stacks = [c["state"] for c in cache.values() if "state" in c] + \
        [c["k"] for c in cache.values() if "k" in c]
    assert mem.alias_size_in_bytes >= stacks[0].size * 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2**30

    # buffers: instructions outside the fusions' own computations (inside
    # one, a dynamic-slice is a read fused into its consumer)
    text = compiled.as_text()
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.-]+)", text))
    bodies = [b for b in re.split(r"\n(?=\S)", text)
              if b.split(" ", 1)[0] not in fused]
    sized = "|".join(_shape(t, whole=False) for t in stacks)
    whole = re.compile("|".join(_shape(t, whole=True) for t in stacks))

    def buffers(entry):
        return re.findall(rf"= ((?:{sized}))\S* ([\w-]+)\(", "\n".join(
            b for b in bodies if b.startswith("ENTRY") == entry))

    assert buffers(entry=False)
    for shape, op in buffers(entry=False):
        assert op not in ("copy", "dynamic-slice"), (shape, op)
        assert whole.fullmatch(shape), (shape, op)
    outside = buffers(entry=True)
    assert all(whole.fullmatch(shape) for shape, _ in outside), outside
    copies = [shape for shape, op in outside
              if op.startswith("copy") or op == "dynamic-slice"]
    assert len(copies) == layout_copies, copies
    assert mem.temp_size_in_bytes < 0.1 * 2**30 + sum(map(_bytes, copies))

    # the operations that take a state stack as an operand, computation by
    # computation, past the layout copies counted above: one in each Mamba
    # run's scan body, the kernel
    shapes = dict(re.findall(r"(%[\w.-]+) = (\w+\[[\d,]*\])", text))
    state = re.compile(_shape(stacks[0], whole=True))
    moves = ("parameter", "get-tuple-element", "tuple", "while", "bitcast")
    readers = []
    for body in re.split(r"\n(?=\S)", text):
        skip = moves + (("copy",) if body.startswith("ENTRY") else ())
        ops_here = [op for op, operands in re.findall(
            r"\n\s*(?:ROOT )?%[\w.-]+ = .*? ([\w-]+)\(([^)]*)\)", body)
            if op not in skip and any(
                state.fullmatch(shapes.get(o, ""))
                for o in re.findall(r"%[\w.-]+", operands))]
        if ops_here:
            readers.append(ops_here)
    assert readers == [["custom-call"]] * runs, readers
