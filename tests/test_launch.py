"""Entry points: the in-process train loop, the compile-cache helper, the
peak table, and the benchmarks' refusal of an undersized backend."""
import math

import jax
import pytest

from repro.launch import device, train
from repro.launch.roofline import PEAKS, chip_peaks


def test_train_run_in_process_on_one_device():
    args = train.parse_args(["--arch", "mamba2-370m-smoke", "--steps", "3",
                             "--global-batch", "2", "--seq-len", "32"])
    out = train.run(args, devices=jax.devices()[:1], log=lambda _: None)
    assert len(out["losses"]) == len(out["step_s"]) == 3
    assert out["workers"] == out["state_devices"] == [1, 1, 1]
    assert all(math.isfinite(l) for l in out["losses"])
    assert not out["events"]


def test_train_defaults_span_the_pool():
    args = train.parse_args(["--arch", "mamba2-370m-smoke"])
    assert (args.min, args.max, args.pref) == (1, None, None)


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env):
    old = jax.config.jax_compilation_cache_dir
    old_key = jax.config.jax_compilation_cache_include_metadata_in_key
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = device.enable_compile_cache()
        if env:
            assert path == env
            assert jax.config.jax_compilation_cache_dir == old   # untouched
        else:
            assert path == f"{device.CHECKOUT}/.jax_cache"
            assert jax.config.jax_compilation_cache_dir == path
        # scopes are part of the key: no executable with stale op_names
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          old_key)


def test_describe_names_the_device():
    d = device.describe(jax.devices()[:1])
    assert d == {"platform": jax.devices()[0].platform,
                 "kind": jax.devices()[0].device_kind, "count": 1}


def test_chip_peaks_by_device_kind():
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


@pytest.mark.parametrize("name", ["live_cluster", "mixed_pool",
                                  "redistribution_overhead"])
def test_live_benchmarks_refuse_an_undersized_backend(name):
    import importlib
    mod = importlib.import_module(f"benchmarks.{name}")
    with pytest.raises(RuntimeError, match="needs 8 devices"):
        mod.run()
