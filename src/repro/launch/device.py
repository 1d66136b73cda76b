"""What the entry points share about the device: its description and the
persistent compilation cache.

``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before they compile anything. The test suite
does not: a compile for a described chip is written to the cache but cannot
be read back without one.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax

#: the checkout's root (``src/repro/launch/device.py`` -> three levels up)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache is the fixed path
    ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    directory that moved would never hit.

    The key holds the programs' metadata: an executable read back carries
    the ``op_name`` scopes of the code that asks for it, which a profile
    reads (``models/``'s ``jax.named_scope``s). By JAX's default, code that
    differs only in its scopes would get an executable compiled for other
    scopes, and its profile would show those.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe(devices: Optional[Sequence] = None) -> Dict:
    """``{"platform", "kind", "count"}`` of ``devices`` (default: all)."""
    devices = list(devices) if devices is not None else jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
