"""Mesh construction: production meshes, elastic job submeshes, bridge meshes.

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module never touches jax device state — required by the dry-run contract.

Every mesh of the repo is built here, with ``Auto`` axes: the model code pins
activations with ``with_sharding_constraint`` (``parallel/context.py``),
which JAX accepts only on ``Auto`` axes, while ``jax.make_mesh`` defaults to
``Explicit`` ones.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def factor_mesh(n: int, max_model: int = 16) -> Tuple[int, int]:
    """Pick a (data, model) factorization for an n-chip elastic job."""
    model = 1
    for m in range(min(max_model, n), 0, -1):
        if n % m == 0:
            model = m
            break
    return n // model, model


def make_job_mesh(devices: Sequence, *, max_model: int = 16) -> Mesh:
    """Mesh over an explicit device set (an elastic job's allocation)."""
    n = len(devices)
    data, model = factor_mesh(n, max_model)
    dev = np.asarray(devices, dtype=object).reshape(data, model)
    return Mesh(dev, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def host_devices(n: Optional[int] = None):
    devs = jax.devices()
    if n is not None:
        if len(devs) < n:
            raise RuntimeError(
                f"need {n} devices, have {len(devs)} — launch with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
        devs = devs[:n]
    return devs


def mesh_device_set(mesh: Mesh):
    return set(d.id for d in mesh.devices.flat)
