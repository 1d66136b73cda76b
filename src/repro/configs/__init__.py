"""Config registry: the assigned architectures + reduced smoke variants."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (
    ArchConfig,
    FrontendConfig,
    MoEConfig,
    SHAPES,
    SHAPES_BY_NAME,
    SMOKE_DECODE,
    SMOKE_PREFILL,
    SMOKE_SHAPE,
    SSMConfig,
    ShapeConfig,
    reduced,
    shape_applicable,
)

_ARCH_MODULES = {
    "zamba2-2.7b": "zamba2_2_7b",
    "internlm2-20b": "internlm2_20b",
    "granite-3-2b": "granite_3_2b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "qwen2.5-32b": "qwen2_5_32b",
    "pixtral-12b": "pixtral_12b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "mixtral-8x7b": "mixtral_8x7b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "mamba2-370m": "mamba2_370m",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}


def list_archs() -> List[str]:
    return list(_ARCH_MODULES)


def get_config(name: str) -> ArchConfig:
    """Look up an architecture config by id; ``<id>-smoke`` gives the reduced one."""
    if name.endswith("-smoke"):
        return reduced(get_config(name[: -len("-smoke")]))
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def all_configs() -> Dict[str, ArchConfig]:
    return {n: get_config(n) for n in _ARCH_MODULES}


def get_shape(name: str) -> ShapeConfig:
    if name in SHAPES_BY_NAME:
        return SHAPES_BY_NAME[name]
    for s in (SMOKE_SHAPE, SMOKE_PREFILL, SMOKE_DECODE):
        if s.name == name:
            return s
    raise KeyError(f"unknown shape {name!r}")


def live_cells():
    """All (arch, shape) dry-run cells with applicability verdicts."""
    out = []
    for an, cfg in all_configs().items():
        for shp in SHAPES:
            ok, why = shape_applicable(cfg, shp)
            out.append((an, shp.name, ok, why))
    return out


__all__ = [
    "ArchConfig", "MoEConfig", "SSMConfig", "FrontendConfig", "ShapeConfig",
    "SHAPES", "SHAPES_BY_NAME", "SMOKE_SHAPE", "SMOKE_PREFILL", "SMOKE_DECODE",
    "reduced", "shape_applicable", "list_archs", "get_config", "all_configs",
    "get_shape", "live_cells",
]
