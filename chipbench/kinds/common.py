"""What the kinds share: the reference and the program's configuration of a
benchmark configuration, and the device's peaks."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict

from chipbench import arith


def reference(c: Dict):
    """The plain reference of a configuration: ``refs/<arch>.py``."""
    return importlib.import_module(f"chipbench.refs.{c['arch']}")


def program_config(c: Dict, ref):
    """The program's ``ArchConfig`` for ``c``: its registry entry, with the
    ``program.overrides`` a configuration may state, checked field by field
    against the sizes the file gives (the configuration as it is run)."""
    from repro.configs import get_config

    prog = c["program"]
    cfg = get_config(prog["registry"])
    over = dict(prog.get("overrides", {}))
    ssm_over = {k[4:]: over.pop(k) for k in list(over) if k.startswith("ssm.")}
    if ssm_over:
        over["ssm"] = dataclasses.replace(cfg.ssm, **ssm_over)
    cfg = dataclasses.replace(cfg, **over)
    wrong = []
    for field, want in ref.program_fields(c).items():
        obj, name = cfg, field
        if field.startswith("ssm."):
            obj, name = cfg.ssm, field[4:]
        got = getattr(obj, name)
        if got != want:
            wrong.append(f"{field}: program {got!r}, configuration {want!r}")
    if wrong:
        raise ValueError(f"{c['name']} is not run as its file states: "
                         + "; ".join(wrong))
    return cfg


def decode_least_s(c: Dict, batch: int, pos: int, peaks: Dict) -> float:
    step = arith.decode_step(c, reference(c).layout(c), batch, pos)
    return arith.least_s(step["flops"], step["bytes"], peaks["flops"],
                         peaks["hbm_bytes_per_s"])
