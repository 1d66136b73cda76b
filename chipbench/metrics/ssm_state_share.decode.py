"""Share of the step program's device time in operations whose innermost
scope is the program's ``ssm_state``: the Mamba layers' state decay and
update, its in-place write and its read-out. Own device time of each
operation, over the device time of the step program
(``mfu_roofline.decode``'s; ``chipbench/scopes.py``).

The Mamba layers' scopes (``models/ssm.py``: ``ssm_in``, ``ssm_conv``,
``ssm_state``, ``ssm_out``) are not in ``scopes.SCOPES``, so the innermost
scope is found here with them added."""
from chipbench import scopes

SSM = ("ssm_in", "ssm_conv", "ssm_state", "ssm_out")


def innermost(path: str) -> str:
    """As ``scopes.innermost``, knowing the Mamba layers' scopes too."""
    for part in reversed(path.split("/")):
        if part in SSM:
            return part
        known = scopes.innermost(part)
        if known:
            return known
    return ""


def read(record, trace):
    if record.get("kind") != "decode":
        return None
    t = scopes.of(trace)
    if t is None or not t.scoped() or not t.step_device_s():
        return None
    own = [s for e, s in t.step_ops() if innermost(e.scope) == "ssm_state"]
    if not own:
        return None
    return 100.0 * sum(own) * 1e-9 / t.step_device_s()
