"""Share of the step program's device time in operations under the
program's ``attend`` scope: the attention over the cache. Own device time
of each operation, over the device time of the step program
(``mfu_roofline.decode``'s; ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    if record.get("kind") != "decode":
        return None
    t = scopes.of(trace)
    if t is None or not t.scoped() or not t.step_device_s():
        return None
    return 100.0 * t.scope_table().get("attend", 0.0) / t.step_device_s()
