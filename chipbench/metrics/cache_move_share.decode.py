"""Share of the step program's device time in operations outside every
compute scope: those under ``kv_write``, those under ``layers`` with no
compute scope below, and those with no scope at all, such as the copies XLA
inserts. This is the time the layer scan and the KV write spend slicing,
restacking and copying the cache. Own device time of each operation, over
the device time of the step program (``mfu_roofline.decode``'s); the scopes
are the program's ``jax.named_scope`` names (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    if record.get("kind") != "decode":
        return None
    t = scopes.of(trace)
    if t is None or not t.scoped() or not t.step_device_s():
        return None
    moved = sum(s for scope, s in t.scope_table().items()
                if scope not in scopes.COMPUTE)
    return 100.0 * moved / t.step_device_s()
