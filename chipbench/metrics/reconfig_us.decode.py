"""Mean length in microseconds of the program's ``dmr.reconfig`` spans in
the traced window: the host's cost of each DMR_RECONFIG point that queries
the RMS, while the device waits for the next step."""
from chipbench import scopes


def read(record, trace):
    if record.get("kind") != "decode":
        return None
    t = scopes.of(trace)
    spans = t.spans_named("dmr.reconfig") if t is not None else []
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) * 1e-3 / len(spans)
