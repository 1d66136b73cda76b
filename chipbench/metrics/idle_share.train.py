"""Share of the traced window in which no operation ran on a chip,
averaged over the chips: 1 - busy / window."""


def read(record, trace):
    if record.get("kind") != "train" or trace is None or not trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
