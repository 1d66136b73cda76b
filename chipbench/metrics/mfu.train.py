"""Model FLOPs of the window's training steps over what the chips of each
step's mesh could do in its wall time at peak: resize time left out."""


def read(record, trace):
    if record.get("kind") != "train":
        return None
    its = record["iterations"]
    peak = record["peaks"]["flops"]
    capacity = sum(peak * it["step_s"] * it["chips"] for it in its)
    if not capacity:
        return None
    return 100.0 * record["flops_per_step"] * len(its) / capacity
