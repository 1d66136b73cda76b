"""Mean over the window's resizes of the first step on the new mesh over
the next step at that size: compile or placement that lands on the first
step after a resize shows here."""


def read(record, trace):
    if record.get("kind") != "train":
        return None
    its = record["iterations"]
    ratios = [a["step_s"] / b["step_s"] for a, b in zip(its, its[1:])
              if a["resized"] and not b["resized"] and a["chips"] == b["chips"]]
    return sum(ratios) / len(ratios) if ratios else None
