"""Mean seconds of the state transfer of the window's resizes, as the
program's ``ResizeEvent.transfer.seconds`` reports them."""


def read(record, trace):
    if record.get("kind") != "train" or not record["transfer_s"]:
        return None
    return sum(record["transfer_s"]) / len(record["transfer_s"])
