"""Share of the window spent in prompt steps (each fed one prompt token),
by the host's clock."""


def read(record, trace):
    if record.get("kind") != "decode" or not record["window_s"]:
        return None
    return 100.0 * record["prompt_s"] / record["window_s"]
