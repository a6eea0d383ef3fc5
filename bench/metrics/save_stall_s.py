"""Train loop: mean of the loop's own ``save_s`` (device-to-host copy,
encode, durable write) over the saves in the window."""


def read(r: dict):
    s = r["save_s"]
    return sum(s) / len(s) if s else None
