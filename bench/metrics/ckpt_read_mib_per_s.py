"""Checkpoint layer, read path: raw state bytes over the seconds spent
inside ``CheckpointManager.restore``, over the window's restores."""


def read(r: dict):
    s = r["restores"]
    t = sum(x["t1"] - x["t0"] for x in s)
    return sum(x["bytes"] for x in s) / t / 2**20 if s and t > 0 else None
