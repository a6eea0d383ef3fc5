"""Checkpoint layer, write path: raw state bytes over the seconds spent
inside ``CheckpointManager.save``, over the window's saves."""


def read(r: dict):
    s = r["saves"]
    t = sum(x["t1"] - x["t0"] for x in s)
    return sum(x["bytes"] for x in s) / t / 2**20 if s and t > 0 else None
