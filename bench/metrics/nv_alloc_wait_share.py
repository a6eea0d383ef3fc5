"""NVCache engine, in percent: share of the seconds inside ``CheckpointManager.save``
that writers waited for log space (the engine's ``log.alloc_wait_us``)."""


def read(r: dict):
    s = r["saves"]
    t = sum(x["t1"] - x["t0"] for x in s)
    return 100.0 * sum(x["alloc_wait_s"] for x in s) / t if s and t > 0 else None
