"""Train loop: mean seconds of the window's ``train.d2h_us`` spans, the
device-to-host copy of the state a save holds (program_span)."""
from bench import timeline


def read(r: dict):
    d = (timeline.of_run(r) or {}).get("spans", {}).get("train.d2h_us")
    return d["s"] / d["count"] if d and d["count"] else None
