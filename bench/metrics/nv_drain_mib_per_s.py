"""NVCache engine, drain: bytes over the seconds of the window's
``drain.batch_us`` spans, one drain batch each (plan, apply, the modelled
fsync and consume), on the drain thread (program_span)."""
from bench import timeline


def read(r: dict):
    return timeline.mib_per_s(timeline.of_run(r), "drain.batch_us")
