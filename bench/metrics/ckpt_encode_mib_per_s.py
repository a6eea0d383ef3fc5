"""Checkpoint layer: raw bytes over the seconds of the window's
``ckpt.encode_us`` spans, one record's contiguous copy, compression and
framing each (program_span)."""
from bench import timeline


def read(r: dict):
    return timeline.mib_per_s(timeline.of_run(r), "ckpt.encode_us")
