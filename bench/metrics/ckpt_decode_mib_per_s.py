"""Checkpoint layer, read path: raw bytes over the seconds of the window's
``ckpt.decode_us`` spans, one record's decompression each (program_span)."""
from bench import timeline


def read(r: dict):
    return timeline.mib_per_s(timeline.of_run(r), "ckpt.decode_us")
