"""NVCache engine, read path: bytes over the seconds of the window's
``ckpt.read_us`` spans, one checkpoint record's ``pread`` through NVCache
each (program_span)."""
from bench import timeline


def read(r: dict):
    return timeline.mib_per_s(timeline.of_run(r), "ckpt.read_us")
