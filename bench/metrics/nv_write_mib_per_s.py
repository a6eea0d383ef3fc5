"""NVCache engine, write path: bytes over the seconds of the window's
``ckpt.write_us`` spans, one checkpoint record's ``pwrite`` through NVCache
each, log-full waits inside (program_span)."""
from bench import timeline


def read(r: dict):
    return timeline.mib_per_s(timeline.of_run(r), "ckpt.write_us")
