"""Checkpoint layer, in percent: the seconds of the window's
``ckpt.encode_wait_us`` spans, the calling thread's waits for the next
record's encode, over the seconds of its ``ckpt.save_us`` spans: the share
of the save that the encode still holds up (program_span).  A program
without the wait span reports nothing."""
from bench import timeline


def read(r: dict):
    spans = (timeline.of_run(r) or {}).get("spans", {})
    wait, save = spans.get("ckpt.encode_wait_us"), spans.get("ckpt.save_us")
    if not wait or not save or save["s"] <= 0:
        return None
    return 100.0 * wait["s"] / save["s"]
