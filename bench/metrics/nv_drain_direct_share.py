"""NVCache engine, drain, in percent: share of the bytes of the window's
``drain.batch_us`` spans that the drain planned without page images (the
spans' ``direct_bytes`` over their ``bytes``; program_span).  A program
whose spans carry no ``direct_bytes`` reports nothing."""
from bench import timeline


def read(r: dict):
    d = (timeline.of_run(r) or {}).get("spans", {}).get("drain.batch_us")
    args = d["args"] if d else {}
    if "direct_bytes" not in args or not args.get("bytes"):
        return None
    return 100.0 * args["direct_bytes"] / args["bytes"]
