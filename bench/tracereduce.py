"""From a profiler trace to the numbers the benchmark reports.

``load`` reads the newest ``.xplane.pb`` under a directory with JAX's own
``ProfileData`` and keeps three lists: the device's operations (the "XLA
Ops" line of the first TPU plane), the device's program executions (its
"XLA Modules" line) and the host spans the harness wrote (``bench.*``
annotations).  ``reduce`` is plain arithmetic on those lists, checked by the
benchmark's tests on a small recorded trace.  All times are nanoseconds on
the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):0$")


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    ops, modules, host = [], [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    dst.append((ev.name, float(ev.start_ns), float(ev.end_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, float(ev.start_ns), float(ev.end_ns)))
    return {"ops": ops, "modules": modules, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(tr: dict, *, step_module: str = "jit_step", top: int = 10) -> dict:
    """Busy time, idle share, step device time, top operations and the
    longest idle gaps, inside the ``bench.window`` span."""
    win = [(s, e) for n, s, e in tr["host"] if n == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = win[-1]
    clip = [(max(s, w0), min(e, w1)) for _n, s, e in tr["ops"] if e > w0 and s < w1]
    busy = _union(clip)
    busy_ns = sum(e - s for s, e in busy)
    per_op: dict = {}
    for n, s, e in tr["ops"]:
        if e > w0 and s < w1:
            n = n.split(" = ")[0].lstrip("%")     # the HLO instruction's name
            per_op[n] = per_op.get(n, 0.0) + (min(e, w1) - max(s, w0))
    steps = [(s, e) for n, s, e in tr["modules"]
             if n.startswith(step_module) and s >= w0 and e <= w1]
    gaps, t = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = [(n, s, e) for n, s, e in tr["host"] if n != WINDOW_SPAN]

    def label(g0, g1):
        """The innermost host span around the gap's middle."""
        mid = (g0 + g1) / 2
        best, length = "other", float("inf")
        for n, s, e in spans:
            if s <= mid <= e and e - s < length:
                best, length = n[len(HOST_PREFIX):], e - s
        return best

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "op_count": len(clip),
        "idle_share": 1.0 - busy_ns / (w1 - w0),
        "step_count": len(steps),
        "step_device_s": sum(e - s for s, e in steps) * 1e-9,
        "device_ops": [[n, v * 1e-9] for n, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(g0, g1), (g1 - g0) * 1e-9] for g0, g1 in gaps[:top]],
    }
