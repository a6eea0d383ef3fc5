"""What the expert layer's metrics read beyond ``bench/timeline.py``: the
traced run they are read for, and the device time of the operations under
one of the expert layer's own scopes (``router``, ``dispatch``,
``experts``, ``combine``, ``shared``, all inside ``mlp``).

``timeline`` attributes each operation to the innermost of its fixed
``SCOPES``, so an expert layer's operations count under ``mlp`` there; this
module reads the same trace and the same HLO op paths for a finer scope.
A program or trace without that scope reads as zero seconds.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from pathlib import Path

from bench import timeline, tracereduce

HERE = Path(__file__).resolve().parent


def run_of(readings: dict):
    """The traced run whose per-layer metrics are being read (found among
    the callers' locals, as ``timeline.of_run`` finds it), or None."""
    tr = readings.get("trace")
    f = sys._getframe(1)
    while tr is not None and f is not None:
        for run in f.f_locals.values():
            if getattr(run, "trace_red", None) is tr and getattr(run, "trace_dir", None):
                return run
        f = f.f_back
    return None


def peaks(kind: str) -> dict:
    return json.loads((HERE / "peaks.json").read_text())["devices"][kind]


def _in_scope(op_name: str | None, scope: str) -> bool:
    for comp in (op_name or "").split("/"):
        ids = timeline._IDENT.findall(comp)
        if ids and ids[-1] == scope:
            return True
    return False


def scope_seconds(trace_dir, scope: str) -> tuple[float, int]:
    """(device seconds under ``scope``, step executions) over the whole
    step executions inside the window: each instant goes to the innermost
    operation running, as ``timeline.reduce`` counts its scopes."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return 0.0, 0
    data = ProfileData.from_file(files[-1])
    window, ops, modules, marks = [], [], [], {}
    op_names = None
    for plane in data.planes:
        if tracereduce._DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(ev.name, float(ev.start_ns), float(ev.end_ns))
                                for ev in line.events]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        if ev.name not in marks:
                            if op_names is None:
                                op_names = timeline.hlo_op_names(files[-1],
                                                                 timeline.STEP_MODULE)
                            hlo = ev.name.split(" = ")[0].lstrip("%")
                            marks[ev.name] = _in_scope(op_names.get(hlo), scope)
                        ops.append((float(ev.start_ns), float(ev.end_ns), marks[ev.name]))
        elif plane.name.startswith("/host:"):
            window += [(float(ev.start_ns), float(ev.end_ns)) for line in plane.lines
                       for ev in line.events if ev.name == tracereduce.WINDOW_SPAN]
    if not window:
        return 0.0, 0
    w0, w1 = window[-1]
    steps = sorted((s, e) for n, s, e in modules
                   if n.startswith(timeline.STEP_MODULE) and s >= w0 and e <= w1)
    pieces = timeline.innermost(timeline._in_steps(ops, steps))
    return sum(e - s for s, e, k in pieces if k) * 1e-9, len(steps)
