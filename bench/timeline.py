"""The program's own spans and scopes, from the same profiler trace.

``tracereduce`` keeps the harness's ``bench.*`` spans and the device's
operations by their HLO names.  This module reads the same ``.xplane.pb``
for what the program itself writes there:

* host spans whose names start with one of ``PROGRAM_PREFIXES``
  (``repro.obs.span``: train loop, checkpoint codec, NVCache engine), with
  their trace stats (``bytes``, ``step``, ...) and the host thread (trace
  line) each ran on;
* each device operation's ``op_name`` path, and from it the innermost
  ``jax.named_scope`` of ``SCOPES``.  A TPU's operation events carry no
  path (their names are HLO text without metadata, their stats times), so
  the path comes from the HLO proto of the step's module, which the trace
  keeps in its ``/host:metadata`` plane, read with a minimal protobuf wire
  decoder.

``reduce`` is plain arithmetic on those lists inside the ``bench.window``
span, checked by the benchmark's tests on small synthetic traces.  Times
are nanoseconds on the trace's clock; results are seconds.  A program
without spans or scopes (an older checkout) reduces to empty tables, and
the metrics that read them report nothing.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
import sys
from pathlib import Path

from bench import tracereduce

PROGRAM_PREFIXES = ("train.", "ckpt.", "nv.", "log.", "drain.")
# spans of threads that run beside the job (the NVCache drain): they never
# stand for what the job's own thread was doing during a device-idle gap
BACKGROUND_PREFIXES = ("drain.",)
SCOPES = ("embed", "attention", "mlp", "loss", "optimizer")
# the program modules that open the SCOPES, and the step's jitted module
SCOPE_MODULES = ("repro.models.lm", "repro.train.steps")
STEP_MODULE = "jit_step"
NONE = "none"
WINDOW_SPAN = tracereduce.WINDOW_SPAN
_IDENT = re.compile(r"[A-Za-z_]\w*")
_cache: dict = {}


# ------------------------------------------------------------------- load

def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, span=None):
    """(field number, value) of the protobuf message in ``b[span]``: an int
    for a varint, a ``(start, end)`` span for a length-delimited field, None
    for a fixed-width one."""
    i, end = span or (0, len(b))
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(b, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def hlo_op_names(path: str, module_prefix: str) -> dict:
    """HLO instruction name -> op_name, over the modules whose names start
    with ``module_prefix``, from the ``Hlo Proto`` stats of the trace's
    ``/host:metadata`` plane.  Field numbers: XSpace.planes 1; XPlane.name
    2, .event_metadata 4, .stat_metadata 5 (map entries: key 1, value 2);
    XEventMetadata.name 2, .stats 5; XStatMetadata.name 2; XStat.metadata_id
    1, .bytes_value 6; HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    with open(path, "rb") as f:
        b = f.read()
    out: dict = {}
    for field, plane in _fields(b):
        if field != 1:
            continue
        name, events, stat_ids = None, [], {}
        for pf, pv in _fields(b, plane):
            if pf == 2:
                name = _text(b, pv)
                if name != "/host:metadata":
                    break
            elif pf == 4:
                events += [v for f, v in _fields(b, pv) if f == 2]
            elif pf == 5:
                for f, v in _fields(b, pv):
                    meta = dict(_fields(b, v)) if f == 2 else {}
                    if 2 in meta:
                        stat_ids[_text(b, meta[2])] = meta.get(1)
        hlo_stat = stat_ids.get("Hlo Proto")
        for ev in events if name == "/host:metadata" else ():
            meta = list(_fields(b, ev))
            ev_name = next((_text(b, v) for f, v in meta if f == 2), "")
            if not ev_name.startswith(module_prefix):
                continue
            for f, stat in meta:
                st = dict(_fields(b, stat)) if f == 5 else {}
                if hlo_stat is not None and st.get(1) == hlo_stat and 6 in st:
                    _module_op_names(b, st[6], out)
    return out


def _module_op_names(b: bytes, proto, out: dict) -> None:
    for f, module in _fields(b, proto):
        if f != 1:
            continue
        for f2, comp in _fields(b, module):
            if f2 != 3:
                continue
            for f3, inst in _fields(b, comp):
                if f3 != 2:
                    continue
                name = op_name = None
                for f4, v in _fields(b, inst):
                    if f4 == 1:
                        name = _text(b, v)
                    elif f4 == 7:
                        op_name = next((_text(b, x) for g, x in _fields(b, v) if g == 2),
                                       None)
                if name and op_name:
                    out[name] = op_name


def scope_of(op_name: str | None) -> str | None:
    """The innermost component of an op_name path that names one of
    ``SCOPES``; a component may be wrapped by transformations, as in
    ``transpose(jvp(attention))``."""
    best = None
    for comp in (op_name or "").split("/"):
        ids = _IDENT.findall(comp)
        if ids and ids[-1] in SCOPES:
            best = ids[-1]
    return best


def load(logdir: str) -> dict:
    """The newest trace under ``logdir``: the window, the program's host
    spans ``(name, start, end, thread, args)``, the device's operations
    ``(start, end, scope)`` (scopes from the step module's HLO) and its
    program executions ``(name, start, end)``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    window, spans, ops, modules = [], [], [], []
    scopes: dict = {}                    # event name -> scope
    op_names = None                      # read from the HLO once, if needed
    for plane in data.planes:
        if tracereduce._DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(ev.name, float(ev.start_ns), float(ev.end_ns))
                                for ev in line.events]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        if ev.name not in scopes:
                            if op_names is None:
                                op_names = hlo_op_names(files[-1], STEP_MODULE)
                            hlo = ev.name.split(" = ")[0].lstrip("%")
                            scopes[ev.name] = scope_of(op_names.get(hlo))
                        ops.append((float(ev.start_ns), float(ev.end_ns),
                                    scopes[ev.name]))
        elif plane.name.startswith("/host:"):
            # a line is a thread; lines carry the process's name, not an id
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window.append((float(ev.start_ns), float(ev.end_ns)))
                    elif ev.name.startswith(PROGRAM_PREFIXES):
                        args = {k: v for k, v in ev.stats
                                if isinstance(v, (int, float))}
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.end_ns), (plane.name, i), args))
    return {"window": window, "spans": spans, "ops": ops, "modules": modules}


# ----------------------------------------------------------------- reduce

def innermost(intervals):
    """Disjoint pieces ``[start, end, key]`` of the union of ``(start, end,
    key)`` intervals, each labelled with the key of the covering interval
    that started last (of those starting together, the one that ends
    first): the innermost, where intervals nest."""
    events = []
    for i, (s, e, _k) in enumerate(intervals):
        if e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))
    events.sort()                        # at one instant, ends before starts
    heap, ended, out, t = [], set(), [], None
    for now, starts, i in events:
        while heap and heap[0][2] in ended:
            heapq.heappop(heap)
        if heap and now > t:
            key = intervals[heap[0][2]][2]
            if out and out[-1][1] == t and out[-1][2] == key:
                out[-1][1] = now
            else:
                out.append([t, now, key])
        t = now
        if starts:
            s, e, _k = intervals[i]
            heapq.heappush(heap, (-s, e, i))
        else:
            ended.add(i)
    return out


def _overlap_by_key(pieces, intervals) -> dict:
    """Seconds of each key's pieces inside the disjoint sorted
    ``intervals``; time of the intervals that no piece covers goes to
    ``NONE``."""
    out: dict = {}
    covered = 0.0
    j = 0
    for s, e in intervals:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
            if ov > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + ov * 1e-9
                covered += ov
            k += 1
    total = sum(e - s for s, e in intervals)
    if total - covered > 0:
        out[NONE] = out.get(NONE, 0.0) + (total - covered) * 1e-9
    return out


def _nesting(spans, w0, w1) -> dict:
    """Seconds of each span's direct children, by parent and child name,
    for parents that start in the window (spans of one thread nest)."""
    out: dict = {}
    by_line: dict = {}
    for sp in spans:
        by_line.setdefault(sp[3], []).append(sp)
    for line in by_line.values():
        stack = []
        for name, s, e, _l, _a in sorted(line, key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][2] <= s:
                stack.pop()
            if stack and w0 <= stack[-1][1] <= w1:
                kids = out.setdefault(stack[-1][0], {})
                kids[name] = kids.get(name, 0.0) + (e - s) * 1e-9
            stack.append((name, s, e))
    return out


def reduce(tl: dict) -> dict:
    """Inside the last ``bench.window`` span:

    * ``spans``: per span name, the spans that start in the window:
      ``count``, their whole seconds ``s`` (a save that closes the window
      counts whole) and the sums of their numeric ``args``;
    * ``children``: per span name, the seconds of its direct children;
    * ``idle``: device-idle seconds put down to the innermost foreground
      program span (drain spans never label a gap), ``none`` where no
      program span covers the gap; ``idle_s`` their sum;
    * ``scopes``: device seconds of the operations of the window's whole
      step executions by innermost scope, each instant to the innermost
      operation running (a ``while`` and its body are not counted twice),
      ``none`` for operations under no scope; and ``step_count``."""
    if not tl["window"]:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = tl["window"][-1]
    spans: dict = {}
    for name, s, e, _line, args in tl["spans"]:
        if w0 <= s < w1:
            d = spans.setdefault(name, {"count": 0, "s": 0.0, "args": {}})
            d["count"] += 1
            d["s"] += (e - s) * 1e-9
            for k, v in args.items():
                d["args"][k] = d["args"].get(k, 0) + v
    busy = tracereduce._union([(max(s, w0), min(e, w1)) for s, e, _k in tl["ops"]
                   if e > w0 and s < w1])
    idle, t = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    fg = [(s, e, n) for n, s, e, _l, _a in tl["spans"]
          if not n.startswith(BACKGROUND_PREFIXES) and e > w0 and s < w1]
    steps = sorted((s, e) for n, s, e in tl["modules"]
                   if n.startswith(STEP_MODULE) and s >= w0 and e <= w1)
    step_ops = _in_steps(tl["ops"], steps)
    scoped = innermost([(s, e, k or NONE) for s, e, k in step_ops])
    scopes: dict = {}
    for s, e, k in scoped:
        scopes[k] = scopes.get(k, 0.0) + (e - s) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "spans": spans,
        "children": _nesting(tl["spans"], w0, w1),
        "idle": _overlap_by_key(innermost(fg), idle) if busy else {},
        "idle_s": sum(e - s for s, e in idle) * 1e-9 if busy else 0.0,
        "scopes": scopes,
        "step_count": len(steps),
    }


def _in_steps(ops, steps):
    """The operations that lie inside one of the sorted step intervals."""
    out = []
    starts = [s for s, _e in steps]
    for s, e, k in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= steps[i][1]:
            out.append((s, e, k))
    return out


# ----------------------------------------------------------- for metrics

def of_run(readings: dict) -> dict | None:
    """The reduced timeline of the traced run whose per-layer metrics are
    being read, loaded once per run; None for an untraced run.
    ``bench/run.py`` hands a metric only its readings, so the run is found
    among the callers' locals as the object whose reduced trace is the one
    in ``readings`` and which knows its ``trace_dir``."""
    tr = readings.get("trace")
    if tr is None:
        return None
    f = sys._getframe(1)
    while f is not None:
        for run in f.f_locals.values():
            if getattr(run, "trace_red", None) is tr and getattr(run, "trace_dir", None):
                key = str(run.trace_dir)
                if key not in _cache:
                    _cache.clear()
                    _cache[key] = reduce(load(key))
                return _cache[key]
        f = f.f_back
    raise LookupError("no caller holds the traced run (trace_red, trace_dir) "
                      "of these readings")


def mib_per_s(tl: dict | None, name: str):
    """``bytes`` summed over the window's ``name`` spans, over their
    seconds, in MiB/s; None where the window holds no such span."""
    d = (tl or {}).get("spans", {}).get(name)
    if not d or not d["count"] or d["s"] <= 0 or "bytes" not in d["args"]:
        return None
    return d["args"]["bytes"] / d["s"] / 2**20


def code_scopes() -> set:
    """The ``SCOPES`` that the loaded program's model and step code open
    with ``jax.named_scope``; empty where that code is not loaded."""
    text = ""
    for name in SCOPE_MODULES:
        path = getattr(sys.modules.get(name), "__file__", None)
        if path:
            text += Path(path).read_text()
    return {s for s in SCOPES if f'named_scope("{s}")' in text}


def step_scopes(tl: dict | None) -> dict | None:
    """The window's step device seconds by scope (``tl["scopes"]``); None
    without traced steps or scoped operations.  JAX keys its compilation
    cache without op metadata, so a step compiled by a tree with other
    scopes can be loaded from a shared cache: where the step's HLO and the
    loaded code disagree on having scopes at all, this says so on stderr
    and gives None."""
    if not tl or not tl["step_count"]:
        return None
    found, code = set(tl["scopes"]) - {NONE}, code_scopes()
    if bool(found) != bool(code):
        print(f"timeline: the traced step's HLO has scopes {sorted(found)} but the "
              f"loaded code opens {sorted(code)}: the step came from a compilation "
              "cache shared with another tree; scope metrics report nothing",
              file=sys.stderr)
        return None
    return tl["scopes"] if found else None
