"""Tests of the NVCache drain's per-layer metrics.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/test_drain_metrics.py

``nv_drain_mib_per_s`` and ``nv_drain_direct_share`` read the window's
``drain.batch_us`` spans: on a small synthetic timeline, on one whose spans
carry no ``direct_bytes`` (a program that predates the direct plan), and in
a traced smoke run on the CPU.
"""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402
from bench import timeline  # noqa: E402
from bench.test_bench import _run, smoke, smoke_root  # noqa: E402,F401 (fixtures)

MS = 1e6
DRAIN = ("/host:CPU", 1)
METRICS = ("nv_drain_mib_per_s", "nv_drain_direct_share")


def _read(monkeypatch, spans):
    tl = timeline.reduce({"window": [(0.0, 100 * MS)], "spans": spans,
                          "ops": [], "modules": []})
    monkeypatch.setattr(timeline, "of_run", lambda readings: tl)
    return {n: R.load_file(ROOT / "bench/metrics" / f"{n}.py").read({})
            for n in METRICS}


def test_drain_metrics_read_the_window_batches(monkeypatch):
    read = _read(monkeypatch, [
        ("drain.batch_us", 10 * MS, 30 * MS, DRAIN,
         {"bytes": 3 << 20, "direct_bytes": 3 << 20, "entries": 5}),
        ("drain.batch_us", 40 * MS, 60 * MS, DRAIN,
         {"bytes": 1 << 20, "direct_bytes": 0, "entries": 1}),
        ("drain.batch_us", -30 * MS, -10 * MS, DRAIN,   # before the window
         {"bytes": 9 << 20, "direct_bytes": 0, "entries": 1})])
    assert read["nv_drain_mib_per_s"] == pytest.approx(4 / 0.040)
    assert read["nv_drain_direct_share"] == pytest.approx(75.0)


def test_drain_metrics_of_an_older_program(monkeypatch):
    """Spans without ``direct_bytes``: the rate reads, the share does not;
    no span: neither reads."""
    read = _read(monkeypatch, [("drain.batch_us", 10 * MS, 30 * MS, DRAIN,
                                {"bytes": 1 << 20, "entries": 5})])
    assert read["nv_drain_mib_per_s"] == pytest.approx(1 / 0.020)
    assert read["nv_drain_direct_share"] is None
    assert _read(monkeypatch, []) == {n: None for n in METRICS}


def test_traced_smoke_run_reports_the_drain_metrics(smoke):
    root, bench = smoke
    bench = copy.deepcopy(bench)
    for m in bench["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"] = ["iv"]
    res = _run((root, bench), "iv", trace=1)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["nv_drain_mib_per_s"]["value"] > 0
    assert 99.0 < m["nv_drain_direct_share"]["value"] <= 100.0
