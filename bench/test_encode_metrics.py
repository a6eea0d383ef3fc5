"""Tests of the checkpoint encode's per-layer metric.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/test_encode_metrics.py

``ckpt_encode_wait_share`` reads the window's ``ckpt.encode_wait_us`` over
its ``ckpt.save_us`` spans: on a small synthetic timeline, on one without
the wait span (a program that encodes on the calling thread), and in a
traced smoke run on the CPU.
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
MAIN, POOL = ("/host:CPU", 0), ("/host:CPU", 2)
METRIC = "ckpt_encode_wait_share"


def _read(monkeypatch, spans):
    tl = timeline.reduce({"window": [(0.0, 100 * MS)], "spans": spans,
                          "ops": [], "modules": []})
    monkeypatch.setattr(timeline, "of_run", lambda readings: tl)
    return R.load_file(ROOT / "bench/metrics" / f"{METRIC}.py").read({})


def test_encode_wait_share_reads_the_window_saves(monkeypatch):
    share = _read(monkeypatch, [
        ("ckpt.save_us", 10 * MS, 50 * MS, MAIN, {"bytes": 8 << 20}),
        ("ckpt.encode_wait_us", 11 * MS, 13 * MS, MAIN, {}),
        ("ckpt.encode_wait_us", 20 * MS, 21 * MS, MAIN, {}),
        ("ckpt.encode_us", 11 * MS, 30 * MS, POOL, {"bytes": 4 << 20}),
        ("ckpt.save_us", 60 * MS, 100 * MS, MAIN, {"bytes": 8 << 20}),
        ("ckpt.encode_wait_us", 61 * MS, 66 * MS, MAIN, {}),
        ("ckpt.save_us", -50 * MS, -10 * MS, MAIN, {"bytes": 8 << 20}),  # before
        ("ckpt.encode_wait_us", -49 * MS, -20 * MS, MAIN, {})])           # the window
    assert share == pytest.approx(100.0 * 8 / 80)


def test_encode_wait_share_of_an_older_program(monkeypatch):
    """Saves whose encode runs on the calling thread emit no wait span: the
    share reads nothing, as it does with no save at all."""
    assert _read(monkeypatch, [
        ("ckpt.save_us", 10 * MS, 50 * MS, MAIN, {"bytes": 8 << 20}),
        ("ckpt.encode_us", 11 * MS, 30 * MS, MAIN, {"bytes": 8 << 20})]) is None
    assert _read(monkeypatch, []) is None


def test_traced_smoke_run_reports_the_encode_wait_share(smoke):
    root, bench = smoke
    bench = copy.deepcopy(bench)
    for m in bench["per_layer"]:
        if m["name"] == METRIC:
            m["workloads"] = ["iv"]
    res = _run((root, bench), "iv", trace=1)
    assert res["correct"], res["checks"]
    assert 0.0 <= res["metrics"][METRIC]["value"] < 100.0
