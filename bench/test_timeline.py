"""Tests of the reading of the program's own spans and scopes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/test_timeline.py

``timeline.reduce`` on small synthetic traces (the window's spans, idle time
put down to the innermost foreground span, drain spans that never label a
gap, the ``none`` label, scopes of a ``while`` and its body counted once),
the metrics that read it, and traced smoke runs on the CPU that report
every program-span metric and no device metric.
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
MAIN, DRAIN = ("/host:CPU", 0), ("/host:CPU", 1)
PROGRAM_SPAN_METRICS = {"iv": {"save_d2h_s", "ckpt_encode_mib_per_s", "nv_write_mib_per_s"},
                        "rs": {"ckpt_decode_mib_per_s", "nv_read_mib_per_s"}}
DEVICE_METRICS = {"step_attn_ms", "idle_unattributed_share"}


def _span(name, s, e, line=MAIN, **args):
    return (name, s * MS, e * MS, line, args)


SMALL = {
    "window": [(0.0, 100 * MS)],
    "spans": [_span("train.step_us", -10, 5, step=2),          # starts before
              _span("train.save_us", 40, 110, step=100),       # ends after
              _span("train.d2h_us", 40, 50, bytes=4 << 20),
              _span("ckpt.save_us", 50, 105, bytes=4 << 20),
              _span("ckpt.encode_us", 55, 60, bytes=2 << 20, out_bytes=1 << 20),
              _span("ckpt.write_us", 60, 70, bytes=1 << 20),
              _span("drain.batch_us", 20, 35, DRAIN, bytes=1, entries=1)],
    # a while (no scope) around two body ops, then one op of the loss
    "ops": [(5 * MS, 15 * MS, None), (6 * MS, 9 * MS, "attention"),
            (10 * MS, 14 * MS, "mlp"), (15 * MS, 20 * MS, "loss")],
    "modules": [("jit_step(3)", 5 * MS, 20 * MS), ("jit_step(3)", 95 * MS, 120 * MS)],
}


def test_reduce_keeps_the_spans_that_start_in_the_window():
    r = timeline.reduce(SMALL)
    sp = r["spans"]
    assert r["window_s"] == pytest.approx(0.1)
    assert "train.step_us" not in sp                          # started before it
    assert sp["train.save_us"]["count"] == 1
    assert sp["train.save_us"]["s"] == pytest.approx(0.070)  # whole span
    assert sp["ckpt.encode_us"]["args"] == {"bytes": 2 << 20, "out_bytes": 1 << 20}
    assert r["children"]["train.save_us"] == {"train.d2h_us": pytest.approx(0.010),
                                              "ckpt.save_us": pytest.approx(0.055)}
    assert r["children"]["ckpt.save_us"] == {"ckpt.encode_us": pytest.approx(0.005),
                                             "ckpt.write_us": pytest.approx(0.010)}


def test_idle_goes_to_the_innermost_foreground_span():
    r = timeline.reduce(SMALL)
    idle = r["idle"]
    assert r["idle_s"] == pytest.approx(0.085)
    assert idle["train.step_us"] == pytest.approx(0.005)
    assert idle["train.d2h_us"] == pytest.approx(0.010)
    assert idle["ckpt.encode_us"] == pytest.approx(0.005)
    assert idle["ckpt.write_us"] == pytest.approx(0.010)
    assert idle["ckpt.save_us"] == pytest.approx(0.035)      # around its children
    # the drain's span covers 20-35 ms of the gap but labels none of it
    assert "drain.batch_us" not in idle
    assert idle[timeline.NONE] == pytest.approx(0.020)
    assert sum(idle.values()) == pytest.approx(r["idle_s"])


def test_scopes_count_a_while_and_its_body_once():
    r = timeline.reduce(SMALL)
    assert r["step_count"] == 1                               # the whole step only
    assert r["scopes"] == {"attention": pytest.approx(0.003), "mlp": pytest.approx(0.004),
                           "loss": pytest.approx(0.005), timeline.NONE: pytest.approx(0.003)}
    assert sum(r["scopes"].values()) == pytest.approx(0.015)   # the step's busy time


def test_reduce_of_a_program_without_spans_or_scopes():
    bare = dict(SMALL, spans=[], ops=[(s, e, None) for s, e, _k in SMALL["ops"]])
    r = timeline.reduce(bare)
    assert r["spans"] == {} and r["idle"] == {timeline.NONE: pytest.approx(0.085)}
    assert set(r["scopes"]) == {timeline.NONE}
    with pytest.raises(ValueError):
        timeline.reduce(dict(SMALL, window=[]))


def test_innermost_labels_nested_and_overlapping_intervals():
    got = timeline.innermost([(0, 10, "a"), (2, 4, "b"), (3, 6, "c"), (12, 13, "d")])
    assert got == [[0, 2, "a"], [2, 3, "b"], [3, 6, "c"], [6, 10, "a"], [12, 13, "d"]]


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/jvp()/while/body/closed_call/attention/dot_general", "attention"),
    ("jit(step)/transpose(jvp())/while/body/checkpoint/mlp/mul", "mlp"),
    ("jit(step)/transpose(jvp(loss))/dot_general", "loss"),
    ("jit(step)/optimizer/add", "optimizer"),
    ("jit(step)/jvp()/while/body/dynamic_slice", None), (None, None)])
def test_scope_of_op_name(path, scope):
    assert timeline.scope_of(path) == scope


def test_metrics_read_the_reduced_timeline(monkeypatch):
    import repro.models.lm  # noqa: F401 - the code that opens the scopes
    import repro.train.steps  # noqa: F401
    r = timeline.reduce(SMALL)
    monkeypatch.setattr(timeline, "of_run", lambda readings: r)
    read = {n: R.load_file(ROOT / "bench/metrics" / f"{n}.py").read({})
            for n in set().union(*PROGRAM_SPAN_METRICS.values()) | DEVICE_METRICS}
    assert read["save_d2h_s"] == pytest.approx(0.010)
    assert read["ckpt_encode_mib_per_s"] == pytest.approx(2 / 0.005)
    assert read["nv_write_mib_per_s"] == pytest.approx(1 / 0.010)
    assert read["ckpt_decode_mib_per_s"] is None and read["nv_read_mib_per_s"] is None
    assert read["step_attn_ms"] == pytest.approx(3.0)
    assert read["idle_unattributed_share"] == pytest.approx(100 * 20 / 85)


@pytest.mark.parametrize("code,step_ops", [
    (set(), SMALL["ops"]),                                   # scoped step, bare code
    (set(timeline.SCOPES), [(s, e, None) for s, e, _k in SMALL["ops"]])])
def test_scope_metrics_report_nothing_for_a_step_of_another_tree(
        monkeypatch, capsys, code, step_ops):
    """A compilation cache keyed without op metadata can hand the run a step
    compiled by a tree with other scopes: the metric says so and reads
    nothing."""
    r = timeline.reduce(dict(SMALL, ops=step_ops))
    monkeypatch.setattr(timeline, "of_run", lambda readings: r)
    monkeypatch.setattr(timeline, "code_scopes", lambda: code)
    assert R.load_file(ROOT / "bench/metrics/step_attn_ms.py").read({}) is None
    assert "compilation cache shared with another tree" in capsys.readouterr().err


def test_of_run_finds_the_traced_run_among_its_callers(monkeypatch):
    class Traced:
        trace_red = {"window_s": 1.0}
        trace_dir = "/nonexistent/trace"

    monkeypatch.setattr(timeline, "load", lambda logdir: SMALL)
    any_name = Traced()
    readings = {"trace": any_name.trace_red}
    assert timeline.of_run(readings) == timeline.reduce(SMALL)
    assert timeline.of_run({"trace": None}) is None
    with pytest.raises(LookupError):
        timeline.of_run({"trace": {"window_s": 1.0}})    # no caller holds it


@pytest.fixture
def program_span_cells(smoke):
    """The smoke checkout, with each program-span metric in the cell kind
    that reads it."""
    root, bench = smoke
    bench = copy.deepcopy(bench)
    for m in bench["per_layer"]:
        for kind, names in PROGRAM_SPAN_METRICS.items():
            if m["name"] in names:
                m["workloads"] = [kind]
    return root, bench


@pytest.mark.parametrize("kind", sorted(PROGRAM_SPAN_METRICS))
def test_traced_smoke_run_reports_program_span_metrics(program_span_cells, kind):
    res = _run(program_span_cells, kind, trace=1)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert PROGRAM_SPAN_METRICS[kind] <= set(m)
    assert all(m[n]["value"] > 0 for n in PROGRAM_SPAN_METRICS[kind])
    # the CPU has no device plane: no device metric is made up from it
    assert not DEVICE_METRICS & set(m)


def test_op_names_come_from_the_traced_module_hlo(tmp_path):
    """The trace keeps each module's HLO proto; its instructions' op_name
    paths carry the scopes the step was traced under."""
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x @ x)
        with jax.named_scope("mlp"):
            return (y @ y).sum()
    x = jnp.ones((64, 64))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jax.jit(step)(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = timeline.hlo_op_names(str(path), "jit_step")
    scopes = {timeline.scope_of(v) for v in names.values()}
    assert {"attention", "mlp"} <= scopes
    assert timeline.hlo_op_names(str(path), "jit_nothing") == {}
