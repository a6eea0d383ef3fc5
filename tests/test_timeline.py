"""Timeline spans (``repro.obs.span``) in the JAX profiler's trace.

A smoke-size ``train()`` with two saves and a resume, and a tiny NVCache
log under write load, each recorded with ``jax.profiler.start_trace`` and
read back with ``ProfileData``: every span appears, nests where the code
nests, and carries byte counts that add up to the state and the files.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.checkpoint import codec
from repro.configs.registry import get_smoke
from repro.core import NVCache, Policy
from repro.data.pipeline import SyntheticTokens
from repro.launch.train import open_fs
from repro.models.registry import build
from repro.optim.adamw import AdamW
from repro.storage.fsapi import NVCacheFS
from repro.storage.tiers import DRAM, Tier
from repro.train import steps as tsteps
from repro.train.loop import train

PREFIXES = ("train.", "ckpt.", "nv.", "log.", "drain.")
TRAIN_SPANS = {"train.step_us", "train.batch_us", "train.metrics_us",
               "train.save_us", "train.d2h_us", "ckpt.save_us",
               "ckpt.finalize_us", "ckpt.encode_us", "ckpt.encode_wait_us",
               "ckpt.write_us", "ckpt.manifest_us", "train.pipeline_us",
               "train.restore_us", "ckpt.restore_us", "ckpt.read_us",
               "ckpt.decode_us", "train.h2d_us", "nv.recover_us"}


def _trace(logdir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)


def _spans(logdir):
    """(name, start, end, thread, stats) of every program span; a thread is
    a line of a host plane (lines carry the process's name, not an id)."""
    path = next(Path(logdir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.name, ev.start_ns, ev.end_ns, (plane.name, i),
                                dict(ev.stats)))
    return out


def _inside(inner, outer):
    return inner[3] == outer[3] and outer[1] <= inner[1] and inner[2] <= outer[2]


def _within(spans, name, outer):
    return [s for s in spans if s[0] == name and _inside(s, outer)]


def _during(spans, name, outer):
    """The ``name`` spans of any thread that lie inside ``outer``'s time."""
    return [s for s in spans if s[0] == name and outer[1] <= s[1] and s[2] <= outer[2]]


@pytest.fixture(scope="module")
def traced_job(tmp_path_factory):
    """Four steps saving every two, a power loss, and a resume that runs
    one step and saves, all inside one trace."""
    cfg = get_smoke("minicpm3-4b")
    model, opt = build(cfg), AdamW()
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        tsteps.abstract_train_state(model, opt)))
    fs = open_fs(log_mib=1)
    logdir = tmp_path_factory.mktemp("trace")
    pipe = lambda: SyntheticTokens(cfg.vocab, 2, 16, seed=3)  # noqa: E731
    _trace(logdir)
    try:
        train(model, opt, pipe(), fs, total_steps=4, ckpt_every=2)
        region = fs.nv.crash()
        fs2 = NVCacheFS(NVCache(fs.nv.policy, fs.nv.tier, nvmm=region))
        _, hist = train(model, opt, pipe(), fs2, total_steps=5, ckpt_every=100)
    finally:
        jax.profiler.stop_trace()
    sizes = {}
    for step in (2, 4):
        fd = fs2.open(f"/ckpt/step_{step:08d}.ckpt")
        sizes[step] = fs2.size(fd)
        fs2.close(fd)
    fs2.nv.shutdown()
    return _spans(logdir), nbytes, sizes, hist


def test_every_span_of_the_job_appears(traced_job):
    spans, _, _, hist = traced_job
    assert TRAIN_SPANS <= {s[0] for s in spans}
    assert [h["step"] for h in hist] == [4]          # resumed at the save


def test_save_spans_nest_and_count_the_state(traced_job):
    spans, nbytes, sizes, _ = traced_job
    saves = [s for s in spans if s[0] == "train.save_us"]
    assert [s[4]["step"] for s in saves] == [2, 4, 5]
    for outer in saves:
        ckpt = _within(spans, "ckpt.save_us", outer)
        assert len(ckpt) == 1 and ckpt[0][4]["bytes"] == nbytes
        assert len(_within(spans, "train.d2h_us", outer)) == 1
        assert len(_within(spans, "train.pipeline_us", outer)) == 1
        enc = _during(spans, "ckpt.encode_us", ckpt[0])
        assert enc and sum(s[4]["bytes"] for s in enc) == nbytes
        assert all(0 < s[4]["out_bytes"] for s in enc)
        assert len(_within(spans, "ckpt.manifest_us", ckpt[0])) == 1
        writes = _within(spans, "ckpt.write_us", ckpt[0])
        step = outer[4]["step"]
        if step in sizes:
            assert sum(s[4]["bytes"] for s in writes) == sizes[step]
    ckpt_saves = [s for s in spans if s[0] == "ckpt.save_us"]
    assert all(any(_inside(s, o) for o in ckpt_saves)
               for s in spans if s[0] in ("ckpt.encode_wait_us", "ckpt.write_us",
                                          "ckpt.manifest_us"))
    assert all(any(o[1] <= s[1] and s[2] <= o[2] for o in ckpt_saves)
               for s in spans if s[0] == "ckpt.encode_us")


def test_save_encodes_on_pool_threads(traced_job):
    """Each save's records are encoded on the codec's pool threads
    (``ckpt.encode_us``) while the calling thread waits for each in turn
    (``ckpt.encode_wait_us``) and writes it; ``ckpt.save_us`` carries the
    pool's size."""
    spans, _, _, _ = traced_job
    for ckpt in [s for s in spans if s[0] == "ckpt.save_us"]:
        enc = _during(spans, "ckpt.encode_us", ckpt)
        waits = _within(spans, "ckpt.encode_wait_us", ckpt)
        # every write but the magic, the index and the footer is a record
        assert len(waits) == len(enc) == len(_within(spans, "ckpt.write_us", ckpt)) - 3
        threads = {s[3] for s in enc}
        assert ckpt[3] not in threads
        assert ckpt[4]["encode_workers"] == codec.encode_workers()
        assert 1 <= len(threads) <= ckpt[4]["encode_workers"]


def test_restore_spans_nest_and_count_the_tree(traced_job):
    spans, nbytes, _, _ = traced_job
    (outer,) = [s for s in spans if s[0] == "train.restore_us"]
    assert outer[4]["step"] == 4
    (ckpt,) = _within(spans, "ckpt.restore_us", outer)
    assert ckpt[4]["bytes"] == nbytes
    dec = _within(spans, "ckpt.decode_us", ckpt)
    assert sum(s[4]["bytes"] for s in dec) == nbytes
    assert len(_within(spans, "ckpt.read_us", ckpt)) == len(dec)
    (h2d,) = _within(spans, "train.h2d_us", outer)
    assert h2d[4]["bytes"] == nbytes
    (rec,) = [s for s in spans if s[0] == "nv.recover_us"]
    assert rec[2] <= outer[1] and rec[4]["entries"] >= 0


def test_step_spans_carry_their_step(traced_job):
    spans, _, _, _ = traced_job
    steps = [s[4]["step"] for s in spans if s[0] == "train.step_us"]
    assert steps == [0, 1, 2, 3, 4]
    batches = [s for s in spans if s[0] == "train.batch_us"]
    assert len(batches) == len(steps)
    assert all(s[4]["bytes"] > 0 for s in spans if s[0] == "train.metrics_us")


def test_tiny_log_emits_alloc_wait_and_drain_spans(tmp_path):
    """Writes through a log of eight entries: writers wait for log space,
    and the drain, on its own thread, emits one span per batch."""
    pol = Policy(entry_size=256, log_entries=8, page_size=256,
                 read_cache_pages=4, batch_min=2, batch_max=4)
    nv = NVCache(pol, Tier(DRAM))
    fd = nv.open("/tiny")
    _trace(tmp_path)
    try:
        for i in range(64):
            nv.pwrite(fd, bytes([i]) * 200, i * 200)
        nv.fsync(fd)
    finally:
        jax.profiler.stop_trace()
        nv.shutdown()
    spans = _spans(tmp_path)
    waits = [s for s in spans if s[0] == "log.alloc_wait_us"]
    drains = [s for s in spans if s[0] == "drain.batch_us"]
    assert waits and all(s[4]["shard"] == 0 for s in waits)
    assert drains and all(s[4]["entries"] > 0 for s in drains)
    assert sum(s[4].get("bytes", 0) for s in drains) > 0
    writer_lines = {s[3] for s in waits}
    assert not writer_lines & {s[3] for s in drains}     # another thread


def test_span_is_a_shared_noop_without_a_session():
    from repro import obs
    assert not jax.profiler.TraceAnnotation.is_enabled()
    a, b = obs.span("train.step_us", step=1), obs.span("ckpt.write_us")
    assert a is b
    with a as sp:
        sp.set(bytes=3)


@pytest.mark.parametrize("bound", [(), ("profiler",)])
def test_span_is_a_noop_while_jax_is_half_imported(monkeypatch, bound):
    """``sys.modules['jax']`` exists from the first line of its import, long
    before ``jax.profiler.TraceAnnotation`` does."""
    import types

    from repro.obs import spans
    half = types.ModuleType("jax")
    for name in bound:
        setattr(half, name, types.ModuleType(f"jax.{name}"))
    monkeypatch.setattr(spans, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", half)
    with spans.span("drain.batch_us", bytes=1) as sp:
        sp.set(entries=1)
    assert spans.span("log.alloc_wait_us") is spans._NO_SPAN
    assert spans._annotation is None                 # nothing partial cached


def test_core_import_leaves_jax_unloaded():
    env = dict(os.environ, PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    code = ("import sys, repro.core, repro.obs; "
            "assert 'jax' not in sys.modules, 'jax loaded'")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
