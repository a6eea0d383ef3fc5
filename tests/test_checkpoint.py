"""Checkpoint manager: round-trip, crash-mid-save atomicity (the paper's
group-commit at application granularity), resharded restore, int8 mode,
and the codec's encode pool (same file, errors, abandoned writers)."""
import gc
import itertools
import os
import struct
import threading
import zlib

import jax
import ml_dtypes
import msgpack
import numpy as np
import pytest

from repro.checkpoint import codec
from repro.checkpoint.manager import CheckpointManager
from repro.core import NVCache, Policy
from repro.runtime.elastic import reshard_restore
from repro.storage.fsapi import NVCacheFS, TierFS
from repro.storage.tiers import DRAM, Tier

POL = Policy(entry_size=4096, log_entries=4096, page_size=4096,
             read_cache_pages=64, batch_min=8, batch_max=256, verify_crc=False)


def _tree(seed=0, n=4000):
    rng = np.random.default_rng(seed)
    return {"params": {"w": rng.standard_normal((8, n)).astype(np.float32),
                       "b": rng.standard_normal((n,)).astype(np.float32)},
            "opt": {"m": rng.standard_normal((8, n)).astype(np.float32),
                    "step": np.int32(3)}}


def _eq(a, b, atol=0.0):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.allclose(x, y, atol=atol) for x, y in zip(flat_a, flat_b))


def test_roundtrip_tier():
    fs = TierFS(Tier(DRAM))
    mgr = CheckpointManager(fs)
    t = _tree()
    mgr.save(1, t)
    got = mgr.restore(t)
    assert _eq(t, got)


def test_roundtrip_nvcache_and_latest():
    nv = NVCache(POL, Tier(DRAM))
    mgr = CheckpointManager(NVCacheFS(nv))
    t1, t2 = _tree(1), _tree(2)
    mgr.save(1, t1)
    mgr.save(2, t2)
    assert mgr.latest_step() == 2
    assert _eq(t2, mgr.restore(t2))
    assert _eq(t1, mgr.restore(t1, step=1))
    mgr.close()
    nv.shutdown()


def _workers(monkeypatch, cpus):
    """Size the codec's encode pool by the core count it reads."""
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    return codec.encode_workers()


def _encode_threads():
    return {t for t in threading.enumerate() if t.name.startswith("ckpt-encode")}


@pytest.mark.parametrize("cpus", [1, 64])
def test_crash_mid_save_restores_previous_step(monkeypatch, cpus):
    """Kill power while step-2 data is written but its manifest is not:
    recovery must restore step 1 exactly, never a torn step 2.  The
    abandoned writer, with records still encoding, writes nothing more,
    and its encode threads exit once it is collected."""
    _workers(monkeypatch, cpus)
    tier = Tier(DRAM)
    nv = NVCache(POL, tier, track_crashes=True)
    fs = NVCacheFS(nv)
    mgr = CheckpointManager(fs)
    t1, t2 = _tree(1), _tree(2)
    mgr.save(1, t1)
    # write step-2 data WITHOUT committing the manifest (crash point)
    before = _encode_threads()
    w = codec.Writer(fs, "/ckpt/step_00000002.ckpt", close_on_finish=False,
                     chunk_bytes=16000)
    for k, leaf in [("params/w", t2["params"]["w"]), ("opt/m", t2["opt"]["m"])]:
        w.put_leaf(k, leaf)
    written = fs.size(w.fd)
    for *_, fut in list(w._pending):
        fut.result()
    assert fs.size(w.fd) == written == w.off
    threads = _encode_threads() - before
    assert threads
    del w, fut
    gc.collect()
    for t in threads:
        t.join(timeout=10)
    assert not [t for t in threads if t.is_alive()]
    nvmm = nv.crash()
    # recovery into the surviving slow tier
    from repro.core import recover
    recover(nvmm, POL, tier.open)
    nv2 = NVCache(POL, tier)
    mgr2 = CheckpointManager(NVCacheFS(nv2))
    assert mgr2.latest_step() == 1
    assert _eq(t1, mgr2.restore(t1))
    nv2.shutdown()


def test_resharded_restore():
    """Save once, restore per-shard slices for a new shard count; the
    concatenation equals the original (elastic re-mesh path)."""
    fs = TierFS(Tier(DRAM))
    mgr = CheckpointManager(fs)
    t = _tree()
    mgr.save(5, t)
    parts = [reshard_restore(mgr, t, shard_idx=i, n_shards=4) for i in range(4)]
    w = np.concatenate([p["params"]["w"] for p in parts], axis=0)
    assert np.allclose(w, t["params"]["w"])
    # leaves not divisible by shards are replicated
    assert all(np.allclose(p["params"]["b"], t["params"]["b"]) for p in parts)


def test_int8_checkpoint_error_bounded():
    fs = TierFS(Tier(DRAM))
    mgr = CheckpointManager(fs, encoding=codec.ENC_INT8)
    t = _tree()
    info = mgr.save(1, t)
    got = mgr.restore(t)
    w, gw = t["params"]["w"], got["params"]["w"]
    denom = np.abs(w).max()
    assert np.abs(w - gw).max() <= denom / 127 + 1e-6
    # int (non-float) leaves stay exact
    assert got["opt"]["step"] == 3


requires_zstd = pytest.mark.skipif(
    codec.zstandard is None, reason="optional dependency `zstandard` not installed")


@requires_zstd
def test_zstd_payloads_roundtrip():
    """With zstandard installed, compressed records use it (not the zlib
    fallback) and round-trip exactly."""
    fs = TierFS(Tier(DRAM))
    w = codec.Writer(fs, "/z.ckpt", encoding=codec.ENC_ZSTD)
    arr = np.arange(8192, dtype=np.float32).reshape(64, 128)
    w.put_leaf("a", arr)
    w.finish()
    r = codec.Reader(fs, "/z.ckpt")
    assert all(e[0] == "a" for e in r.index)
    assert np.array_equal(r.read_leaf("a"), arr)


def test_zlib_fallback_roundtrip():
    """Force the zlib path (as on hosts without zstandard): records are
    tagged ENC_ZLIB / zc=1 and decode without zstd."""
    real = codec.zstandard
    codec.zstandard = None
    try:
        fs = TierFS(Tier(DRAM))
        w = codec.Writer(fs, "/zl.ckpt", encoding=codec.ENC_ZSTD)
        arr = np.arange(4096, dtype=np.float32)
        w.put_leaf("a", arr)
        w.finish()
        wq = codec.Writer(fs, "/q.ckpt", encoding=codec.ENC_INT8)
        wq.put_leaf("a", arr)
        wq.finish()
        assert np.array_equal(codec.Reader(fs, "/zl.ckpt").read_leaf("a"), arr)
        got = codec.Reader(fs, "/q.ckpt").read_leaf("a")
        assert np.abs(got - arr).max() <= np.abs(arr).max() / 127 + 1e-6
    finally:
        codec.zstandard = real


def test_missing_manifest_reads_as_no_checkpoint():
    mgr = CheckpointManager(TierFS(Tier(DRAM)))
    assert mgr.latest_step() is None
    with pytest.raises(FileNotFoundError):
        mgr.restore(_tree())


def test_corrupt_manifest_raises():
    """A torn or garbled manifest must not read as 'no checkpoint' (which
    would silently restart training from step 0)."""
    fs = TierFS(Tier(DRAM))
    mgr = CheckpointManager(fs)
    mgr.save(1, _tree())
    fd = fs.open("/ckpt/MANIFEST.json")
    fs.pwrite(fd, b"{\"steps\": [1], \"lat", 0)
    fs.ftruncate(fd, 19)
    with pytest.raises(ValueError):
        CheckpointManager(fs).latest_step()


def test_gc_keeps_last_k():
    fs = TierFS(Tier(DRAM))
    mgr = CheckpointManager(fs, keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t)
    m = mgr._read_manifest()
    assert m["steps"] == [3, 4]


def _mixed_tree(seed=0):
    """Leaves of several dtypes, a scalar, and leaves of many records at a
    small ``chunk_bytes``."""
    rng = np.random.default_rng(seed)
    return {"f32": rng.standard_normal((96, 300)).astype(np.float32),
            "bf16": rng.standard_normal((77, 130)).astype(ml_dtypes.bfloat16),
            "i32": rng.integers(-1000, 1000, (50, 64)).astype(np.int32),
            "vec": rng.standard_normal((700,)).astype(np.float32),
            "step": np.int32(7)}


def _file(fs, enc, tree, chunk_bytes):
    w = codec.Writer(fs, f"/f{enc}", encoding=enc, chunk_bytes=chunk_bytes,
                     close_on_finish=False)
    for k, leaf in tree.items():
        w.put_leaf(k, leaf)
    info = w.finish()
    return fs.pread(w.fd, info["size"], 0), w.workers


@pytest.mark.parametrize("enc", [codec.ENC_ZSTD, codec.ENC_ZLIB, codec.ENC_INT8,
                                 codec.ENC_RAW])
def test_encode_pool_writes_the_serial_file(monkeypatch, enc):
    """One encode worker or four, the file is the same bytes: records in
    put order, back to back after the magic, each zstd or zlib payload the
    level-3 or level-6 compression of its rows, and a restore bitwise equal."""
    tree, chunk_bytes = _mixed_tree(), 4096
    files = {}
    for cpus in (1, 64):
        _workers(monkeypatch, cpus)
        fs = TierFS(Tier(DRAM))
        files[cpus], workers = _file(fs, enc, tree, chunk_bytes)
        assert workers == {1: 1, 64: 4}[cpus]
    data = files[1]
    assert files[64] == data
    r = codec.Reader(fs, f"/f{enc}")
    assert len(r.index) > 20
    off, zstd_records = len(codec.MAGIC), 0
    for path, start, end, rec_off, ln in r.index:
        assert rec_off == off
        off += ln
        hlen, plen = struct.unpack("<II", data[rec_off:rec_off + 8])
        meta = msgpack.unpackb(data[rec_off + 8:rec_off + 8 + hlen])
        payload = data[rec_off + 8 + hlen:rec_off + 8 + hlen + plen]
        leaf = np.asarray(tree[path])
        rows = leaf.reshape(1) if leaf.ndim == 0 else leaf[start:end]
        if meta["enc"] == codec.ENC_ZSTD:
            zstd_records += 1
            assert payload == codec.zstandard.compress(rows.tobytes(), 3)
        elif meta["enc"] == codec.ENC_ZLIB:
            assert payload == zlib.compress(rows.tobytes(), 6)
        elif meta["enc"] == codec.ENC_RAW:
            assert payload == rows.tobytes()
    order = [(e[0], e[1]) for e in r.index]
    assert order == sorted(order, key=lambda ps: (list(tree).index(ps[0]), ps[1]))
    if enc == codec.ENC_ZSTD and codec.zstandard is not None:
        assert zstd_records == len(r.index)
    for key, leaf in tree.items():
        got = r.read_leaf(key)
        if enc != codec.ENC_INT8 or np.asarray(leaf).dtype.kind != "f":
            assert got.dtype == np.asarray(leaf).dtype
            assert got.tobytes() == np.asarray(leaf).tobytes()


class _Planted(RuntimeError):
    pass


@pytest.mark.parametrize("where", ["encode", "write"])
def test_failed_save_keeps_previous_step(monkeypatch, where):
    """An encode that raises on a pool thread, or a write that raises on
    the calling thread, makes ``save`` raise before its manifest: step 1
    stays newest and restores bitwise, and no encode thread of the failed
    save is left running."""
    assert _workers(monkeypatch, 64) == 4
    fs = TierFS(Tier(DRAM))
    mgr = CheckpointManager(fs)
    t1, t2 = _tree(1, n=400_000), _tree(2, n=400_000)    # records of 4 MiB
    mgr.save(1, t1)
    calls = itertools.count()
    if where == "encode":
        real = codec._compress

        def compress(raw, **kw):
            if next(calls) == 3:
                raise _Planted(where)
            return real(raw, **kw)
        monkeypatch.setattr(codec, "_compress", compress)
    else:
        real = fs.pwrite

        def pwrite(fd, data, off):
            if next(calls) == 3:
                raise _Planted(where)
            return real(fd, data, off)
        monkeypatch.setattr(fs, "pwrite", pwrite)
    before = _encode_threads()
    with pytest.raises(_Planted):
        mgr.save(2, t2)
    assert not [t for t in _encode_threads() - before if t.is_alive()]
    mgr2 = CheckpointManager(fs)
    assert mgr2.latest_step() == 1 and mgr2._read_manifest()["steps"] == [1]
    got = mgr2.restore(t1)
    assert all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
               for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(got)))
