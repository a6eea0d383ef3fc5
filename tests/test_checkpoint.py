"""Checkpoint manager: round-trip, crash-mid-save atomicity (the paper's
group-commit at application granularity), resharded restore, int8 mode."""
import jax
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


def test_crash_mid_save_restores_previous_step():
    """Kill power while step-2 data is written but its manifest is not:
    recovery must restore step 1 exactly, never a torn step 2."""
    tier = Tier(DRAM)
    nv = NVCache(POL, tier, track_crashes=True)
    fs = NVCacheFS(nv)
    mgr = CheckpointManager(fs)
    t1, t2 = _tree(1), _tree(2)
    mgr.save(1, t1)
    # write step-2 data WITHOUT committing the manifest (crash point)
    w = codec.Writer(fs, "/ckpt/step_00000002.ckpt", close_on_finish=False)
    for k, leaf in [("params/w", t2["params"]["w"])]:
        w.put_leaf(k, leaf)
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
