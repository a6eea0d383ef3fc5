"""The page-coalescing drain engine and the dirty-page index (PR 2).

Covers: O(entries-on-page) dirty-miss replay with zero whole-log scans,
per-page entry-ref retire accounting across K shards, dirty-miss reads
racing a concurrent drain (never torn, never stale), extent coalescing
reducing backend page writes, the direct plan for non-overlapping entries
(the same extents and device work as the page-image plan), fsync epoch
merging, and the two tier-model satellite fixes (truncate page-state
cleanup, DMWriteCacheTier re-wrap).
"""
import random
import threading
import struct

import pytest

from repro.core import NVCache, Policy
from repro.core import drain as drain_mod
from repro.core.cleanup import CleanupThread
from repro.core.drain import FsyncEpochScheduler
from repro.storage.tiers import (DMWriteCacheTier, DRAM, PAGE, SSD_SATA,
                                 Tier, TierFile)


def make_policy(k: int, **kw) -> Policy:
    defaults = dict(entry_size=256, log_entries=64 * k, page_size=256,
                    read_cache_pages=4, batch_min=4, batch_max=16,
                    shards=k, shard_route="stripe", stripe_pages=2)
    defaults.update(kw)
    return Policy(**defaults)


# ----------------------------------------------------------- dirty-page index
@pytest.mark.parametrize("k", [1, 2, 4])
def test_dirty_miss_inspects_only_the_pages_entries(k):
    """A dirty miss on a page with E live entries replays exactly E refs and
    never rescans the log (acceptance criterion: no scan_all_committed on
    the read path)."""
    # batch_min is clamped to entries_per_shard // 2 = 16: with <= 8 entries
    # per shard nothing drains, so every written entry stays live
    pol = make_policy(k, log_entries=64 * k, batch_min=10 ** 6,
                      read_cache_pages=2)
    nv = NVCache(pol, Tier(DRAM))
    fd = nv.open("/f")
    ps = pol.page_size
    E = 5
    for j in range(E):                       # E small writes, all on page 0
        nv.pwrite(fd, bytes([j + 1]) * 16, j * 16)
    nv.pwrite(fd, b"\xEE" * 32, 7 * ps)      # unrelated page
    # page 0 was updated in place while loaded; force it out of the cache
    for p in range(1, 6):
        nv.pread(fd, ps, p * ps)
    d0 = nv._files["/f"].radix.get(0)
    assert d0.content is None, "page 0 should have been evicted"
    assert d0.dirty_refs == E
    misses0 = nv.stats_dirty_misses
    replay0 = nv.stats_replay_entries
    got = nv.pread(fd, ps, 0)                # the dirty miss under test
    exp = bytearray(ps)
    for j in range(E):
        exp[j * 16:(j + 1) * 16] = bytes([j + 1]) * 16
    assert got == bytes(exp)
    assert nv.stats_dirty_misses == misses0 + 1
    assert nv.stats_replay_entries == replay0 + E   # exactly E, not O(log)
    nv.shutdown()


@pytest.mark.parametrize("k", [1, 2, 4])
def test_refs_are_seq_ordered_and_retired_on_drain(k):
    """Per-page index invariants: refs stay in commit order, and a full
    drain retires every ref on every page (pending accounting matches)."""
    import random
    pol = make_policy(k)
    nv = NVCache(pol, Tier(DRAM))
    fd = nv.open("/f")
    rng = random.Random(17 * k)
    for _ in range(60):
        off = rng.randrange(0, 6 * pol.page_size)
        n = rng.randint(1, 3 * pol.entry_data)
        nv.pwrite(fd, bytes([rng.randrange(1, 255)]) * n, off)
        # sample the invariant mid-stream on a few descriptors
        f = nv._files["/f"]
        for p in range(6):
            d = f.radix.get(p)
            if d is None:
                continue
            refs = d.snapshot_refs()
            seqs = [r.seq for r in refs]
            assert seqs == sorted(seqs), f"page {p} index out of commit order"
    nv.flush()
    f = nv._files["/f"]
    assert f.pending.get() == 0
    assert nv.log.used_entries == 0
    for p in range(12):                       # covers every touched page
        d = f.radix.get(p)
        if d is not None:
            assert d.dirty_refs == 0, f"page {p} kept refs after full drain"
    nv.shutdown()


@pytest.mark.parametrize("k", [1, 2, 4])
def test_dirty_miss_racing_drain_never_torn_or_stale(k):
    """Readers take dirty misses while drains are forced concurrently: a
    page image must never mix two writes (torn) nor lose the freshest
    committed one the reader could prove durable (stale)."""
    pol = Policy(entry_size=1024, log_entries=64 * k, page_size=1024,
                 read_cache_pages=2, batch_min=4, batch_max=16,
                 shards=k, shard_route="stripe", stripe_pages=1)
    nv = NVCache(pol, Tier(DRAM))
    fd = nv.open("/f")
    ps = pol.page_size
    NPAGES = 4
    OPS = 60
    started = [0] * NPAGES
    errors = []
    stop = threading.Event()

    def writer(w):
        try:
            for i in range(OPS):
                p = (w + i) % NPAGES
                c = (w << 16) | (i + 1)
                started[p] = max(started[p], c)
                nv.pwrite(fd, struct.pack("<I", c) * (ps // 4), p * ps)
        except Exception as exc:
            errors.append(exc)

    def reader():
        try:
            i = 0
            while not stop.is_set():
                p = i % NPAGES
                i += 1
                page = nv.pread(fd, ps, p * ps)
                if not page.strip(b"\x00"):
                    continue
                word = page[:4]
                if word * (ps // 4) != page:
                    errors.append(AssertionError(f"torn page {p}"))
                    stop.set()
        except Exception as exc:
            errors.append(exc)

    def flusher():
        try:
            while not stop.is_set():
                nv.flush(timeout=60)
        except Exception as exc:
            errors.append(exc)

    ws = [threading.Thread(target=writer, args=(w,)) for w in range(3)]
    rs = [threading.Thread(target=reader) for _ in range(2)]
    fl = threading.Thread(target=flusher)
    for t in ws + rs + [fl]:
        t.start()
    for t in ws:
        t.join(timeout=120)
    stop.set()
    for t in rs + [fl]:
        t.join(timeout=60)
    if errors:
        raise errors[0]
    nv.flush()
    # after a full drain a dirty miss degenerates to a clean backend read:
    # evict and re-read every page, values must be the freshest committed
    for p in range(NPAGES):
        page = nv.pread(fd, ps, p * ps)
        if page.strip(b"\x00"):
            word = page[:4]
            assert word * (ps // 4) == page, f"torn page {p} after drain"
    nv.shutdown()


# ------------------------------------------------------------- coalescing win
def test_sequential_small_writes_coalesce_into_few_backend_writes():
    """16 KiB of 1 KiB-sequential writes: the coalescing engine writes each
    of the 4 backend pages once, in one vectored write (entry at a time
    would be 16 writes of 16 pages)."""
    pol = Policy(entry_size=1024 + 48, log_entries=256, page_size=4096,
                 read_cache_pages=8, batch_min=4, batch_max=64)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/f")
    for i in range(16):
        nv.pwrite(fd, bytes([i + 1]) * 1024, i * 1024)
    nv.flush()
    f = tier.open("/f")
    assert (f.stats_writes, f.stats_page_writes) == (1, 4)
    # correctness of the coalesced image
    for i in range(16):
        assert nv.pread(fd, 1024, i * 1024) == bytes([i + 1]) * 1024
    assert f.snapshot()[:16 * 1024] == b"".join(
        bytes([i + 1]) * 1024 for i in range(16))
    nv.shutdown()


def test_overlapping_writes_in_one_batch_drain_in_commit_order():
    """Same bytes overwritten repeatedly inside one batch: the materialized
    page must hold the LAST committed value, and the backend page is
    written once."""
    pol = Policy(entry_size=256, log_entries=64, page_size=256,
                 read_cache_pages=4, batch_min=10 ** 6, batch_max=10 ** 6)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/f")
    for v in (1, 2, 3, 4, 5):
        nv.pwrite(fd, bytes([v]) * 100, 50)
    nv.pwrite(fd, b"\x77" * 60, 120)          # overlaps the tail of the above
    nv.flush()
    f = tier.open("/f")
    snap = f.snapshot()
    assert snap[50:120] == b"\x05" * 70
    assert snap[120:180] == b"\x77" * 60
    nv.shutdown()


# ------------------------------------------------------------- direct plan
DPS = 256                                   # page size of the direct-plan runs
DED = 256 - 48                              # entry_data
PATHS = ("/a", "/b", "/c")


def _detached_nv(max_extent: int):
    """An NVCache whose pool threads are stopped, plus an unstarted
    CleanupThread over shard 0 that the test steps by hand, so batch
    boundaries (and carried tails) are exactly the test's choices."""
    pol = Policy(entry_size=256, log_entries=1024, page_size=DPS,
                 read_cache_pages=4, batch_min=10 ** 6, batch_max=10 ** 6,
                 coalesce_max_extent=max_extent,
                 coalesce_deadline_ms=10_000.0)
    tier = Tier(DRAM)
    for path in PATHS:
        tier.open(path)      # pre-exist: open() journals no create record
    nv = NVCache(pol, tier)
    for th in nv.cleanup.threads:
        th.hard_stop.set()
        th.stop_event.set()
        th.shard.notify_committed()
    for th in nv.cleanup.threads:
        th.join(timeout=10)
    return nv, tier, CleanupThread(nv.log, nv.log.shards[0], nv._resolve_fdid)


def _ops(seed: int):
    """A seeded mix, in application order: ``("w", path, off, data)``,
    ``("z", path, off)`` for a zero-length log entry, and ``("step",
    limit)`` for one hand-stepped drain batch of at most ``limit``
    entries.  /a is an append stream with occasional gaps (runs that end
    and begin inside one page) and a rare overlapping rewrite; /b appends
    short lines that share pages at their boundaries; /c is rewritten at
    offset 0, so its batches overlap."""
    rng = random.Random(seed)
    ends = {"/a": rng.randrange(0, DPS), "/b": 0, "/c": 0}
    out = []
    for _ in range(rng.randint(60, 120)):
        r = rng.random()
        if r < 0.45:
            path = "/a"
            off = ends["/a"]
            if rng.random() < 0.1:
                off += rng.randint(1, DPS // 2)           # gap
            elif rng.random() < 0.05:
                off = rng.randrange(0, max(1, off))      # overlapping rewrite
            elif rng.random() < 0.05:
                off = max(0, off - 1)                    # overlaps by a byte
            data = bytes([rng.randrange(1, 256)]) * rng.randint(1, 3 * DED)
        elif r < 0.75:
            path, off = "/b", ends["/b"]
            data = bytes([rng.randrange(1, 256)]) * rng.randint(20, 120)
        elif r < 0.85:
            path, off = "/c", 0
            data = bytes([rng.randrange(1, 256)]) * rng.randint(50, 300)
        elif r < 0.9:
            path = rng.choice(("/a", "/b"))
            out.append(("z", path, ends[path]))
            continue
        else:
            out.append(("step", rng.randint(1, 48)))
            continue
        ends[path] = max(ends[path], off + len(data))
        out.append(("w", path, off, data))
    return out


def _drive(ops, max_extent: int, monkeypatch, *, page_images: bool):
    """Run ``ops`` through a detached drain; returns each batch's extents
    per file, the tier, the drain thread and the written bytes' replay."""
    nv, tier, t = _detached_nv(max_extent)
    fds = {p: nv.open(p) for p in PATHS}
    files = {nv._files[p]: p for p in PATHS}
    sh = nv.log.shards[0]
    batches = []
    build = drain_mod.build_plan

    def record(*a, **kw):
        plan = build(*a, **kw)
        batches.append([(files[fp.file], fp.direct,
                         [(x.off, bytes(x.data), list(x.pages),
                           {p: sorted(i) for p, i in x.retire.items()})
                          for x in fp.extents]) for fp in plan.files])
        return plan

    with monkeypatch.context() as m:
        m.setattr(drain_mod, "build_plan", record)
        if page_images:
            m.setattr(drain_mod, "_disjoint", lambda ents: False)

        def step(limit):
            run = sh.committed_run(sh.persistent_tail, limit)
            if run:
                t._consume_batch(run)
            # every drained entry's refs are gone from every page
            for f in files:
                for p in range(f.hwm // DPS + 2):
                    d = f.radix.get(p)
                    assert d is None or not any(
                        r.sid == sh.sid and r.idx < sh.persistent_tail
                        for r in d.snapshot_refs()), (files[f], p)

        replay = {p: bytearray() for p in PATHS}
        for op in ops:
            if op[0] == "step":
                step(op[1])
            elif op[0] == "z":
                f = nv._files[op[1]]
                nv.log.append(f.fdid, op[2], b"", shard=0)
                f.pending.inc(1)
            else:
                _, path, off, data = op
                nv.pwrite(fds[path], data, off)
                img = replay[path]
                if off + len(data) > len(img):
                    img.extend(bytes(off + len(data) - len(img)))
                img[off:off + len(data)] = data
        t.drain_event.set()                   # a barrier: flush any carry
        while sh.committed_run(sh.persistent_tail, 10 ** 6):
            step(10 ** 6)
    assert nv.log.used_entries == 0
    nv.shutdown()
    return batches, tier, t, replay


@pytest.mark.parametrize("max_extent", [DPS, 3 * DPS + 100, 1 << 20])
@pytest.mark.parametrize("seed", range(4))
def test_direct_plan_matches_page_images(seed, max_extent, monkeypatch):
    """Seeded batches mixing append streams, entries sharing a page at
    their boundary, overlapping rewrites, zero-length entries and carried
    tails: the adaptive plan writes the bytes of an in-order replay, and
    gives the tier the extents, the write calls, the dirty pages and the
    fsyncs that the page-image plan gives for the same batches."""
    ops = _ops(seed)
    got, tier, t, replay = _drive(ops, max_extent, monkeypatch,
                                  page_images=False)
    ref, rtier, rt, _ = _drive(ops, max_extent, monkeypatch,
                               page_images=True)
    assert [[(p, x) for p, _d, x in b] for b in got] == \
        [[(p, x) for p, _d, x in b] for b in ref]
    directs = {d for b in got for _p, d, _x in b}
    assert directs == {True, False}, "both plans must be exercised"
    assert not any(d for b in ref for _p, d, _x in b)
    assert t.stats_direct_entries > 0 and rt.stats_direct_entries == 0
    assert t.stats_span_merges > 0, "no carried tail was exercised"
    for path in PATHS:
        a, b = tier.open(path), rtier.open(path)
        assert a.snapshot()[:len(replay[path])] == bytes(replay[path])
        assert not a.snapshot()[len(replay[path]):].strip(b"\x00")
        assert a.snapshot() == b.snapshot()
        for k in ("stats_writes", "stats_page_writes", "stats_wvec_segments",
                  "stats_fsyncs"):
            assert getattr(a, k) == getattr(b, k), (path, k)


@pytest.mark.parametrize("kind", ["append", "rewrite"])
def test_direct_entries_counter(kind):
    """``stats_direct_entries`` counts an append stream's entries, and
    stays 0 for a file rewritten at offset 0 within one batch."""
    nv, tier, t = _detached_nv(1 << 20)
    fd = nv.open("/a")
    sh = nv.log.shards[0]
    for i in range(6):
        off = i * 300 if kind == "append" else 0
        nv.pwrite(fd, bytes([i + 1]) * 300, off)
    t.drain_event.set()
    n = sh.committed_run(sh.persistent_tail, 10 ** 6)
    t._consume_batch(n)
    assert t.stats_entries == n
    assert t.stats_direct_entries == (n if kind == "append" else 0)
    nv.shutdown()
    # the pool sums it and stats() reports it
    nv = NVCache(make_policy(2), Tier(DRAM))
    fd = nv.open("/a")
    for i in range(6):
        nv.pwrite(fd, bytes([i + 1]) * 300, i * 300 if kind == "append" else 0)
    nv.flush()
    direct = nv.stats()["drain_direct_entries"]
    assert direct == nv.cleanup.stats_direct_entries
    if kind == "append":
        assert direct == nv.stats()["cleanup_entries"] > 0
    nv.shutdown()


@pytest.mark.parametrize("log_mib", [1, 8])
def test_checkpoint_stream_drains_direct_at_the_launcher_policy(log_mib):
    """A checkpoint-shaped stream (seeded records of varied length appended
    back to back, as ``checkpoint.codec.Writer`` writes them, 4 MiB in all)
    through the file system ``launch.train.open_fs`` builds (16 KiB
    entries): on a 1 MiB log that the stream fills four times over, and on
    an 8 MiB log that holds it whole.  Every drained entry is planned
    direct, and the blob file reads back byte for byte."""
    from repro.launch.train import open_fs
    fs = open_fs(log_mib)
    nv = fs.nv
    rng = random.Random(log_mib)
    fd = fs.open("/ckpt.bin")
    blob = bytearray()
    while len(blob) < 4 << 20:
        rec = rng.randbytes(rng.randrange(1, 256 << 10))
        fs.pwrite(fd, rec, len(blob))
        blob += rec
    nv.flush()
    s = nv.stats()
    entries = s["cleanup_entries"]
    assert entries >= len(blob) // nv.policy.entry_data
    if log_mib == 1:
        assert entries > 4 * nv.policy.log_entries    # the log refilled
    else:
        assert entries < nv.policy.log_entries        # the log held it all
    assert s["drain_direct_entries"] == entries
    assert nv.tier.open("/ckpt.bin").snapshot() == blob
    fs.close(fd)
    nv.shutdown()


# ------------------------------------------------------------------ batching
def test_drain_fsyncs_fall_as_batch_min_rises():
    """The cleanup thread's fsync amortisation (paper §IV-C, Fig. 6): one
    stream of 512 random 4 KiB writes, one log entry each, drains with
    fewer fsyncs as ``batch_min`` rises.  With a carry deadline far past
    the run, only the batch bounds decide where a batch ends, so the
    counts are bounded whatever the threads' timing: at least 51 fsyncs
    at (1, 10), 32 to 39 at (16, 16), at most 4 at (256, 256)."""
    fsyncs = []
    for batch_min, batch_max in ((1, 10), (16, 16), (256, 256)):
        pol = Policy(entry_size=4096 + 48, log_entries=1024, page_size=4096,
                     read_cache_pages=8, batch_min=batch_min,
                     batch_max=batch_max, coalesce_deadline_ms=10_000.0)
        nv = NVCache(pol, Tier(DRAM))
        fd = nv.open("/f")
        rng = random.Random(11)
        for _ in range(512):
            nv.pwrite(fd, b"x" * 4096, rng.randrange(512) * 4096)
        nv.flush()
        s = nv.stats()
        assert s["cleanup_entries"] == 512
        fsyncs.append(s["cleanup_fsyncs"])
        nv.shutdown()
    assert fsyncs[0] > fsyncs[1] > fsyncs[2], fsyncs


# ---------------------------------------------------------------- fsync epoch
class _SlowSyncFile:
    def __init__(self):
        self.gate = threading.Event()
        self.entered = threading.Event()
        self.fsyncs = 0
        self._lock = threading.Lock()

    def fsync(self):
        with self._lock:
            self.fsyncs += 1
            first = self.fsyncs == 1
        if first:
            self.entered.set()
            assert self.gate.wait(timeout=30)


def test_fsync_epoch_scheduler_merges_concurrent_requests():
    """While one fsync is in flight, every caller that arrives shares the
    single next epoch: 1 + N concurrent requests -> exactly 2 device
    fsyncs, and each caller returns only after an fsync that started after
    its request."""
    sched = FsyncEpochScheduler()
    f = _SlowSyncFile()
    t0 = threading.Thread(target=sched.fsync, args=(f,))
    t0.start()
    assert f.entered.wait(timeout=30)         # epoch 1 is now in flight
    late = [threading.Thread(target=sched.fsync, args=(f,)) for _ in range(3)]
    for t in late:
        t.start()
    # the 3 latecomers must all be waiting, not issuing
    deadline = threading.Event()
    deadline.wait(0.05)
    assert f.fsyncs == 1
    f.gate.set()                              # release epoch 1
    t0.join(timeout=30)
    for t in late:
        t.join(timeout=30)
    assert not t0.is_alive() and not any(t.is_alive() for t in late)
    assert f.fsyncs == 2                      # 4 requests -> 2 epochs
    assert sched.stats_requests == 4
    assert sched.stats_issued == 2
    assert sched.stats_merged == 2


def test_fsync_epoch_failure_reaches_every_sharer():
    """A failed device fsync must surface to EVERY caller that shared the
    epoch — a merged drain thread must never retire log entries whose data
    never became durable."""
    class FailingSyncFile(_SlowSyncFile):
        def fsync(self):
            super().fsync()
            raise OSError("EIO")

    sched = FsyncEpochScheduler()
    f = FailingSyncFile()
    results = []

    def call():
        try:
            sched.fsync(f)
            results.append(None)
        except OSError as e:
            results.append(e)

    t0 = threading.Thread(target=call)
    t0.start()
    assert f.entered.wait(timeout=30)         # epoch 1 in flight (will fail)
    late = [threading.Thread(target=call) for _ in range(3)]
    for t in late:
        t.start()
    f.gate.set()
    for t in [t0] + late:
        t.join(timeout=30)
    assert len(results) == 4
    assert all(isinstance(r, OSError) for r in results), results
    assert f.fsyncs == 2                      # epoch 1 + the shared epoch 2


# ---------------------------------------------------------- tier model fixes
def test_truncate_drops_page_state_beyond_new_size():
    """Satellite: fsync after truncate must not pay for pages that no
    longer exist."""
    tier = Tier(SSD_SATA)
    f = tier.open("/t")
    f.pwrite(b"x" * (10 * PAGE), 0)
    assert len(f._dirty_pages) == 10
    f.truncate(PAGE + 1)                      # keep pages 0 and 1 (partial)
    assert f._dirty_pages == {0, 1}
    assert f._cached_pages == {0, 1}
    cost_before = tier.gate.total_cost
    f.fsync()
    paid = tier.gate.total_cost - cost_before
    expect = (SSD_SATA.fsync_base_s + 2 * SSD_SATA.page_write_s
              + SSD_SATA.syscall_s)
    assert abs(paid - expect) < 1e-9, (paid, expect)
    f.truncate(0)
    assert not f._dirty_pages and not f._cached_pages


def test_dm_writecache_reopen_does_not_double_charge():
    """Satellite: re-opening the same path must not stack another pwrite
    wrapper (which double-charged the NVMM commit cost per reopen)."""
    tier = DMWriteCacheTier(scale=1.0)
    f1 = tier.open("/d")
    wrapped_once = f1.pwrite
    f2 = tier.open("/d")
    assert f2 is f1
    assert f2.pwrite is wrapped_once          # not re-wrapped
    cost0 = tier.gate.total_cost
    f2.pwrite(b"z" * PAGE, 0)
    single_open_cost = tier.gate.total_cost - cost0
    ref_tier = DMWriteCacheTier(scale=1.0)
    rf = ref_tier.open("/d")
    rc0 = ref_tier.gate.total_cost
    rf.pwrite(b"z" * PAGE, 0)
    assert abs((ref_tier.gate.total_cost - rc0) - single_open_cost) < 1e-9
    assert f1.stats_writes == 1               # counted once, not per wrapper


def test_pwritev_cost_and_stats_model():
    """The vectored write path: one syscall + per-segment overhead, page
    accounting deduplicated per call."""
    tier = Tier(SSD_SATA)                     # buffered: no page cost on write
    f = tier.open("/v")
    c0 = tier.gate.total_cost
    n = f.pwritev([(b"a" * 100, 0), (b"b" * 100, 100), (b"c" * 100, 200)])
    assert n == 300
    paid = tier.gate.total_cost - c0
    expect = SSD_SATA.syscall_s + 2 * SSD_SATA.iov_seg_s
    assert abs(paid - expect) < 1e-12
    assert f.stats_writes == 1
    assert f.stats_wvec_segments == 3
    assert f.stats_page_writes == 1           # all three segments on page 0
    assert f.snapshot()[:300] == b"a" * 100 + b"b" * 100 + b"c" * 100
    # sync tier: unique pages charged once per call even if hit twice
    stier = Tier(SSD_SATA, sync=True)
    sf = stier.open("/s")
    c0 = stier.gate.total_cost
    sf.pwritev([(b"x" * 10, 0), (b"y" * 10, 100)])   # same page twice
    paid = stier.gate.total_cost - c0
    expect = (SSD_SATA.syscall_s + SSD_SATA.iov_seg_s
              + 1 * SSD_SATA.page_write_s)
    assert abs(paid - expect) < 1e-12
