"""Dual persistence engine (PR 7 tentpole): paged NVMM regions absorbing
large / overwrite-heavy streams in place, next to the sharded log.

Three layers:

* classifier — the per-file stream detector proposes log→page for large or
  rewrite-heavy windows, page→log for small-write windows, and never flips
  on a flip-flopping stream (hysteresis);
* engine semantics — paged writes commit into frames (no log append),
  reads serve framed pages from NVMM without replay or full scans, flush /
  close / shutdown write frames back, truncate clips or drops them, the
  pool falls back to the log when exhausted;
* crash consistency — a fuse wired into the NVMM kills the run at every
  persistence-protocol point across a log→page→log migration script;
  after recovery every page must hold a committed prefix state: old or
  new, never torn, across BOTH modes (the frames' seq-fencing against the
  log and the metadata journal).

Also covers the satellites riding along: the fsync-free ``ftruncate(0)``
WAL-reset drain and the deferred backend apply for ``rename``.
"""
import os

import pytest

from repro.core import NVCache, Policy, recover
from repro.core.log import META_NO_FDID, MOP_RENAME
from repro.core.policy import StreamClassifier
from repro.storage.tiers import DRAM, Tier
from test_namespace import ThreadFusedNVMM, clone_tier
from test_sharded_recovery import PowerLoss

PS = 256


def make_policy(**kw):
    base = dict(entry_size=256, log_entries=128, page_size=PS,
                read_cache_pages=8, batch_min=4, batch_max=16,
                page_frames=16, classify_window=4)
    base.update(kw)
    return Policy(**base)


def the_file(nv):
    assert len(nv._by_fdid) == 1
    return next(iter(nv._by_fdid.values()))


# ------------------------------------------------------------- classifier
def test_classifier_small_writes_stay_log():
    clf = StreamClassifier(make_policy())
    for i in range(64):                      # small writes, distinct pages
        assert clf.note_write(i * PS, 16) is None
    assert clf.mode == "log"


def test_classifier_large_writes_propose_page():
    clf = StreamClassifier(make_policy())
    got = [clf.note_write(i * PS, PS) for i in range(8)]
    # window 1 votes page (no switch yet: hysteresis), window 2 confirms
    assert got[3] is None and got[7] == "page"
    clf.confirm("page")
    assert clf.mode == "page"
    # and the same stream never re-proposes the mode it is already in
    assert all(clf.note_write(i * PS, PS) is None for i in range(8))


def test_classifier_overwrites_propose_page():
    clf = StreamClassifier(make_policy())
    # half-page writes, all to the same page: small avg but pure rewrite
    got = [clf.note_write(0, PS // 2) for _ in range(8)]
    assert got[7] == "page"


def test_classifier_flip_flop_never_switches():
    clf = StreamClassifier(make_policy())
    switched = []
    for rnd in range(8):                     # alternate window votes
        size = PS if rnd % 2 == 0 else 16
        off = 0 if rnd % 2 == 0 else (100 + rnd) * PS
        for i in range(4):
            r = clf.note_write(off + i, size)
            if r is not None:
                switched.append(r)
    assert switched == [] and clf.mode == "log"


def test_classifier_page_mode_back_to_log():
    clf = StreamClassifier(make_policy())
    for i in range(8):
        r = clf.note_write(i * PS, PS)
    clf.confirm("page")
    got = [clf.note_write((1000 + i) * PS, 8) for i in range(8)]
    assert got[7] == "log"


# -------------------------------------------------------- engine semantics
def test_paged_write_read_flush_roundtrip():
    pol = make_policy()
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/f")
    blob = bytes(range(256))
    for rnd in range(12):                    # overwrite-heavy: 4 hot pages
        for p in range(4):
            nv.pwrite(fd, blob, p * PS)
    f = the_file(nv)
    assert f.pmode and set(f.frames) == {0, 1, 2, 3}
    st = nv.stats()
    assert st["mode_migrations"] == 1
    assert st["paged_frames_used"] == 4
    assert st["paged_frame_writes"] > 12     # overwrites landed in frames
    # reads serve framed pages from NVMM — fresh, replay-free, no scans
    assert nv.pread(fd, PS, 0) == blob
    assert nv.pread(fd, PS, 3 * PS) == blob
    nv.flush()                               # paged half of the barrier
    assert tier.open("/f").pread(PS, 2 * PS) == blob
    nv.close(fd)
    nv.shutdown()


def test_paged_mode_persists_fewer_bytes_per_committed_byte():
    """Whole-page rewrites at eager drain settings (``batch_min=1``): the
    log persists each overwrite twice (its NVMM entry, then the drain's
    backend write), a frame once in place, with one backend image at
    writeback.  Counting NVMM plus backend bytes, paged mode persists at
    least 1.5x fewer bytes per committed byte.  ``batch_max`` is half a
    pass, so no batch can coalesce one pass's overwrite with the next."""
    page, n_pages, passes = 4096, 32, 8
    persisted = {}
    for mode in ("log", "paged"):
        pol = Policy(entry_size=page, log_entries=256, page_size=page,
                     batch_min=1, batch_max=n_pages // 2,
                     page_frames=2 * n_pages if mode == "paged" else 0,
                     classify_window=8)
        tier = Tier(DRAM)
        nv = NVCache(pol, tier)
        fd = nv.open("/hot.dat")
        for p in range(n_pages):            # warm-up pass: the classifier
            nv.pwrite(fd, b"\x00" * page, p * page)
        nv.flush()                          # flips, its entries drain
        tf = tier.open("/hot.dat")
        nvmm0, backend0 = nv.nvmm.stats_stored_bytes, tf.stats_bytes
        for rnd in range(passes):
            for p in range(n_pages):
                nv.pwrite(fd, bytes([rnd + 1]) * page, p * page)
        nv.flush()
        assert nv.stats()["mode_migrations"] == (mode == "paged")
        assert tf.snapshot() == bytes([passes]) * (page * n_pages)
        persisted[mode] = (nv.nvmm.stats_stored_bytes - nvmm0
                           + tf.stats_bytes - backend0)
        nv.shutdown()
    committed = passes * n_pages * page
    assert persisted["log"] >= 2 * committed, persisted
    assert 1.5 * persisted["paged"] <= persisted["log"], persisted


def test_paged_mode_appends_nothing_to_the_log():
    pol = make_policy(batch_min=10 ** 6, batch_max=10 ** 6)  # no drain
    nv = NVCache(pol, Tier(DRAM))
    fd = nv.open("/f")
    for _ in range(8):                       # classifier flips to page mode
        nv.pwrite(fd, b"x" * PS, 0)
    assert the_file(nv).pmode
    used = nv.log.used_entries
    for _ in range(30):                      # framed overwrites: in place
        nv.pwrite(fd, b"y" * PS, 0)
    assert nv.log.used_entries == used
    assert nv.pread(fd, PS, 0) == b"y" * PS
    nv.cleanup.power_loss()                  # tear down without draining


def test_pool_exhaustion_falls_back_to_log_per_page():
    pol = make_policy(page_frames=2, batch_min=10 ** 6, batch_max=10 ** 6)
    nv = NVCache(pol, Tier(DRAM))
    fd = nv.open("/f")
    for rnd in range(4):                     # flip to page mode on 2 pages
        for p in range(2):
            nv.pwrite(fd, b"a" * PS, p * PS)
    for p in range(2):
        nv.pwrite(fd, b"b" * PS, p * PS)
    f = the_file(nv)
    assert f.pmode and len(f.frames) == 2    # pool is now full
    used = nv.log.used_entries
    nv.pwrite(fd, b"c" * PS, 5 * PS)         # no frame left: log fallback
    assert nv.log.used_entries > used
    assert 5 not in f.frames
    assert nv.stats()["paged_alloc_fallbacks"] >= 1
    assert nv.pread(fd, PS, 5 * PS) == b"c" * PS
    assert nv.pread(fd, PS, 0) == b"b" * PS
    nv.cleanup.power_loss()


def test_truncate_drops_and_clips_frames():
    pol = make_policy()
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/f")
    for rnd in range(4):
        for p in range(3):
            nv.pwrite(fd, bytes([rnd + p]) * PS, p * PS)
    f = the_file(nv)
    assert f.pmode and set(f.frames) == {0, 1, 2}
    nv.ftruncate(fd, PS + 100)               # cuts page 2, clips page 1
    assert set(f.frames) == {0, 1}
    assert nv.stat_size(fd) == PS + 100
    assert nv.pread(fd, PS, PS) == bytes([4]) * 100  # tail gone
    nv.ftruncate(fd, 0)                      # WAL reset drops everything
    assert f.frames == {}
    assert nv.stat_size(fd) == 0
    nv.close(fd)
    nv.shutdown()
    assert tier.open("/f").size() == 0


def test_unlinked_file_frames_die_without_writeback():
    pol = make_policy()
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/j")
    for _ in range(8):
        nv.pwrite(fd, b"J" * PS, 0)
    f = the_file(nv)
    assert f.pmode and f.frames
    tf = tier.open("/j")
    before = tf.stats_bytes
    nv.unlink("/j")
    nv.close(fd)                             # last close reaps the file
    nv.flush()
    assert tf.stats_bytes == before          # no frame writeback
    assert not tier.exists("/j")
    assert nv.stats()["paged_frames_used"] == 0   # pool reclaimed
    nv.shutdown()


def test_mode_migration_page_to_log_writes_back():
    pol = make_policy(batch_min=10 ** 6, batch_max=10 ** 6)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/f")
    for _ in range(8):
        nv.pwrite(fd, b"P" * PS, 0)
    f = the_file(nv)
    assert f.pmode
    assert nv._migrate_mode(f, False)        # explicit page -> log
    assert not f.pmode and f.frames == {}
    assert tier.open("/f").pread(PS, 0) == b"P" * PS  # frame reached backend
    nv.pwrite(fd, b"L" * PS, 0)              # back to log appends
    assert nv.log.used_entries > 0
    assert nv.pread(fd, PS, 0) == b"L" * PS
    nv.cleanup.power_loss()


# ------------------------------------------------------- crash consistency
def _mode_script(nv):
    """log writes -> migrate to paged -> framed overwrites -> migrate back
    -> log write; every op is individually atomic and synchronously
    durable, so a crash may sit between any two."""
    fd = nv.open("/f")
    nv.pwrite(fd, b"A" * PS, 0)
    nv.pwrite(fd, b"a" * PS, PS)
    f = the_file(nv)
    assert nv._migrate_mode(f, True)
    nv.pwrite(fd, b"B" * PS, 0)              # framed
    nv.pwrite(fd, b"C" * PS, 0)              # framed overwrite (slot flip)
    nv.pwrite(fd, b"b" * PS, PS)             # framed
    assert nv._migrate_mode(f, False)        # writeback + free
    nv.pwrite(fd, b"D" * PS, 0)              # log again


def _mode_script_states():
    A, a = b"A" * PS, b"a" * PS
    return [
        {"/f": b""},
        {"/f": A},
        {"/f": A + a},
        {"/f": b"B" * PS + a},
        {"/f": b"C" * PS + a},
        {"/f": b"C" * PS + b"b" * PS},
        {"/f": b"D" * PS + b"b" * PS},
    ]


def _legal(observed, states):
    return any(observed == s for s in states)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_mode_migration_crash_sweep_old_or_new(k):
    """Crash at every 3rd NVMM persistence op across the full
    log→page→log script, K ∈ {1, 2, 4}: recovery must land a committed
    prefix state — no torn frames, no lost committed writes, across both
    modes and the migrations between them."""
    pol = make_policy(shards=k, log_entries=128 * k,
                      batch_min=10 ** 6, batch_max=10 ** 6)
    dry = ThreadFusedNVMM(pol.nvmm_bytes)
    nv = NVCache(pol, Tier(DRAM), nvmm=dry, recover=False)
    dry.ops = 0
    _mode_script(nv)
    total = dry.ops
    nv.cleanup.power_loss()
    states = [{}] + _mode_script_states()

    checked = 0
    for fuse in range(0, total + 1, 3):
        nvmm = ThreadFusedNVMM(pol.nvmm_bytes, track=True)
        tier = Tier(DRAM)
        nv = NVCache(pol, tier, nvmm=nvmm, recover=False, track_crashes=True)
        nvmm.arm(fuse)
        done = False
        try:
            _mode_script(nv)
            done = True
        except PowerLoss:
            pass
        nvmm._fuse = None
        nv._crashed = True
        nv.cleanup.power_loss()
        nvmm.crash()                         # nothing un-flushed survives
        tier2 = clone_tier(tier)
        stats = recover(nvmm, pol, tier2)
        observed = {p: tier2.open(p).snapshot() for p in tier2.paths()}
        assert _legal(observed, states), \
            f"k={k} fuse={fuse}: torn state {observed!r} ({stats})"
        if done:
            assert _legal(observed, [states[-1]]), \
                f"k={k} fuse={fuse}: completed script lost writes"
        checked += 1
    assert checked > 20


def test_paged_overwrite_crash_sweep_dense():
    """Every single fuse point across framed overwrites of one page: the
    header flip is the commit — the page is always one of the committed
    images, never a mix."""
    pol = make_policy(batch_min=10 ** 6, batch_max=10 ** 6)

    def script(nv):
        fd = nv.open("/p")
        f = the_file(nv)
        nv.pwrite(fd, b"0" * PS, 0)
        assert nv._migrate_mode(f, True)
        for ch in b"123":
            nv.pwrite(fd, bytes([ch]) * PS, 0)

    dry = ThreadFusedNVMM(pol.nvmm_bytes)
    nv = NVCache(pol, Tier(DRAM), nvmm=dry, recover=False)
    dry.ops = 0
    script(nv)
    total = dry.ops
    nv.cleanup.power_loss()
    legal = [{}, {"/p": b""}] + [{"/p": bytes([c]) * PS} for c in b"0123"]

    for fuse in range(total + 1):
        nvmm = ThreadFusedNVMM(pol.nvmm_bytes, track=True)
        tier = Tier(DRAM)
        nv = NVCache(pol, tier, nvmm=nvmm, recover=False, track_crashes=True)
        nvmm.arm(fuse)
        try:
            script(nv)
        except PowerLoss:
            pass
        nvmm._fuse = None
        nv._crashed = True
        nv.cleanup.power_loss()
        nvmm.crash()
        tier2 = clone_tier(tier)
        stats = recover(nvmm, pol, tier2)
        observed = {p: tier2.open(p).snapshot() for p in tier2.paths()}
        assert _legal(observed, legal), \
            f"fuse={fuse}: torn frame {observed!r} ({stats})"


# ------------------------------------------- satellite: fsync-free WAL reset
def test_ftruncate_zero_drains_without_backend_fsync():
    pol = make_policy(page_frames=0, batch_min=10 ** 6, batch_max=10 ** 6)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/wal")
    for i in range(6):
        nv.pwrite(fd, bytes([i]) * 200, i * 200)
    tf = tier.open("/wal")
    fsyncs = tf.stats_fsyncs
    nv.ftruncate(fd, 0)                      # barrier drains all 6 entries
    assert the_file(nv).pending.get() == 0   # ...but the discarded bytes
    assert tf.stats_fsyncs == fsyncs         # never paid a device fsync
    assert not the_file(nv).skip_drain_fsync  # window closed
    assert nv.stat_size(fd) == 0
    # a normal shrink (length > 0) still fsyncs its surviving bytes
    nv.pwrite(fd, b"k" * 300, 0)
    nv.ftruncate(fd, 100)
    assert tf.stats_fsyncs > fsyncs
    assert nv.pread(fd, 300, 0) == b"k" * 100
    nv.close(fd)
    nv.shutdown()


def test_ftruncate_zero_crash_sweep_old_or_new():
    pol = make_policy(page_frames=0, batch_min=10 ** 6, batch_max=10 ** 6)

    def script(nv):
        fd = nv.open("/w")
        nv.pwrite(fd, b"W" * 300, 0)
        nv.ftruncate(fd, 0)
        nv.pwrite(fd, b"X" * 100, 0)

    dry = ThreadFusedNVMM(pol.nvmm_bytes)
    nv = NVCache(pol, Tier(DRAM), nvmm=dry, recover=False)
    dry.ops = 0
    script(nv)
    total = dry.ops
    nv.cleanup.power_loss()
    legal = [{}, {"/w": b""}, {"/w": b"W" * 300}, {"/w": b""},
             {"/w": b"X" * 100}]
    for fuse in range(0, total + 1, 3):
        nvmm = ThreadFusedNVMM(pol.nvmm_bytes, track=True)
        tier = Tier(DRAM)
        nv = NVCache(pol, tier, nvmm=nvmm, recover=False, track_crashes=True)
        nvmm.arm(fuse)
        try:
            script(nv)
        except PowerLoss:
            pass
        nvmm._fuse = None
        nv._crashed = True
        nv.cleanup.power_loss()
        nvmm.crash()
        tier2 = clone_tier(tier)
        stats = recover(nvmm, pol, tier2)
        observed = {p: tier2.open(p).snapshot() for p in tier2.paths()}
        assert _legal(observed, legal), \
            f"fuse={fuse}: torn WAL reset {observed!r} ({stats})"


# --------------------------------------- satellite: deferred rename apply
def test_rename_apply_is_queued_and_runs_before_return():
    tier = Tier(DRAM)
    nv = NVCache(make_policy(), tier)
    fd = nv.open("/a")
    nv.pwrite(fd, b"payload", 0)
    nv.close(fd)
    nv.rename("/a", "/b")
    # the apply went through the deferred queue, not synchronously under
    # the namespace lock — but it IS done by the time rename returns
    assert nv.ns.stats_deferred_applies >= 1
    assert tier.exists("/b") and not tier.exists("/a")
    fd = nv.open("/b", os.O_RDONLY)
    assert nv.pread(fd, 16, 0) == b"payload"
    nv.close(fd)
    nv.shutdown()


def test_drain_applies_deferred_record_when_caller_does_not():
    """The drain's meta-apply path: a queued apply whose originating
    thread never ran it must not wedge the drain — the drain thread runs
    the queue itself before consuming the record."""
    pol = make_policy(page_frames=0)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/a")
    nv.pwrite(fd, b"data", 0)
    nv.close(fd)
    applied = []
    with nv._meta:
        marks, mseq = nv.ns.journal_locked(MOP_RENAME, META_NO_FDID, 0, "/a", "/b")
        nv.ns.queue_apply(
            mseq, lambda: (tier.rename("/a", "/b"), applied.append(1)), marks)
    # note: apply_deferred() deliberately NOT called here
    nv.flush()      # flush waits for the record to be consumed — which
    #                 requires a drain thread to have applied it first
    assert applied == [1]
    assert tier.exists("/b") and not tier.exists("/a")
    assert not nv.ns.has_unapplied()
    nv.shutdown()


# ------------------------------------------------------ recovery stats
def test_recovery_reports_frames():
    pol = make_policy(batch_min=10 ** 6, batch_max=10 ** 6)
    nvmm = ThreadFusedNVMM(pol.nvmm_bytes, track=True)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier, nvmm=nvmm, recover=False, track_crashes=True)
    fd = nv.open("/f")
    for _ in range(8):
        nv.pwrite(fd, b"F" * PS, 0)
    assert the_file(nv).pmode
    nv.crash()
    tier2 = clone_tier(tier)
    stats = recover(nvmm, pol, tier2)
    assert stats.frames_seen == 1 and stats.frames_replayed == 1
    assert stats.frames_dropped == 0
    assert tier2.open("/f").snapshot() == b"F" * PS
