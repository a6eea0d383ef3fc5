"""Batch-spanning drain coalescing (PR 3 tentpole, write side).

A drain batch may leave its contiguous tail extent (the still-filling tail
page) unconsumed so the next batch's contiguous entries merge into one
backend write.  Deferred entries keep every invariant: they stay committed
in the log (recovery replays them), their dirty-page-index refs stay live
(reads replay them), and nothing is consumed/retired before its bytes and
fsync land — the carry is just "not drained yet", bounded by
``Policy.coalesce_deadline_ms`` and closed by any drain barrier.

Most tests drive a detached (unstarted) CleanupThread so batch boundaries
are exact; the pool's own threads stay idle under a huge ``batch_min``.
"""
import time

import pytest

from repro.core import NVCache, Policy, recover
from repro.core.cleanup import CleanupThread
from repro.core.drain import choose_deferred_suffix
from repro.storage.tiers import DRAM, Tier

PS = 1024


def make_nv(**kw):
    defaults = dict(entry_size=256, log_entries=256, page_size=PS,
                    read_cache_pages=16, batch_min=10 ** 6, batch_max=10 ** 6,
                    coalesce_deadline_ms=10_000.0)   # nothing flushes by time
    defaults.update(kw)
    pol = Policy(**defaults)
    tier = Tier(DRAM)
    tier.open("/f")
    tier.open("/g")    # pre-exist the test paths: open() then journals no
    #                    create record, keeping the hand-stepped batch
    #                    arithmetic below exactly as authored
    nv = NVCache(pol, tier, track_crashes=True)
    # the detached drain thread below is stepped by hand; stop the pool's
    # own threads so batch boundaries are exactly the test's step() calls
    for th in nv.cleanup.threads:
        th.hard_stop.set()
        th.stop_event.set()
        th.shard.notify_committed()
    for th in nv.cleanup.threads:
        th.join(timeout=10)
    t = CleanupThread(nv.log, nv.log.shards[0], nv._resolve_fdid)
    return nv, tier, t


def step(nv, t):
    """One manual drain batch over everything committed in shard 0."""
    sh = nv.log.shards[0]
    run = sh.committed_run(sh.persistent_tail, nv.policy.batch_max)
    if run:
        t._consume_batch(run)
    return run


ED = 256 - 48   # entry_data


def test_tail_extent_is_carried_not_consumed():
    nv, tier, t = make_nv()
    fd = nv.open("/f")
    f = nv._files["/f"]
    nv.pwrite(fd, b"\x01" * ED, 0)           # entries 0..: page 0, open
    nv.pwrite(fd, b"\x02" * ED, ED)
    step(nv, t)
    # the whole batch fits the open tail page: carried, nothing written
    assert t._span_deferred == 2
    assert tier.open("/f").stats_writes == 0
    assert nv.log.used_entries == 2, "carried entries were consumed"
    assert f.pending.get() == 2, "pending retired before the deferred flush"
    assert f.radix.get(0).dirty_refs == 2, \
        "refs retired before the deferred flush"
    # reads replay the carried entries from the index (not the backend)
    assert nv.pread(fd, 2 * ED, 0) == b"\x01" * ED + b"\x02" * ED
    # a write entering the next page closes the carried extent: one merged
    # backend write covers both batches' page-0 bytes
    nv.pwrite(fd, b"\x03" * (PS - 2 * ED), 2 * ED)   # completes page 0
    nv.pwrite(fd, b"\x04" * 64, PS)                   # opens page 1
    step(nv, t)
    tf = tier.open("/f")
    assert tf.stats_writes == 1 and tf.stats_page_writes == 1
    assert t.stats_span_merges == 1
    assert t._span_deferred == 1                      # page-1 entry carried
    assert f.radix.get(0).dirty_refs == 0
    assert f.radix.get(1).dirty_refs == 1
    snap = tf.snapshot()
    assert snap[:PS] == b"\x01" * ED + b"\x02" * ED + b"\x03" * (PS - 2 * ED)
    nv.shutdown()


def test_deadline_closes_the_carried_extent():
    nv, tier, t = make_nv(coalesce_deadline_ms=10.0)
    fd = nv.open("/f")
    nv.pwrite(fd, b"\x05" * 100, 0)
    step(nv, t)
    assert t._span_deferred == 1
    assert tier.open("/f").stats_writes == 0
    time.sleep(0.02)                          # older than the deadline
    # the drain loop would wake on deadline_at; step the batch by hand
    step(nv, t)
    assert t._span_deferred == 0
    assert tier.open("/f").stats_writes == 1
    assert nv.log.used_entries == 0
    nv.shutdown()


def test_drain_barrier_flushes_the_carry():
    """close/flush/fsync set drain_event: the carried extent must be
    flushed — a drain barrier means 'durably on the slow tier', not
    'parked in the log'."""
    nv, tier, t = make_nv()
    fd = nv.open("/f")
    nv.pwrite(fd, b"\x06" * 200, 0)
    step(nv, t)
    assert t._span_deferred == 1
    t.drain_event.set()
    step(nv, t)
    assert t._span_deferred == 0
    assert tier.open("/f").snapshot()[:200] == b"\x06" * 200
    assert nv.log.used_entries == 0
    nv.shutdown()


def test_noncontiguous_next_batch_flushes_and_recarries():
    nv, tier, t = make_nv()
    fd = nv.open("/f")
    nv.pwrite(fd, b"\x07" * 100, 0)
    step(nv, t)
    assert t._span_deferred == 1
    nv.pwrite(fd, b"\x08" * 100, 5 * PS)      # far away: new open extent
    step(nv, t)
    # the old carry was written; the new tail entry is carried instead
    assert t._span_deferred == 1
    tf = tier.open("/f")
    assert tf.snapshot()[:100] == b"\x07" * 100
    assert len(tf.snapshot()) <= 5 * PS       # the new tail is NOT written yet
    nv.shutdown()


def test_carried_entries_survive_power_loss():
    """Deferred != lost: carried entries are still committed in the log, so
    recovery replays them."""
    nv, tier, t = make_nv()
    fd = nv.open("/f")
    nv.pwrite(fd, b"\x09" * 300, 0)
    step(nv, t)
    assert t._span_deferred >= 1
    assert tier.open("/f").stats_writes == 0  # nothing on the slow tier yet
    nvmm = nv.crash()
    tier2 = Tier(DRAM)
    recover(nvmm, nv.policy, tier2.open)
    assert tier2.open("/f").snapshot()[:300] == b"\x09" * 300
    nv.shutdown() if not nv._crashed else None


def test_choose_deferred_suffix_rules():
    nv, tier, t = make_nv()
    fd = nv.open("/f")
    sh = nv.log.shards[0]
    pol = nv.policy
    # one entry, inside one page -> carried
    nv.pwrite(fd, b"a" * 100, 0)
    assert choose_deferred_suffix(sh, sh.persistent_tail, 1, pol) == 1
    # second entry contiguous, still inside page 0 -> both carried
    nv.pwrite(fd, b"b" * 100, 100)
    assert choose_deferred_suffix(sh, sh.persistent_tail, 2, pol) == 2
    # an entry crossing into page 1 cuts the carry at the crossing group
    nv.pwrite(fd, b"c" * (PS - 100), 200)     # multi-entry group, crosses
    run = sh.committed_run(sh.persistent_tail, pol.batch_max)
    assert choose_deferred_suffix(sh, sh.persistent_tail, run, pol) == 0
    # a fresh entry cleanly inside page 1 is carried again
    nv.pwrite(fd, b"d" * 50, PS + 100)
    run = sh.committed_run(sh.persistent_tail, pol.batch_max)
    assert choose_deferred_suffix(sh, sh.persistent_tail, run, pol) == 1
    # a different file's entry breaks the suffix walk
    fd2 = nv.open("/g")
    nv.pwrite(fd2, b"e" * 50, PS + 150)       # contiguous bytes, other file
    run = sh.committed_run(sh.persistent_tail, pol.batch_max)
    assert choose_deferred_suffix(sh, sh.persistent_tail, run, pol) == 1
    nv.shutdown()


def test_space_pressure_disables_the_carry():
    nv, tier, t = make_nv(log_entries=8)      # tiny shard
    fd = nv.open("/f")
    nv.pwrite(fd, b"\x0B" * 800, 0)           # 4 entries: shard half full
    step(nv, t)
    assert t._span_deferred == 0, "carried while writers may be blocked"
    assert nv.log.used_entries == 0
    nv.shutdown()


@pytest.mark.parametrize("k", [1, 2])
def test_trickle_workload_end_to_end(k):
    """Real pool threads, trickling contiguous 256-byte writes: with the
    carry each backend page is written about once, where draining each
    tiny batch on its own would rewrite the tail page per batch."""
    writes, bs = 24, 256
    pol = Policy(entry_size=bs + 48, log_entries=256 * k, page_size=PS,
                 read_cache_pages=16, batch_min=1, batch_max=64,
                 shards=k, shard_route="fdid", coalesce_deadline_ms=500.0)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/t")
    for i in range(writes):
        nv.pwrite(fd, bytes([i + 1]) * bs, i * bs)
        time.sleep(0.003)                     # drain sees tiny batches
    nv.flush()
    tf = tier.open("/t")
    assert tf.snapshot()[:writes * bs] == b"".join(
        bytes([i + 1]) * bs for i in range(writes))
    assert nv.log.used_entries == 0
    assert nv.stats()["drain_deferred"] > 0
    nv.shutdown()
    pages = writes * bs // PS
    assert tf.stats_page_writes <= pages + 3, tf.stats_page_writes
