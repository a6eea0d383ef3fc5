"""Crash injection over the plan/apply drain engine (PR 2).

Every application write in these tests *returned* before the crash, so it
is synchronously durable in the NVMM log.  A power loss at ANY plan/apply
checkpoint — mid-plan, between extent writes, after extents but before the
index retire, before the fsync, before the log consume — must therefore be
fully repaired by recovery: the slow tier ends up exactly equal to the
in-order application of all writes.  Torn extents or reordered batches
would surface as a byte mismatch.

The fuse counts drain-engine checkpoints (the ``fault_hook`` of
:class:`~repro.core.cleanup.CleanupThread`) across all K shards and flips
``hard_stop`` — the same switch real power loss uses — at an arbitrary one.
"""
import random
import threading

import pytest

from repro.core import NVCache, Policy, recover
from repro.core import drain as drain_mod
from repro.storage.tiers import DRAM, Tier


def make_policy(k: int, route: str = "stripe") -> Policy:
    # log big enough that writers never need a (possibly fused-dead) drain
    # thread to recycle entries: every write in these tests must return
    return Policy(entry_size=256, log_entries=256 * k, page_size=256,
                  read_cache_pages=4, batch_min=2, batch_max=8,
                  shards=k, shard_route=route, stripe_pages=2)


def apply_ops(ops):
    img = bytearray()
    for off, data in ops:
        if off + len(data) > len(img):
            img.extend(b"\x00" * (off + len(data) - len(img)))
        img[off:off + len(data)] = data
    return bytes(img)


class Fuse:
    """Counts drain checkpoints across every shard thread; at the armed
    count, simulates power loss by hard-stopping the whole pool."""

    def __init__(self, nv, at: int):
        self.nv = nv
        self.at = at
        self.count = 0
        self.tags = []
        self._lock = threading.Lock()

    def __call__(self, tag: str) -> None:
        with self._lock:
            self.count += 1
            self.tags.append(tag)
            fire = self.count == self.at
        if fire:
            for t in self.nv.cleanup.threads:
                t.hard_stop.set()
                t.stop_event.set()


@pytest.mark.parametrize("k", [1, 2, 4])
def test_power_loss_at_any_plan_apply_point_loses_nothing(k):
    seen_tags = set()
    for trial in range(25):
        rng = random.Random(5000 * k + trial)
        pol = make_policy(k, "stripe" if trial % 2 else "fdid")
        tier = Tier(DRAM)
        nv = NVCache(pol, tier, track_crashes=True)
        fuse = Fuse(nv, at=rng.randrange(1, 120))
        for t in nv.cleanup.threads:
            t.fault_hook = fuse
        fd = nv.open("/f")
        ops = []
        for _ in range(rng.randint(10, 25)):
            off = rng.randrange(0, 1200)
            data = bytes(rng.randrange(1, 256)
                         for _ in range(rng.randint(1, 500)))
            nv.pwrite(fd, data, off)          # returns => durable
            ops.append((off, data))
        # poke the drain so the fuse has work to bite on, then crash
        nv.cleanup.request_drain()
        for t in nv.cleanup.threads:
            t.join(timeout=0.05)
        nvmm = nv.crash()                     # drop every un-flushed line
        seen_tags.update(fuse.tags)
        # surviving slow-tier bytes + NVMM replay must equal ALL the writes
        tier2 = Tier(DRAM)
        for path in tier.paths():
            snap = tier.open(path).snapshot()
            if snap:
                tier2.open(path).pwrite(snap, 0)
        stats = recover(nvmm, pol, tier2.open)
        assert stats.crc_failures == 0
        got = tier2.open("/f").snapshot()
        exp = apply_ops(ops)
        assert got[:len(exp)] == exp, \
            f"k={k} trial={trial} fuse@{fuse.at}: torn/reordered/lost bytes"
        assert all(b == 0 for b in got[len(exp):])
    # the fuse must actually have exercised both phases across the trials
    assert drain_mod.PLAN_ENTRY in seen_tags
    assert {drain_mod.APPLY_EXTENT, drain_mod.APPLY_RETIRE} & seen_tags
    assert {drain_mod.FSYNC, drain_mod.CONSUME} & seen_tags


TAGS = [drain_mod.PLAN_ENTRY, drain_mod.APPLY_FILE, drain_mod.APPLY_EXTENT,
        drain_mod.APPLY_RETIRE, drain_mod.FSYNC, drain_mod.CONSUME]


@pytest.mark.parametrize("tag,stream",
                         [pytest.param(tag, "random", id=tag) for tag in TAGS]
                         + [pytest.param(tag, "append", id=f"{tag}-append")
                            for tag in TAGS])
def test_power_loss_pinned_at_each_checkpoint(tag, stream, monkeypatch):
    """Deterministic variant: die at the FIRST occurrence of one specific
    checkpoint, for every checkpoint the engine defines; ``append`` is a
    sequential stream, which the drain plans without page images."""
    pol = make_policy(2, "stripe")
    tier = Tier(DRAM)
    nv = NVCache(pol, tier, track_crashes=True)
    hit = threading.Event()
    direct = []
    build = drain_mod.build_plan

    def record(*a, **kw):
        plan = build(*a, **kw)
        if plan is not None:
            direct.extend(fp.direct for fp in plan.files)
        return plan

    monkeypatch.setattr(drain_mod, "build_plan", record)

    def hook(t):
        if t == tag:
            hit.set()
            for th in nv.cleanup.threads:
                th.hard_stop.set()
                th.stop_event.set()

    for t in nv.cleanup.threads:
        t.fault_hook = hook
    fd = nv.open("/f")
    ops = []
    rng = random.Random(42)
    off = 37
    for _ in range(12):
        n = rng.randint(1, 400)
        if stream == "random":
            off = rng.randrange(0, 900)
        data = bytes([rng.randrange(1, 256)]) * n
        nv.pwrite(fd, data, off)
        ops.append((off, data))
        if stream == "append":
            off += n
    nv.cleanup.request_drain()
    assert hit.wait(timeout=30), f"checkpoint {tag} never reached"
    nvmm = nv.crash()
    if stream == "append":                # planned, unless it died planning
        assert all(direct) and (direct or tag == drain_mod.PLAN_ENTRY)
    tier2 = Tier(DRAM)
    for path in tier.paths():
        snap = tier.open(path).snapshot()
        if snap:
            tier2.open(path).pwrite(snap, 0)
    recover(nvmm, pol, tier2.open)
    got = tier2.open("/f").snapshot()
    exp = apply_ops(ops)
    assert got[:len(exp)] == exp
    assert all(b == 0 for b in got[len(exp):])


def test_graceful_stop_is_not_a_crash():
    """stop_event (shutdown) finishes the in-flight batch; only hard_stop
    abandons it — flush-then-shutdown must drain everything."""
    pol = make_policy(2)
    tier = Tier(DRAM)
    nv = NVCache(pol, tier)
    fd = nv.open("/f")
    for i in range(20):
        nv.pwrite(fd, bytes([i + 1]) * 100, i * 60)
    nv.flush()
    assert nv.log.used_entries == 0
    nv.shutdown()
    exp = apply_ops([(i * 60, bytes([i + 1]) * 100) for i in range(20)])
    assert tier.open("/f").snapshot()[:len(exp)] == exp
