"""End-to-end behaviour: train -> crash -> recover -> resume, and the
paper's Table-I property matrix on our stacks."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke
from repro.core import NVCache, Policy, recover
from repro.data.pipeline import SyntheticTokens
from repro.models.registry import build
from repro.optim.adamw import AdamW
from repro.storage.fsapi import NVCacheFS, TierFS
from repro.storage.tiers import DRAM, Tier
from repro.train import loop as train_loop

POL = Policy(entry_size=16384, log_entries=8192, page_size=4096,
             read_cache_pages=64, batch_min=8, batch_max=512, verify_crc=False)


def _setup(tier=None):
    tier = tier or Tier(DRAM)
    nv = NVCache(POL, tier)
    cfg = get_smoke("llama3.2-1b")
    model = build(cfg)
    opt = AdamW(lr=1e-3)
    pipe = SyntheticTokens(cfg.vocab, batch=2, seq=32, seed=9)
    return tier, nv, model, opt, pipe


def test_train_loss_decreases():
    tier, nv, model, opt, pipe = _setup()
    _state, hist = train_loop.train(model, opt, pipe, NVCacheFS(nv),
                                    total_steps=30, ckpt_every=10)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first, f"loss did not decrease: {first} -> {last}"
    nv.shutdown()


def test_crash_restart_resumes_exactly():
    """Run 17 steps (ckpt@10), 'crash', recover the NVMM log, restart: the
    loop resumes from step 10 with identical data batches, and finishes."""
    tier = Tier(DRAM)
    nv = NVCache(POL, tier, track_crashes=True)
    cfg = get_smoke("llama3.2-1b")
    model = build(cfg)
    opt = AdamW(lr=1e-3)
    pipe = SyntheticTokens(cfg.vocab, batch=2, seq=32, seed=9)
    _, hist1 = train_loop.train(model, opt, pipe, NVCacheFS(nv),
                                total_steps=17, ckpt_every=10)
    # power loss right after the step-17 checkpoint: its bytes are durable
    # ONLY in the NVMM log (cleanup may not have drained) — recovery must
    # replay them into the slow tier for the restart to see step 17.
    nvmm = nv.crash()
    recover(nvmm, POL, tier.open)          # the paper's recovery procedure

    nv2 = NVCache(POL, tier)
    pipe2 = SyntheticTokens(cfg.vocab, batch=2, seq=32, seed=9)
    state2, hist2 = train_loop.train(model, opt, pipe2, NVCacheFS(nv2),
                                     total_steps=20, ckpt_every=10)
    # restarted at step 17 => 3 more steps run, data pipeline in lockstep
    assert len(hist2) == 3
    assert pipe2.step == 20
    nv2.shutdown()


def test_mesh_path_trains_like_single_device():
    """The sharded path (here a 1x1 mesh) sees the same batches from step 0
    and computes the same losses as the plain jit."""
    from repro.launch.mesh import make_single_mesh
    losses = []
    for mesh in (None, make_single_mesh()):
        _tier, nv, model, opt, pipe = _setup()
        _, hist = train_loop.train(model, opt, pipe, NVCacheFS(nv),
                                   total_steps=3, ckpt_every=3, mesh=mesh)
        losses.append([h["loss"] for h in hist])
        assert pipe.step == 3
        nv.shutdown()
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)


def test_table1_property_matrix():
    """Paper Table I, as executable assertions."""
    # NVCache: synchronous durability (write durable before return) and
    # durable linearizability (visible => durable)
    tier = Tier(DRAM)
    nv = NVCache(POL, tier, track_crashes=True)
    fd = nv.open("/t")
    nv.pwrite(fd, b"D" * 100, 0)
    nvmm = nv.crash()                      # adversarial: nothing evicted
    tier2 = Tier(DRAM)
    recover(nvmm, POL, tier2.open)
    assert tier2.open("/t").snapshot()[:100] == b"D" * 100   # durable

    # large storage space: data >> NVMM log flows through to the slow tier
    tier = Tier(DRAM)
    small = Policy(entry_size=256, log_entries=16, page_size=256,
                   read_cache_pages=4, batch_min=2, batch_max=8)
    nv = NVCache(small, tier)
    fd = nv.open("/big")
    blob = bytes(range(256)) * 64          # 16 KiB >> 4 KiB log
    nv.pwrite(fd, blob, 0)
    assert nv.pread(fd, len(blob), 0) == blob
    nv.flush()
    assert tier.open("/big").snapshot()[:len(blob)] == blob
    nv.shutdown()

    # tmpfs: no durability (volatile) — fsync buys nothing
    vol = Tier(DRAM, volatile=True)
    f = vol.open("/v")
    f.pwrite(b"x", 0)
    f.fsync()
    assert vol.volatile                    # documented: no durability

    # fsync is a no-op on NVCache (Table III)
    tier = Tier(DRAM)
    nv = NVCache(POL, tier)
    fd = nv.open("/noop")
    nv.write(fd, b"abc")
    before = nv.cleanup.stats_fsyncs
    nv.fsync(fd)
    assert nv.cleanup.stats_fsyncs == before
    nv.shutdown()
