"""Moonlight-16B-A3B (the DeepSeek-V3 block) against its plain reference,
``bench/reference/mla_moe.py`` (loaded by path: the benchmark's check and
these tests compare with one reference), at a smoke size on the CPU: one
dense and two expert layers, 16 routed experts of which 4 are held, top-4,
one shared expert."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.core import NVCache, Policy, recover
from repro.models import lm, moe
from repro.models.registry import build
from repro.optim.adamw import AdamW
from repro.storage.fsapi import NVCacheFS
from repro.storage.tiers import DRAM, Tier
from repro.train import loop as train_loop
from repro.train import steps as tsteps

ROOT = Path(__file__).resolve().parents[1]
CFG = get_smoke("moonlight-16b-a3b")
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0}
# the reference's names for the smoke sizes (a configuration file's "model")
M = {"hidden_size": 64, "num_attention_heads": 4, "vocab_size": 256,
     "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
     "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
     "moe_intermediate_size": 32, "n_shared_experts": 1, "n_routed_experts": 16,
     "num_experts_per_tok": 4, "experts_held": 4, "num_hidden_layers": 3,
     "first_k_dense_replace": 1, "scoring_func": "sigmoid", "norm_topk_prob": True,
     "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
     "rope_theta": 50000.0, "rms_norm_eps": 1e-5, "aux_loss_alpha": 1e-4,
     "bias_update_speed": 1e-3}
KEY = jax.random.PRNGKey(7)


def _ref():
    path = ROOT / "bench" / "reference" / "mla_moe.py"
    spec = importlib.util.spec_from_file_location("bench_ref_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _ref()


def _batches(n=3, b=2, s=64, seed=3):
    rng = np.random.default_rng(seed)
    z = rng.zipf(1.3, size=(n, b, s))
    return [(x % 254).astype(np.int32) + 1 for x in z]


def _rel(a, b, floor):
    return float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64))
                 / max(np.linalg.norm(np.asarray(b, np.float64)), floor))


def test_init_matches_reference():
    """Every leaf of the program's initialisation, read by the reference's
    names, equals the reference's: both start from the same point."""
    p = REF.program_leaves(lm.init_lm(CFG, KEY))
    q = REF.leaves(REF.init_params(M, KEY))
    assert set(p) == set(q)
    for k in q:
        np.testing.assert_array_equal(p[k], q[k], err_msg=k)


def _program_steps(cfg, batches):
    """The program's first gradient (by reference names), its losses, its
    change over the steps and its router biases, through make_train_step."""
    model = build(cfg)
    opt = AdamW(**OPT)
    state = tsteps.init_train_state(model, opt, KEY)
    p0 = REF.program_leaves(state["params"])
    batch = {"tokens": jnp.asarray(batches[0])}
    grad = REF.program_leaves(jax.grad(lambda p: model.loss(p, batch, state["buffers"])[0])(
        state["params"]))
    step = jax.jit(tsteps.make_train_step(model, opt))
    losses = []
    for b in batches:
        state, metrics = step(state, {"tokens": jnp.asarray(b)})
        losses.append(float(metrics["loss"]))
        assert int(metrics["moe_dropped"]) == 0
    p3 = REF.program_leaves(state["params"])
    change = {k: float(np.linalg.norm(p3[k].astype(np.float64) - p0[k])) for k in p0}
    return losses, grad, change, np.asarray(state["buffers"]["router_bias"])


# float32: the program computes what the reference computes in another
# order (blocked against whole attention, sorted rows against a dense sum),
# so only rounding separates them: losses 1e-7, gradients and changes 2e-6
# read.  bfloat16 products read losses 5e-4 apart and a leaf's gradient up
# to 12% apart element by element (rounding of small elements, and the
# choice of an expert flipped near a tie, which moves that expert's rows);
# the change over 3 steps, a norm, reads under 1%; a flipped choice moves
# a bias by 2 gamma (1 of 32 read).  Each limit sits 2-100x above its
# reading.
TOL = {"float32": {"loss": 1e-5, "grad": 1e-4, "change": 1e-4, "bias_flips": 0},
       "bfloat16": {"loss": 5e-3, "grad": 0.25, "change": 0.05, "bias_flips": 0.125}}


@pytest.mark.parametrize("dtype", sorted(TOL))
def test_program_matches_reference(dtype):
    """Loss, every leaf of the first gradient, every leaf's change over 3
    AdamW steps and the router biases after them, against the float32
    reference; compute in float32 under a tight tolerance and in bfloat16
    under a looser one."""
    tol = TOL[dtype]
    batches = _batches()
    cfg = dataclasses.replace(CFG, compute_dtype=dtype)
    losses, grad, change, bias = _program_steps(cfg, batches)
    want = REF.first_steps(M, OPT, KEY, batches)
    with jax.default_matmul_precision("highest"):
        _l, g_ref, _load = REF.make_loss_and_grad(M)(
            REF.init_params(M, KEY), jnp.zeros((2, 16)), jnp.asarray(batches[0]))
    g_ref = REF.leaves(g_ref)
    for a, b in zip(losses, want["losses"]):
        assert abs(a - b) / abs(b) < tol["loss"], (losses, want["losses"])
    med = float(np.median([np.linalg.norm(v) for v in g_ref.values()]))
    for k in g_ref:
        assert _rel(grad[k], g_ref[k], med) < tol["grad"], k
    cmed = float(np.median(list(want["change"].values())))
    for k in want["change"]:
        assert abs(change[k] - want["change"][k]) / max(want["change"][k], cmed) \
            < tol["change"], k
    assert np.all(np.abs(want["bias"]) <= 3 * M["bias_update_speed"] + 1e-9)
    flips = np.mean(np.abs(bias - want["bias"]) > 1e-9)
    assert flips <= tol["bias_flips"], (bias, want["bias"])


def _h(seed=1, b=2, s=32):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, CFG.d_model), jnp.float32)


def _full():
    """The uncut layer: all 16 experts held, float32."""
    return dataclasses.replace(CFG, experts_held=0, compute_dtype="float32")


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares (4 experts each, the shared expert counted once)
    add up to the uncut reference layer; each share's initialisation is
    the uncut layer's slice."""
    full = _full()
    p = moe.held_moe_init(full, KEY)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    h = _h()
    total = 0.0
    for r in range(4):
        cfg = dataclasses.replace(full, experts_held=4, expert_first=4 * r)
        mine = moe.held_moe_init(cfg, KEY)
        for w in ("wg", "wu", "wd"):
            np.testing.assert_array_equal(mine[w], p[w][4 * r:4 * r + 4])
        share = {**mine, "router": p["router"],
                 "shared": jax.tree.map(lambda x: x * (r == 0), p["shared"])}
        y, stats = moe.moe_dropless(cfg, share, h, bias)
        assert int(stats["rows"]) == int(jnp.sum(stats["load"][4 * r:4 * r + 4]))
        total = total + y
    m = dict(M, experts_held=16)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([REF.experts(m, REF.C.matmul("float32"), p, hb, bias)[0]
                          for hb in h])
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_one_expert_takes_every_token_and_nothing_drops():
    """A router whose bias sends every token to expert 0 (and three more
    held ones): the held experts get all T * k rows, nothing is dropped,
    and the output equals the reference's."""
    cfg = dataclasses.replace(CFG, compute_dtype="float32")
    p = moe.held_moe_init(cfg, KEY)
    bias = jnp.zeros((16,)).at[:4].set(jnp.array([100.0, 50.0, 50.0, 50.0]))
    h = _h(seed=4)
    y, stats = moe.moe_dropless(cfg, p, h, bias)
    T = h.shape[0] * h.shape[1]
    assert int(stats["load"][0]) == T
    assert int(stats["rows"]) == T * cfg.top_k
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([REF.experts(M, REF.C.matmul("float32"), p, hb, bias)[0]
                          for hb in h])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    counters = lm.moe_counters(cfg, {k: stats[k][None] for k in ("load", "rows", "dropped")})
    assert int(counters["moe_load_max"]) == T and int(counters["moe_dropped"]) == 0


class _Feed:
    """Batches as a pure function of the step, in the pipeline protocol."""

    def __init__(self, stop_at):
        self.step, self.stop_at = 0, stop_at
        self.batches = _batches(n=stop_at, s=32, seed=11)

    def next(self):
        if self.step >= self.stop_at:
            return None
        b = {"tokens": self.batches[self.step]}
        self.step += 1
        return b

    def save_state(self, fs):
        fd = fs.open("/feed")
        fs.pwrite(fd, str(self.step).encode().ljust(16), 0)
        fs.close(fd)

    def restore_state(self, fs):
        fd = fs.open("/feed")
        self.step = int(fs.pread(fd, 16, 0).decode())
        fs.close(fd)


def test_router_bias_survives_a_power_loss():
    """The bias buffer is saved with the checkpoint through NVCache, read
    back after a power loss and a recovery, and the resumed job's losses
    and biases equal the uninterrupted job's."""
    pol = Policy(entry_size=16384, log_entries=8192, page_size=4096,
                 read_cache_pages=64, batch_min=8, batch_max=512, verify_crc=False)
    model, opt = build(CFG), AdamW(lr=1e-3)
    nv = NVCache(pol, Tier(DRAM))
    whole, hist = train_loop.train(model, opt, _Feed(6), NVCacheFS(nv), total_steps=6,
                                   ckpt_every=100)
    nv.shutdown()
    tier = Tier(DRAM)
    nv = NVCache(pol, tier, track_crashes=True)
    _s, first = train_loop.train(model, opt, _Feed(3), NVCacheFS(nv), total_steps=3,
                                 ckpt_every=3)
    assert np.any(np.asarray(_s["buffers"]["router_bias"]) != 0)
    recover(nv.crash(), pol, tier.open)
    nv2 = NVCache(pol, tier)
    resumed, rest = train_loop.train(model, opt, _Feed(6), NVCacheFS(nv2),
                                      total_steps=6, ckpt_every=100)
    nv2.shutdown()
    assert [h["step"] for h in rest] == [3, 4, 5]
    assert [h["loss"] for h in first + rest] == [h["loss"] for h in hist]
    np.testing.assert_array_equal(resumed["buffers"]["router_bias"],
                                  whole["buffers"]["router_bias"])


def test_step_counters_ride_on_the_step_span():
    """After the step, the loop sets the dispatch counters on the
    ``train.step_us`` span; a dense model's span carries none."""
    calls = []

    class Span:
        def __init__(self, name, args):
            self.name = name

        def __enter__(self):
            return self

        def set(self, **args):
            calls.append((self.name, args))

        def __exit__(self, *exc):
            return False

    orig = train_loop.obs.span
    train_loop.obs.span = lambda name, **args: Span(name, args)
    try:
        nv = NVCache(Policy(entry_size=16384, log_entries=4096, page_size=4096,
                            read_cache_pages=16, batch_min=8, batch_max=512,
                            verify_crc=False), Tier(DRAM))
        _s, hist = train_loop.train(build(CFG), AdamW(), _Feed(2), NVCacheFS(nv),
                                    total_steps=2, ckpt_every=100)
        nv.shutdown()
    finally:
        train_loop.obs.span = orig
    steps = [a for n, a in calls if n == "train.step_us"]
    assert len(steps) == 2
    for a, h in zip(steps, hist):
        assert set(a) == {"moe_rows", "moe_load_max", "moe_dropped"}
        assert a["moe_dropped"] == 0 and a["moe_rows"] == h["moe_rows"] > 0
        # 2 expert layers, 64 tokens, top-4: rows and the busiest held expert
        assert a["moe_load_max"] <= 64 and a["moe_rows"] <= 2 * 64 * 4


def test_rows_past_the_groups_never_count(monkeypatch):
    """A grouped matmul may leave the rows past its groups undefined, on
    the way forward and back (the TPU's does): filled with NaN there, the
    layer's output and every gradient stay what they were."""
    cfg = dataclasses.replace(CFG, compute_dtype="float32")
    p = moe.held_moe_init(cfg, KEY)
    h = _h(seed=5)
    real = jax.lax.ragged_dot

    def poison(x, sizes):
        past = (jnp.arange(x.shape[0]) >= sizes.sum())[:, None]
        return jnp.where(past, jnp.nan, x)

    @jax.custom_vjp
    def undefined_past_groups(a, w, sizes):
        return poison(real(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return undefined_past_groups(a, w, sizes), (a, w, sizes)

    def bwd(res, g):
        a, w, sizes = res
        da, dw = jax.vjp(lambda a, w: real(a, w, sizes), a, w)[1](g)
        return poison(da, sizes), dw, None

    undefined_past_groups.defvjp(fwd, bwd)

    def loss(p, h):
        y, _ = moe.moe_dropless(cfg, p, h)
        return jnp.sum(y * y)

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, h)
    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_past_groups)
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, h)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_a_lost_row_is_counted_as_dropped(monkeypatch):
    """``moe_dropped`` counts from the outputs: a grouped matmul that loses
    the last row of its groups (a row routed to a held expert) shows as one
    dropped row, though the routing's counts are unchanged."""
    cfg = dataclasses.replace(CFG, compute_dtype="float32")
    p = moe.held_moe_init(cfg, KEY)
    h = _h(seed=6)
    _y, stats = moe.moe_dropless(cfg, p, h)
    assert int(stats["dropped"]) == 0
    real = jax.lax.ragged_dot

    def loses_last_row(a, w, sizes):
        out = real(a, w, sizes)
        return jnp.where((jnp.arange(a.shape[0]) == sizes.sum() - 1)[:, None], 0, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", loses_last_row)
    _y, lost = moe.moe_dropless(cfg, p, h)
    assert int(lost["rows"]) == int(stats["rows"]) > 0
    assert int(lost["dropped"]) == 1


def test_a_model_without_buffers_keeps_its_state_tree():
    """Only a model with a router bias carries ``buffers``: a dense model's
    train state, and so its checkpoint, holds params and opt alone."""
    opt = AdamW()
    dense = tsteps.abstract_train_state(build(get_smoke("minicpm3-4b")), opt)
    assert set(dense) == {"params", "opt"}
    assert set(tsteps.abstract_train_state(build(CFG), opt)) == {"params", "opt", "buffers"}
