"""What decides ``correct``: each number compared, beside its limit.

Read once the window has closed, in this order:

* durability, through a simulated power loss of the job's NVCache
  (``NVCache.crash``) and a recovery over the same region and tier:
  every metrics line the job wrote reads back equal to the loop's history;
  the manifest's newest step is the last checkpoint committed; rows drawn
  from the seed of every leaf of that checkpoint restore bitwise equal to
  the state the job held when it saved it;
* the job's own bookkeeping: the optimizer's step count in the state the
  loop returned;
* for a resumed job: the tree that ``CheckpointManager.restore`` returned
  inside ``train()`` in one more crash, recovery and resume after the
  window, bitwise against the state that was saved; the losses of every
  resume, the window's and that one, against those of the job that ran on
  from the save without a crash; the step the feed was restored to and the
  optimizer's step count after each resume;
* the model step, against the plain float32 reference of the configuration
  (``bench/reference/<name>.py``) over the same first three batches, once
  the program's state is freed: the worst relative gap of the three losses
  (``loss_gap``), and by the worst leaf, of the first gradient's norm as the
  optimizer got it (``grad_gap``) and of the parameters' change over the
  three steps (``change_gap``), each under the limit that the configuration
  file's ``limits`` sets from readings on the chip (``bench/calibrate.py``).

Exact comparisons have the limit 0.
"""
from __future__ import annotations

import json
import math

import numpy as np

SAMPLE_ROWS = 32          # the restore check reads 1/32 of each leaf's rows


def _ok(value, limit):
    return {"value": value, "limit": limit,
            "ok": value is not None and math.isfinite(value) and value <= limit}


def attempted(run) -> int:
    if run.kind == "resume":
        return len(run.cycles)
    return sum(1 for h in run.histories[0] if h["step"] >= run.warmup)


def failed(run) -> int:
    if run.kind == "resume":
        return sum(1 for c in run.cycles if c["first_step_end"] is None)
    return sum(1 for h in run.histories[0]
               if h["step"] >= run.warmup and not math.isfinite(h["loss"]))


def _recover(run):
    from repro.core import NVCache
    from repro.storage.fsapi import NVCacheFS
    region = run.fs.nv.crash()
    return NVCacheFS(NVCache(run.fs.nv.policy, run.fs.nv.tier, nvmm=region))


def _metrics_lines_lost(fs, histories) -> int:
    from bench.run import METRICS_LOG
    fd = fs.open(METRICS_LOG)
    raw = fs.pread(fd, fs.size(fd), 0)
    fs.close(fd)
    got = [json.loads(x) for x in raw.decode().splitlines() if x.strip()]
    want = [(h["step"], h["loss"]) for hist in histories for h in hist]
    have = [(g.get("step"), g.get("loss")) for g in got]
    return sum(1 for i, w in enumerate(want) if i >= len(have) or have[i] != w) \
        + max(0, len(have) - len(want))


def _same(got, want) -> bool:
    """Bitwise equal: dtype, shape and every byte."""
    got, want = np.ascontiguousarray(got), np.ascontiguousarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        return False
    g, w = got.reshape(-1).view(np.uint8), want.reshape(-1).view(np.uint8)
    block = 1 << 26
    return all(np.array_equal(g[i:i + block], w[i:i + block])
               for i in range(0, g.size, block))


def leaves_differ(got, want) -> int:
    """How many leaves of ``got`` differ bitwise from ``want``'s (a leaf
    that is missing on either side counts)."""
    import jax
    g = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    return sum(1 for k in g.keys() | w.keys()
               if k not in g or k not in w or not _same(g[k], w[k]))


def _restore_mismatch(run, fs, host, rng) -> tuple[int, int]:
    """(|manifest latest - saved step|, leaves whose sampled rows differ)."""
    import jax
    from repro.checkpoint.manager import CheckpointManager
    from repro.train import steps as tsteps
    mgr = CheckpointManager(fs)
    latest = mgr.latest_step()
    if latest is None:
        return (run.saved_step, len(jax.tree_util.tree_leaves(host)))
    rows = {}

    def pick(key, shape):
        if not shape or shape[0] < 2:
            return None
        n = max(1, shape[0] // SAMPLE_ROWS)
        lo = int(rng.integers(0, shape[0] - n + 1))
        rows[key] = (lo, lo + n)
        return rows[key]

    like = tsteps.abstract_train_state(run.model, run.opt)
    got = mgr.restore(like, step=latest, slice_rows=pick)
    mgr.close()
    bad = 0
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(host)[0]
    for (path, g), (_p, w) in zip(flat_got, flat_want):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        w = np.asarray(w)
        if key in rows:
            lo, hi = rows[key]
            w = w[lo:hi]
        if not _same(g, w):
            bad += 1
    return abs(latest - run.saved_step), bad


def _norm(x, minus=None) -> float:
    """Euclidean norm of ``x`` (of ``x - minus``), in float64 a block at a time."""
    flat = np.asarray(x).reshape(-1)
    sub = None if minus is None else np.asarray(minus).reshape(-1)
    total, block = 0.0, 1 << 24
    for i in range(0, flat.size, block):
        c = flat[i:i + block].astype(np.float64)
        if sub is not None:
            c -= sub[i:i + block]
        total += float(np.dot(c, c))
    return math.sqrt(total)


CHECK_STEPS = 3           # the reference follows the job's first three steps


class StateTap:
    """The program's train state at the two points the comparison reads:
    after the first step, whose first moment over 1 - b1 is the first
    gradient as the optimizer got it (its leaves' norms are kept), and
    after the third (the parameters are kept on the host, to take their
    change from the initialisation once the window has closed)."""

    def __init__(self, b1: float, program_leaves):
        self.b1 = b1
        self.program_leaves = program_leaves     # the reference's leaf names
        self.grad: dict | None = None
        self.params = None

    def __call__(self, step: int, state) -> None:
        import jax
        if step == 0:
            m = self.program_leaves(jax.tree.map(np.asarray, state["opt"]["m"]))
            self.grad = {k: _norm(v) / (1 - self.b1) for k, v in m.items()}
        elif step == CHECK_STEPS - 1:
            self.params = jax.tree.map(np.asarray, state["params"])


def loop_state(frame):
    """The train state held by ``repro.train.loop.train`` in ``frame``, the
    frame that called the heartbeat: the loop hands the hook only the step,
    so the check reads the loop's local, and keeps nothing on the device."""
    if frame.f_code.co_name != "train" or "state" not in frame.f_locals:
        raise RuntimeError("heartbeat not called from repro.train.loop.train")
    return frame.f_locals["state"]


def program_readings(losses, tap: StateTap, ref, p0) -> dict:
    """The program's side of the comparison: its first losses, the leaf
    norms of its first gradient, and of its parameters' change from ``p0``
    (the reference's initialisation, which the program's equals)."""
    p3 = tap.program_leaves(tap.params)
    start = {k: np.asarray(v) for k, v in ref.leaves(p0).items()}
    return {"losses": list(losses[:CHECK_STEPS]), "grad": tap.grad,
            "change": {k: _norm(p3[k], minus=start[k]) for k in start}}


def _worst_leaf(got: dict, ref: dict, keys) -> float:
    """The widest gap between a leaf's norm and the reference's, over the
    larger of the reference's norm of that leaf and of the median leaf."""
    med = float(np.median(list(ref.values())))
    return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in keys)


def gaps(got: dict, ref: dict) -> dict:
    """Relative gaps to the reference: the worst of the first steps'
    losses; by the worst leaf, the first gradient's norm and the
    parameters' change over the steps.  Leaves whose reference gradient is
    under a thousandth of the median leaf's move by rounding alone and are
    left out of the change."""
    rel = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
    med = float(np.median(list(ref["grad"].values())))
    moving = [k for k, v in ref["grad"].items() if v >= 1e-3 * med]
    return {"loss_gap": max(rel),
            "grad_gap": _worst_leaf(got["grad"], ref["grad"], ref["grad"]),
            "change_gap": _worst_leaf(got["change"], ref["change"], moving)}


def run_checks(run) -> dict:
    import jax
    rng = np.random.default_rng(run.seed)
    out = {}
    host = None
    if run.kind == "resume":
        host = run.saved_host
    elif run.saved_step is not None:
        host = jax.tree.map(np.asarray, run.final_state)
    if run.kind != "resume":
        out["opt_step_off"] = _ok(abs(run.final_opt_step - run.expect_opt_step), 0)
        del run.final_state
    fs = _recover(run)
    try:
        out["metrics_lines_lost"] = _ok(_metrics_lines_lost(fs, run.histories), 0)
        if host is not None:
            off, bad = _restore_mismatch(run, fs, host, rng)
            out["ckpt_latest_off"] = _ok(off, 0)
            out["ckpt_leaves_differ"] = _ok(bad, 0)
    finally:
        fs.nv.shutdown()
    del host
    if run.kind == "resume":
        out["resume_restore_differ"] = _ok(leaves_differ(run.restored, run.saved_host), 0)
        run.restored = run.saved_host = None
        steps = run.saved_step + run.traffic["resume_steps"]
        out["resume_opt_step_off"] = _ok(sum(abs(s - steps) for s in run.opt_steps), 0)
        out["resume_feed_off"] = _ok(sum(abs(s - run.saved_step)
                                         for s in run.feed.restored_steps), 0)
        want = run.continuation
        out["resume_losses_differ"] = _ok(
            sum(1 for c in run.cycles + [run.check_cycle]
                if c["losses"] != want or len(want) != run.traffic["resume_steps"]), 0)

    g = model_gaps(run.config, run.ref, run.key_seed, run.feed, run.histories[0], run.tap)
    run.tap.params = None
    for k, limit in run.config["limits"].items():
        out[k] = _ok(g[k], limit)
    return out


def model_gaps(config: dict, ref, key_seed: int, feed, history, tap: StateTap) -> dict:
    """The float32 reference over the job's first batches, against what the
    program's first steps produced."""
    import jax
    key = jax.random.PRNGKey(key_seed)
    batches = [feed.batch_at(i)["tokens"] for i in range(CHECK_STEPS)]
    want = ref.first_steps(config["model"], config["optimizer"], key, batches)
    got = program_readings([h["loss"] for h in history], tap, ref,
                           ref.init_params(config["model"], key))
    return gaps(got, want)
