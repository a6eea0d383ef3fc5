"""Training loop with NVCache-backed persistence.

Every durable artifact — checkpoints, data-pipeline state, metrics JSONL —
goes through the plain file API; when that FS is NVCache-backed, a step's
checkpoint is synchronously durable at fast-tier speed and drains to the
blob tier in the background (the paper's cleanup thread IS the
compute/IO overlap).  On restart the loop recovers: NVCache log replay ->
manifest -> restore -> resume the data pipeline at the exact step.
"""
from __future__ import annotations

import json
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.models.registry import Model
from repro.optim.adamw import AdamW
from repro.train import steps as tsteps


# the step's counters that the step span carries (a dropless expert model's)
STEP_COUNTERS = ("moe_rows", "moe_load_max", "moe_dropped")


class MetricsLog:
    """JSONL metrics through the FS (another 'legacy' NVCache consumer)."""

    def __init__(self, fs, path: str = "/metrics.jsonl"):
        self.fs = fs
        self.fd = fs.open(path)
        self.off = fs.size(self.fd)

    def log(self, step: int, metrics: dict) -> None:
        with obs.span("train.metrics_us") as sp:
            rec = {"step": step}
            for k, v in metrics.items():
                try:
                    rec[k] = float(v)
                except (TypeError, ValueError):
                    pass
            line = (json.dumps(rec) + "\n").encode()
            sp.set(bytes=len(line))
            self.fs.pwrite(self.fd, line, self.off)
            self.off += len(line)


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def train(model: Model, optimizer: AdamW, pipeline, fs, *,
          total_steps: int, ckpt_every: int = 50, keep: int = 2,
          mesh=None, fsdp: bool = True, seed: int = 0,
          heartbeat: Optional[Callable[[int], None]] = None,
          compress_grads: bool = False):
    """Returns (final_state, history list of metric dicts).  Each history
    entry carries its ``step``; a step that checkpointed also carries
    ``save_s`` (device-to-host copy, encode and durable write).

    The loop's phases are timeline spans (``repro.obs.span``): the step,
    the batch pull, the metrics line, the save and its device-to-host
    copy, the restore and its host-to-device put, and the pipeline state."""
    mgr = CheckpointManager(fs, keep=keep)
    metrics_log = MetricsLog(fs)
    step_fn = tsteps.make_train_step(model, optimizer, compress=compress_grads)

    latest = mgr.latest_step()
    start = 0 if latest is None else latest
    if latest is not None:
        with obs.span("train.pipeline_us"):
            pipeline.restore_state(fs)
    # the first batch also gives the mesh its batch shardings
    with obs.span("train.batch_us"):
        batch = pipeline.next()
    if mesh is not None:
        (in_sh, b_sh), (out_sh, _), like = tsteps.train_shardings(
            model, optimizer, mesh, batch, fsdp=fsdp)
        step_fn = jax.jit(tsteps.bind_mesh(step_fn, mesh),
                          in_shardings=(in_sh, b_sh),
                          out_shardings=(out_sh, None), donate_argnums=(0,))
    else:
        in_sh = None
        like = tsteps.abstract_train_state(model, optimizer)
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    # ---- restore or init (placed straight onto the mesh's shardings) -------
    if latest is None:
        state = jax.jit(lambda key: tsteps.init_train_state(model, optimizer, key),
                        out_shardings=in_sh)(jax.random.PRNGKey(seed))
    else:
        with obs.span("train.restore_us", step=latest):
            host = mgr.restore(like, step=latest)
            with obs.span("train.h2d_us", bytes=_nbytes(host)):
                state = jax.device_put(
                    jax.tree.map(lambda l, a: a.astype(l.dtype), like, host), in_sh)
            del host    # the host copy would otherwise live through training
    history = []

    # no batch is pulled past total_steps; a None from the feed ends the loop
    for step in range(start, total_steps):
        if step > start:
            with obs.span("train.batch_us"):
                batch = pipeline.next()
        if batch is None:
            break
        with obs.span("train.step_us", step=step) as sp:
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            metrics = dict(metrics, step_time=time.perf_counter() - t0)
            counters = {k: int(metrics[k]) for k in STEP_COUNTERS if k in metrics}
            if counters:
                sp.set(**counters)
        metrics_log.log(step, metrics)
        history.append({"step": step, **{k: float(v) for k, v in metrics.items()}})
        if heartbeat:
            heartbeat(step)
        if (step + 1) % ckpt_every == 0 or step + 1 == total_steps:
            with obs.span("train.save_us", step=step + 1):
                t0 = time.perf_counter()
                with obs.span("train.d2h_us", bytes=_nbytes(state)):
                    host_state = jax.tree.map(np.asarray, state)
                mgr.save(step + 1, host_state)
                with obs.span("train.pipeline_us"):
                    pipeline.save_state(fs)
                history[-1]["save_s"] = time.perf_counter() - t0
    return state, history
