"""Kernel micro-bench: jnp oracle wall time on CPU (the portable path) and
interpret-mode parity check per kernel, plus the NVMM log commit-path
micro-kernel at K ∈ {1, 4} shards (the storage hot path is as much a
"kernel" of this system as the jax ops).  Real TPU timings are out of scope
here: the chip benchmark is ``bench/run.py``."""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp

from repro.kernels import ref


def log_commit_rows(writers: int = 4, ops_per_writer: int = 400):
    """Raw append+drain cycle through the sharded NVMM log, no slow tier:
    measures commit-path overhead and allocation contention per shard count.
    """
    from repro.core import NVMM, Policy
    from repro.core.log import NVLog

    rows = []
    for k in (1, 4):
        pol = Policy(entry_size=4096, log_entries=1024 * k, page_size=4096,
                     batch_min=64, batch_max=256, verify_crc=False,
                     shards=k, shard_route="fdid")
        log = NVLog(NVMM(pol.nvmm_bytes), pol, format=True)
        stop = threading.Event()

        def drainer(sh):
            while not stop.is_set():
                run = sh.committed_run(sh.persistent_tail, pol.batch_max)
                if run:
                    sh.consume(sh.persistent_tail, run)
                else:
                    time.sleep(0.0005)

        ds = [threading.Thread(target=drainer, args=(sh,), daemon=True)
              for sh in log.shards]
        for d in ds:
            d.start()
        buf = b"z" * 4000

        def writer(w):
            for i in range(ops_per_writer):
                log.append(w, i * 4096, buf, timeout=30.0)

        ws = [threading.Thread(target=writer, args=(w,))
              for w in range(writers)]
        t0 = time.perf_counter()
        for w in ws:
            w.start()
        for w in ws:
            w.join()
        dt = time.perf_counter() - t0
        stop.set()
        for d in ds:
            d.join(timeout=5)
        n = writers * ops_per_writer
        rows.append((f"kernel/log_commit_k{k}_{writers}w",
                     1e6 * dt / n, f"{n / dt:.0f}commits/s"))
    return rows


def _time(f, *args, reps=5):
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return 1e6 * (time.perf_counter() - t0) / reps


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    B, S, H, KV, D = 1, 512, 8, 4, 64
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k = jax.random.normal(key, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(key, (B, S, KV, D), jnp.float32)
    us = _time(jax.jit(lambda a, b, c: ref.attention_ref(a, b, c)), q, k, v)
    rows.append(("kernel/attention_ref_512", us,
                 f"{4 * B * H * S * S * D / us / 1e3:.1f}GFLOP/s"))

    b, s, h, p, n = 1, 1024, 8, 64, 64
    x = jax.random.normal(key, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(key, (b, s, h)))
    A = -jnp.exp(jax.random.normal(key, (h,)))
    Bm = jax.random.normal(key, (b, s, 1, n))
    Cm = jax.random.normal(key, (b, s, 1, n))
    us = _time(jax.jit(lambda *a: ref.ssd_ref(*a, chunk=128)[0]), x, dt, A, Bm, Cm)
    rows.append(("kernel/ssd_ref_1k", us, ""))

    xq = jax.random.normal(key, (1024, 4096))
    us = _time(jax.jit(lambda a: ref.quantize_ref(a)[0]), xq)
    rows.append(("kernel/quantize_4M", us, f"{xq.size * 4 / us / 1e3:.1f}GB/s"))

    rows.extend(log_commit_rows())

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    return rows


if __name__ == "__main__":
    run()
