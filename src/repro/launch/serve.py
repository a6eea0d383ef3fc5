"""Serving launcher: batched prefill + decode with NVCache-backed request
logging (every accepted request is synchronously durable before decode —
no request is lost to a crash).

    python -m repro.launch.serve --arch llama3.2-1b --smoke --tokens 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import all_archs, get_config, get_smoke
from repro.core import NVCache, Policy
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build
from repro.storage.fsapi import NVCacheFS
from repro.storage.tiers import BLOB, Tier

JOURNAL = "/requests.jsonl"


def open_fs() -> NVCacheFS:
    """An NVCache-backed file system over a fresh blob tier."""
    return NVCacheFS(NVCache(Policy(entry_size=4096, log_entries=4096,
                                    read_cache_pages=64, batch_min=8,
                                    batch_max=256, verify_crc=False),
                             Tier(BLOB)))


def main(argv=None, fs=None):
    """Serve one batch and return the summary it prints.  ``fs``: journal
    requests (to ``JOURNAL``) on this file system and leave it open; by
    default a fresh :func:`open_fs`, shut down at the end."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=all_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    own_fs = fs is None
    if own_fs:
        fs = open_fs()
    log_fd = fs.open(JOURNAL)
    log_off = 0

    B, P = args.batch, args.prompt_len
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 1,
                                 cfg.vocab - 1).astype(jnp.int32)
    # request accepted == durably logged (synchronous durability)
    line = (json.dumps({"batch": B, "prompt_len": P}) + "\n").encode()
    log_off += fs.pwrite(log_fd, line, log_off)

    if cfg.family == "encdec":
        batch = {"frames": jnp.zeros((B, P, cfg.d_model), cfg.cdt),
                 "dec_tokens": prompts[:, :8]}
    else:
        batch = {"tokens": prompts}
    t0 = time.perf_counter()
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, P + args.tokens + 8)
                            )(params, batch)
    step = jax.jit(model.decode_step)
    out = []
    for _ in range(args.tokens):
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        out.append(tok)
        logits, cache = step(params, cache, tok)
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    tokens = jnp.concatenate(out, 1)
    completed = tokens.shape[0] * tokens.shape[1]
    line = (json.dumps({"completed": completed, "seconds": dt}) + "\n").encode()
    fs.pwrite(log_fd, line, log_off)
    fs.close(log_fd)
    summary = {"arch": cfg.arch, "batch": B, "prompt_len": P,
               "completed": completed,
               # wall clock of prefill + decode, compilation included
               "tokens_per_s": completed / dt,
               "sample": tokens[0, :8].tolist()}
    print(json.dumps(summary))
    if own_fs:
        fs.nv.shutdown()
    return summary


if __name__ == "__main__":
    main()
