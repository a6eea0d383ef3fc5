"""Training launcher.

    python -m repro.launch.train --arch llama3.2-1b --smoke --steps 50

Wires: config -> model -> AdamW -> deterministic data pipeline -> NVCache
(fast persistent tier in front of the blob tier) -> train loop with
synchronous-durability checkpoints, metrics JSONL and crash-safe resume.
On the CPU use --smoke (reduced config); the full configs are for the TPU
(``--n-layers`` cuts the depth and keeps every width).
"""
from __future__ import annotations

import argparse
import dataclasses
import json

from repro.checkpoint.manager import tree_digest
from repro.configs.registry import all_archs, get_config, get_smoke
from repro.core import NVCache, Policy
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.registry import build
from repro.optim.adamw import AdamW
from repro.optim.schedules import warmup_cosine
from repro.storage.fsapi import NVCacheFS
from repro.storage.tiers import BLOB, Tier
from repro.train import loop as train_loop


def open_fs(log_mib: float = 64) -> NVCacheFS:
    """An NVCache-backed file system over a fresh blob tier."""
    policy = Policy(entry_size=16384,
                    log_entries=max(64, int(log_mib * (1 << 20) // 16384)),
                    read_cache_pages=256, batch_min=16, batch_max=1024,
                    verify_crc=False)
    return NVCacheFS(NVCache(policy, Tier(BLOB)))


def main(argv=None, fs=None):
    """Train and return the summary it prints.  ``fs``: train on this file
    system and leave it open, so that a later call resumes from its
    checkpoints; by default a fresh :func:`open_fs`, shut down at the end."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=all_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config runnable on CPU")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="override the config's depth (widths unchanged)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="none", choices=["none", "debug"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-mib", type=float, default=64)
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    model = build(cfg)
    opt = AdamW(lr=args.lr, schedule=warmup_cosine(10, args.steps))
    pipe = SyntheticTokens(cfg.vocab, args.batch, args.seq, seed=0,
                           family=cfg.family, d_model=cfg.d_model)

    own_fs = fs is None
    if own_fs:
        fs = open_fs(args.log_mib)
    mesh = make_debug_mesh() if args.mesh == "debug" else None
    state, hist = train_loop.train(
        model, opt, pipe, fs, total_steps=args.steps,
        ckpt_every=args.ckpt_every, mesh=mesh,
        compress_grads=args.compress_grads)
    fs.nv.flush()
    out = {
        "arch": cfg.arch, "n_layers": cfg.n_layers, "batch": args.batch,
        "seq": args.seq, "steps": len(hist),
        "resumed_from": hist[0]["step"] if hist else None,
        "losses": [h["loss"] for h in hist],
        "save_s": [h["save_s"] for h in hist if "save_s" in h],
        # digest of the final state == of the last checkpoint it saved
        "state_sha256": tree_digest(state),
        "nvcache": fs.nv.stats(),
    }
    print(json.dumps(out, indent=1))
    if own_fs:
        fs.nv.shutdown()
    return out


if __name__ == "__main__":
    main()
