#!/usr/bin/env python3
"""Readings that the limits of ``bench/check.py`` are set from, on the chip.

    python3 bench/calibrate.py --config minicpm3-4b.nvmm64m --seeds 12 [--first-seed N]

For each seed, in one process: the program's first three steps through
``repro.train.loop.train`` at the cells' sizes (the same feed, optimizer,
file system and state tap as a run), then the float32 reference, the
control (the reference with every product in fp8) and the reference with
each planted fault (``unchanged``: the step returns its state unchanged;
``half_batch``: half of the rows left out).  Each of the last three is
compared with the float32 reference as the program is.  Each seed's line
also gives the program's gap in every leaf, signed.  Prints one JSON line per seed and a
summary: the lower reading (largest over the program's seeds) and the upper
(smallest over the control's and each fault's seeds) of each number.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
sys.path[:0] = [str(HERE.parent / "src"), str(HERE.parent)]

from bench import check  # noqa: E402
from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)
    import jax
    from repro.models.registry import build
    from repro.optim.adamw import AdamW
    from repro.train.loop import train
    from bench.tokens import ZipfTokens
    R.device_info(1, True)
    R.enable_cache()
    config = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    ref = R.load_reference(config)
    model = build(R.program_config(config))
    opt = AdamW(**config["optimizer"])
    job, m, o = config["job"], config["model"], config["optimizer"]
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        feed = ZipfTokens(m["vocab_size"], job["batch"], job["seq"], seed=seed, zipf_a=1.3)
        tap = check.StateTap(o["b1"], ref.program_leaves)

        def beat(step, feed=feed, tap=tap):
            tap(step, check.loop_state(sys._getframe(1)))
            if step == check.CHECK_STEPS - 1:
                feed.stop = True
        fs = R.open_job_fs(config["nvcache"]["log_mib"], 0)
        state, hist = train(model, opt, feed, fs, total_steps=R.BIG,
                            ckpt_every=R.BIG, seed=R.fold_seed(seed), heartbeat=beat)
        del state
        fs.nv.shutdown()
        batches = [feed.batch_at(k)["tokens"] for k in range(check.CHECK_STEPS)]
        key = jax.random.PRNGKey(R.fold_seed(seed))
        f32 = ref.first_steps(m, o, key, batches)
        prog = check.program_readings([h["loss"] for h in hist], tap, ref,
                                      ref.init_params(m, key))
        tap.params = None
        row = {"seed": seed, "program": check.gaps(prog, f32),
               "program_losses": prog["losses"], "reference_losses": f32["losses"],
               "leaf_gaps": {k: {"grad": prog["grad"][k] / f32["grad"][k] - 1,
                                 "change": prog["change"][k] / f32["change"][k] - 1}
                             for k in f32["grad"]}}
        for name, kw in (("control", {"mm_dtype": "float8_e4m3fn"}),
                         ("unchanged", {"fault": "unchanged"}),
                         ("half_batch", {"fault": "half_batch"})):
            r = ref.first_steps(m, o, key, batches, **kw)
            row[name] = check.gaps(r, f32)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"lower": {k: max(r["program"][k] for r in rows) for k in rows[0]["program"]}}
    for name in ("control", "unchanged", "half_batch"):
        summary[name] = {k: min(r[name][k] for r in rows) for k in rows[0][name]}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
