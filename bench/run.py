#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``: the
model the job trains, the NVCache deployment under it, and the name of its
plain reference in ``bench/reference/``, which also counts the step's FLOPs)
and a traffic mix (``bench/traffic/<name>.json``: the checkpoint schedule
and the token feed).  The window drives the program's own job,
``repro.train.loop.train``, over the file system of
``repro.launch.train.open_fs``.  Per-layer metrics are read by
``bench/metrics/<name>.py``; the check that decides ``correct`` is in
``bench/check.py``.  All of these are found by name, under the checkout's
``bench/``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, from a run
under the profiler.  Without an accelerator the run exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)            # bench/ holds modules, not top-level names
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check  # noqa: E402

BIG = 1 << 40                  # a step count the job never reaches
MANIFEST = "MANIFEST.json"
METRICS_LOG = "/metrics.jsonl"


class NoChip(RuntimeError):
    pass


def load_file(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}".replace(".", "_")
                                                  .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str, root: Path = ROOT):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return cell, config, traffic


def load_reference(config: dict, root: Path = ROOT):
    return load_file(root / "bench" / "reference" / f"{config['reference']}.py")


def config_value(config: dict, path: str):
    """The value at a dotted path of a configuration file (``model.vocab_size``)."""
    v = config
    for k in path.split("."):
        v = v[k]
    return v


def fold_seed(seed: int) -> int:
    """The seed as JAX's PRNGKey takes it (it keeps 32 bits): fold the high
    bits in, so that seeds which differ only there still differ."""
    return (seed ^ (seed >> 31) ^ (seed >> 62)) & 0x7FFFFFFF


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoChip(f"need {chips} accelerator(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache() -> None:
    """JAX's persistent compilation cache inside the checkout, at a fixed
    path (``JAX_COMPILATION_CACHE_DIR`` wins where it is set)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# ----------------------------------------------------------------- probes

class Span:
    """A host span in the profiler's trace, entered and left by hand (the
    harness's hooks open it in one callback and close it in another)."""

    def __init__(self, on: bool):
        self.on = on
        self.open: dict = {}

    def enter(self, name: str) -> None:
        if self.on and name not in self.open:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
            self.open[name] = ann

    def exit(self, name: str) -> None:
        ann = self.open.pop(name, None)
        if ann is not None:
            ann.__exit__(None, None, None)


class FsProbe:
    """The file system handed to the job: every call passes through to the
    NVCache file system; manifest commits are stamped on the host clock."""

    def __init__(self, inner, on_commit, span: Span):
        self.inner = inner
        self.nv = inner.nv
        self.paths: dict = {}
        self.on_commit = on_commit
        self.span = span

    def open(self, path):
        fd = self.inner.open(path)
        self.paths[fd] = path
        return fd

    def pwrite(self, fd, data, off):
        if self.paths.get(fd) == METRICS_LOG:
            self.span.enter("metrics_log")
            try:
                return self.inner.pwrite(fd, data, off)
            finally:
                self.span.exit("metrics_log")
        return self.inner.pwrite(fd, data, off)

    def fsync(self, fd):
        self.inner.fsync(fd)
        if self.paths.get(fd, "").endswith(MANIFEST):
            self.on_commit(time.perf_counter())

    def close(self, fd):
        self.inner.close(fd)
        self.paths.pop(fd, None)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def alloc_wait_s(nv) -> float:
    return nv.metrics()["log.alloc_wait_us"]["sum_us"] * 1e-6


class CkptProbe:
    """Times ``CheckpointManager.save`` and ``restore`` (the checkpoint
    layer's entry points) while installed; the calls themselves run
    unchanged."""

    def __init__(self, span: Span):
        self.span = span
        self.saves: list = []
        self.restores: list = []
        self.keep_saved = False      # hold the tree handed to the last save
        self.keep_restored = False   # hold the tree the last restore returned
        self.saved = self.restored = None

    def __enter__(self):
        from repro.checkpoint import manager as mg
        self._orig = (mg.CheckpointManager.save, mg.CheckpointManager.restore)
        orig_save, orig_restore = self._orig
        probe = self

        def save(mgr, step, tree):
            probe.span.exit("d2h_copy")
            if probe.keep_saved:
                probe.saved = tree
            nbytes = sum(getattr(x, "nbytes", 0) for x in _leaves(tree))
            w0 = alloc_wait_s(mgr.fs.nv)
            probe.span.enter("ckpt_save")
            t0 = time.perf_counter()
            try:
                return orig_save(mgr, step, tree)
            finally:
                t1 = time.perf_counter()
                probe.span.exit("ckpt_save")
                probe.saves.append({"step": step, "t0": t0, "t1": t1,
                                    "bytes": nbytes,
                                    "alloc_wait_s": alloc_wait_s(mgr.fs.nv) - w0})

        def restore(mgr, tree_like, *a, **kw):
            probe.span.enter("ckpt_restore")
            t0 = time.perf_counter()
            try:
                out = orig_restore(mgr, tree_like, *a, **kw)
            finally:
                probe.span.exit("ckpt_restore")
            probe.restores.append({"t0": t0, "t1": time.perf_counter(),
                                   "bytes": sum(x.nbytes for x in _leaves(out))})
            if probe.keep_restored:
                probe.restored = out
            return out

        mg.CheckpointManager.save, mg.CheckpointManager.restore = save, restore
        return self

    def __exit__(self, *exc):
        from repro.checkpoint import manager as mg
        mg.CheckpointManager.save, mg.CheckpointManager.restore = self._orig
        return False


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


# ------------------------------------------------------------------ the job

def program_config(config: dict):
    """The program's own configuration of the model (``program.arch``),
    with the fields of ``program.set`` taken from the file (the cuts); each
    field of ``program.check`` must already agree with the file.  Both map
    a field of the program's config to a dotted path of the file."""
    from repro.configs.registry import get_config
    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **{f: config_value(config, k) for f, k in prog["set"].items()})
    for attr, key in prog["check"].items():
        have, need = getattr(cfg, attr), config_value(config, key)
        if have != need:
            raise ValueError(f"program config {cfg.arch}: {attr}={have!r}, "
                             f"file says {need!r} ({key})")
    return cfg


def open_job_fs(log_mib: float, obs_level: int):
    """The launcher's NVCache file system; a traced run raises only the
    policy's ``obs_level``, over the same blob tier."""
    from repro.core import NVCache
    from repro.launch.train import open_fs
    from repro.storage.fsapi import NVCacheFS
    fs = open_fs(log_mib)
    if obs_level:
        policy = dataclasses.replace(fs.nv.policy, obs_level=obs_level)
        tier = fs.nv.tier
        fs.nv.shutdown()
        fs = NVCacheFS(NVCache(policy, tier))
    return fs


class Run:
    """One run of one cell: set-up, window, check."""

    def __init__(self, cell, config, traffic, *, seed, seconds, trace,
                 require_chip=True, out_dir: Path | None = None, root: Path = ROOT):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.root = root
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.require_chip = require_chip
        self.out_dir = out_dir or ROOT / "bench" / ".out"
        self.span = Span(self.trace)
        self.step_end: dict = {}
        self.commits: list = []
        self.t_open = self.t_close = None
        self.histories: list = []
        self.cycles: list = []
        self.check_cycle = None

    # -- hooks the job calls -------------------------------------------
    def _on_next(self, step):
        self.span.enter("step")

    def _on_state(self, name, entering):
        (self.span.enter if entering else self.span.exit)(name)

    def _heartbeat(self, step):
        t = time.perf_counter()
        self.span.exit("step")
        self.step_end[step] = t
        if not self.histories and step < check.CHECK_STEPS:    # set-up's steps
            self.tap(step, check.loop_state(sys._getframe(1)))
            t = time.perf_counter()          # the tap's copies are set-up
        k = self.traffic.get("ckpt_every")
        if self.kind != "resume" and step == self.warmup - 1 and self.t_open is None:
            self._open_window(t)
        if self.kind == "steady" and self._window_over(t):
            self._close_window(t)
        if self.kind == "resume" and step == self.resume_at + self.traffic["resume_steps"] - 1:
            self.feed.stop = True
        if k and (step + 1) % k == 0:
            self.span.enter("d2h_copy")

    def _on_commit(self, t):
        self.commits.append(t)
        if self.kind == "interval" and self._window_over(t):
            self._close_window(t)

    def _window_over(self, t):
        return self.t_open is not None and self.t_close is None \
            and t >= self.t_open + self.seconds

    def _open_window(self, t):
        self.t_open = t
        self.setup_s = t - T_START
        if self.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.trace_dir = self.out_dir / f"trace_{self.cell['name']}_{self.seed}"
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            self.span.enter("window")

    def _close_window(self, t):
        self.t_close = t
        self.feed.stop = True
        self.span.exit("window")

    def _stop_trace(self):
        """After the job has returned: serializing the trace inside a hook
        would stretch the save or step that closed the window."""
        if self.trace:
            import jax
            jax.profiler.stop_trace()

    # -- driving the job -------------------------------------------------
    def setup(self):
        from repro.models.registry import build
        from repro.optim.adamw import AdamW
        from bench.tokens import ZipfTokens
        self.device = device_info(self.cell.get("chips", 1), self.require_chip)
        enable_cache()
        self.kind = self.traffic["kind"]
        self.warmup = self.traffic["warmup_steps"]
        if self.warmup < check.CHECK_STEPS:
            raise ValueError(f"warmup_steps must cover the {check.CHECK_STEPS} steps "
                             "the check compares")
        if self.kind == "resume" and self.warmup <= self.traffic["resume_steps"]:
            raise ValueError("warmup_steps must exceed resume_steps: set-up runs on "
                             "that many steps past its save, and saves every "
                             "warmup_steps")
        pcfg = program_config(self.config)
        self.ref = load_reference(self.config, self.root)
        self.model = build(pcfg)
        self.opt = AdamW(**self.config["optimizer"])
        self.tap = check.StateTap(self.opt.b1, self.ref.program_leaves)
        self.key_seed = fold_seed(self.seed)
        job = self.config["job"]
        self.feed = ZipfTokens(pcfg.vocab, job["batch"], job["seq"],
                               seed=self.seed, zipf_a=self.traffic["zipf_a"],
                               on_next=self._on_next, on_state=self._on_state)
        self.fs = FsProbe(open_job_fs(self.config["nvcache"]["log_mib"], 2 if self.trace else 0),
                          self._on_commit, self.span)

    def _train(self, total_steps, ckpt_every):
        from repro.train.loop import train
        state, hist = train(self.model, self.opt, self.feed, self.fs,
                            total_steps=total_steps, ckpt_every=ckpt_every,
                            seed=self.key_seed, heartbeat=self._heartbeat)
        self.histories.append(hist)
        return state, hist

    def window(self):
        with CkptProbe(self.span) as self.ckpt:
            if self.kind == "resume":
                self._resume_window()
                self._stop_trace()
                self._check_resume()
                return
            k = self.traffic.get("ckpt_every") or BIG
            state, hist = self._train(BIG, k)
            self._stop_trace()
            self.final_state = state
            self.final_opt_step = int(state["opt"]["step"])
            self.expect_opt_step = hist[-1]["step"] + 1
            self.saved_step = self.ckpt.saves[-1]["step"] if self.ckpt.saves else None

    def _resume_window(self):
        self.resume_at = self.warmup
        # set-up: save after the first warmup steps, then run on the same
        # resume_steps steps that every resume runs, without a crash
        self.ckpt.keep_saved = True
        state, hist = self._train(BIG, self.warmup)
        del state
        self.ckpt.keep_saved = False
        self.saved_host, self.ckpt.saved = self.ckpt.saved, None
        self.saved_step = self.warmup
        self.continuation = [h["loss"] for h in hist if h["step"] >= self.warmup]
        self._open_window(time.perf_counter())
        self.opt_steps = []
        while True:
            self.cycles.append(self._resume())
            t = time.perf_counter()
            if self._window_over(t):
                self._close_window(t)
                break

    def _resume(self) -> dict:
        """One kill and resume: a power loss of the job's NVCache, a new
        NVCache over the region, and ``train()`` from the newest checkpoint
        until the feed ends it ``resume_steps`` steps later, before any save."""
        from repro.core import NVCache
        from repro.storage.fsapi import NVCacheFS
        t_kill = time.perf_counter()
        region = self.fs.nv.crash()
        self.span.enter("nv_attach")
        t0 = time.perf_counter()
        nv = NVCache(self.fs.nv.policy, self.fs.nv.tier, nvmm=region)
        t1 = time.perf_counter()
        self.span.exit("nv_attach")
        self.fs = FsProbe(NVCacheFS(nv), self._on_commit, self.span)
        self.feed.stop = False
        self.step_end.clear()
        state, hist = self._train(BIG, BIG)
        self.opt_steps.append(int(state["opt"]["step"]))
        del state
        return {"kill": t_kill, "recovery_s": t1 - t0,
                "first_step_end": self.step_end.get(self.resume_at),
                "losses": [h["loss"] for h in hist],
                "first_step": hist[0]["step"] if hist else None}

    def _check_resume(self):
        """After the window: one more resume, untimed, that keeps the tree
        ``CheckpointManager.restore`` returned inside ``train()``."""
        self.ckpt.keep_restored = True
        self.check_cycle = self._resume()
        self.ckpt.keep_restored = False
        self.restored, self.ckpt.restored = self.ckpt.restored, None

    # -- results -----------------------------------------------------------
    def end_to_end(self) -> dict:
        job = self.config["job"]
        tokens_per_step = job["batch"] * job["seq"]
        out = {"setup_s": (self.setup_s, "s")}
        if self.kind == "resume":
            rs = [c["first_step_end"] - c["kill"] for c in self.cycles]
            out["resume_s"] = (statistics.fmean(rs), "s")
            return out
        hist = self.histories[0]
        last = hist[-1]["step"]
        steps = last - (self.warmup - 1)
        out["train_tokens_per_s"] = (steps * tokens_per_step / (self.t_close - self.t_open),
                                     "tokens/s")
        if self.kind == "interval":
            ds = [self._commit_after(s["t1"]) - self.step_end[s["step"] - 1]
                  for s in self.window_saves()]
            out["ckpt_durable_s"] = (statistics.fmean(ds), "s")
        return out

    def _commit_after(self, t):
        return max(c for c in self.commits if c <= t)

    def _in_window(self, t) -> bool:
        return self.t_open <= t <= self.t_close

    def window_saves(self):
        return [s for s in self.ckpt.saves if self._in_window(s["t0"])]

    def readings(self) -> dict:
        job = self.config["job"]
        peaks = json.loads((self.root / "bench" / "peaks.json").read_text())["devices"]
        kind = self.device["kind"]
        r = {"saves": self.window_saves() if self.kind != "resume" else [],
             "restores": [x for x in self.ckpt.restores if self._in_window(x["t0"])],
             "recoveries": [c["recovery_s"] for c in self.cycles],
             "save_s": [h["save_s"] for h in (self.histories[0] if self.kind != "resume" else [])
                        if "save_s" in h and h["step"] >= self.warmup],
             "flops_per_step": self.ref.step_flops(self.config["model"], job["batch"],
                                                   job["seq"]),
             "trace": getattr(self, "trace_red", None)}
        if self.device["platform"] != "cpu":
            if kind not in peaks:
                raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
            r["peak_flops"] = peaks[kind]["bf16_flops"]
        return r

    def memory_peak(self) -> int:
        """The fullest chip's peak: its live buffers plus the region the
        TPU runtime reserves apart for the programs' temporaries, which
        ``peak_bytes_in_use`` does not count."""
        import jax
        peak = 0
        for d in jax.devices()[:self.device["count"]]:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                       + int(st.get("peak_bytes_reserved", 0)))
        return peak


def per_layer_metrics(bench: dict, cell_name: str, readings: dict,
                      root: Path = ROOT) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        v = load_file(root / "bench" / "metrics" / f"{m['name']}.py").read(readings)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: int, *,
             bench: dict | None = None, root: Path = ROOT,
             require_chip: bool = True, out_dir: Path | None = None) -> dict:
    bench = bench or json.loads((root / "BENCHMARK.json").read_text())
    cell, config, traffic = find_cell(bench, name, root)
    run = Run(cell, config, traffic, seed=seed, seconds=seconds, trace=trace,
              require_chip=require_chip, out_dir=out_dir, root=root)
    run.setup()
    run.window()
    device = dict(run.device, memory_peak_bytes=run.memory_peak())
    if trace:
        from bench import tracereduce
        run.trace_red = tracereduce.reduce(tracereduce.load(str(run.trace_dir)))
        device["busy_s"] = run.trace_red["busy_s"]
        device["window_s"] = run.trace_red["window_s"]
    checks = check.run_checks(run)
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": check.attempted(run), "failed": check.failed(run),
              "metrics": {}, "device": device}
    if trace:
        result["metrics"] = per_layer_metrics(bench, name, run.readings(), root)
        result["breakdown"] = {"device_ops": run.trace_red["device_ops"],
                               "idle_gaps": run.trace_red["idle_gaps"]}
    else:
        e2e = run.end_to_end()
        for m in bench["end_to_end"]:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]][0],
                                                "unit": e2e[m["name"]][1]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result))
    sys.stdout.flush()
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
