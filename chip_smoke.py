#!/usr/bin/env python3
"""Bring-up check on the TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip: kernels, serve, train
    python chip_smoke.py --four-chips  # 2x2 mesh: sharded train vs one device

One process, and the only one that touches JAX.  Phases run in order; each
prints its lines and raises on failure.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the device phase exits non-zero before any work.

Phases (one chip):
  kernels  the compiled Pallas SSD scan (mamba2-780m widths) and int8
           quantizer (a llama3.2-1b leaf) against their ``kernels/ref.py``
           oracles, on the chip;
  serve    ``repro.launch.serve.main``: llama3.2-1b at full size, batch 4,
           512-token prompts, 32 new tokens; both request-journal lines are
           read back through NVCache;
  train    ``repro.launch.train.main``: llama3.2-1b at full widths, depth cut
           to ``TRAIN_LAYERS``; 4 steps checkpointing every 2 through
           NVCache, a restore that must equal the saved state bitwise, then
           a resume for 2 more steps through the loop's restore path.
Each phase reports compile seconds apart from run seconds, and the device's
``peak_bytes_in_use`` (the process's peak so far: serve runs before train so
that each peak belongs to its phase).
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ref  # noqa: E402
from repro.kernels.quantize import quantize_pallas  # noqa: E402
from repro.kernels.ssd_scan import ssd_pallas  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "llama3.2-1b"
FULL_LAYERS = 16
# HBM holds 8 layers' train step at batch 1x2048 (compiled for a described
# v5e: 9.0 GB of state, 8.8 GB of temporaries).  The cut comes from the
# host: the run saves the whole state (3.15 GB of embedding and moments,
# plus 0.73 GB per layer) three times and restores it twice, through
# NVCache at about 0.1 GB/s, and the in-memory blob tier keeps every
# checkpoint it was given, so 8 layers would need over 40 GiB of host RAM.
TRAIN_LAYERS = 4
SEQ = 2048
# ssd_pallas vs ssd_ref: tests/test_kernels.py's bound (interpret mode, f32
# arithmetic) and the bound on the chip, where the kernel's f32 matmuls run
# at the default precision (bf16 operands, 2^-8 relative each) and the
# oracle at precision=highest
SSD_TEST_TOL = 2e-3
SSD_CHIP_TOL = 2 ** -6      # max |err| over max |oracle|
# sharded vs one-device losses: bf16 compute, a few ulps of 2^-8
LOSS_RTOL = 1e-2


def check(cond, what):
    """A result check that ``python -O`` keeps."""
    if not cond:
        raise AssertionError(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), as the union of its spans: a nested jit traces
    inside its caller, so summing the spans would count it twice."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans = []         # (start, end) on the perf_counter clock
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event, duration, **_kw):
        if event in self.EVENTS:
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def seconds_since(self, t0):
        total, lo, hi = 0.0, None, None
        for s, e in sorted((max(s, t0), e) for s, e in self.spans if e > t0):
            if hi is not None and s <= hi:
                hi = max(hi, e)
                continue
            if hi is not None:
                total += hi - lo
            lo, hi = s, e
        return total + (hi - lo if hi is not None else 0.0)


class Phase:
    """Reports one phase: compile seconds, the rest of its wall seconds, and
    the device's peak bytes in use so far."""

    def __init__(self, name, clock):
        self.name, self.clock = name, clock

    def __enter__(self):
        print(f"phase {self.name}: start", flush=True)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            print(f"phase {self.name}: FAILED", flush=True)
            return False
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds_since(self.t0)
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        print(f"phase {self.name}: ok compile_s={comp:.3f} "
              f"run_s={wall - comp:.3f} peak_bytes_in_use={peak}", flush=True)
        return False


def phase_device(need):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: device phase: JAX found no TPU "
                 f"(platform {devs[0].platform!r}); nothing was run")
    if len(devs) < need:
        sys.exit(f"chip_smoke: device phase: {need} TPU chips needed, "
                 f"{len(devs)} found")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs[0]


def _compiled(fn, *args):
    """AOT-compile ``fn``; require the Pallas kernel in the program."""
    lowered = jax.jit(fn).lower(*args)
    check("tpu_custom_call" in lowered.as_text(), "no Pallas kernel in program")
    return lowered.compile()


def phase_kernels():
    # mamba2-780m: 48 heads, head_dim 64, state 128, 1 group, chunk 256
    b, s, h, p, g, n, chunk = 1, 2048, 48, 64, 1, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    kern = _compiled(lambda *a: ssd_pallas(*a, chunk=chunk), x, dt, A, B, C)
    y1, st1 = kern(x, dt, A, B, C)
    with jax.default_matmul_precision("highest"):
        y2, st2 = jax.jit(lambda *a: ref.ssd_ref(*a, chunk=chunk))(x, dt, A, B, C)
    for name, got, want in (("y", y1, y2), ("state", st1, st2)):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        err = np.abs(got - want)
        test_q = float(np.max(err / (SSD_TEST_TOL + SSD_TEST_TOL * np.abs(want))))
        chip_q = float(err.max() / np.abs(want).max())
        print(f"kernels: ssd_pallas {name} {got.shape} vs ssd_ref: max |err| "
              f"{err.max():.4e}, max |ref| {np.abs(want).max():.4e}; "
              f"test_kernels bound (atol = rtol = {SSD_TEST_TOL}) used "
              f"{test_q:.2f}x; chip bound max|err| <= {SSD_CHIP_TOL} * max|ref| "
              f"used {chip_q / SSD_CHIP_TOL:.3f}x (default matmul precision "
              f"rounds the kernel's f32 operands to bf16)", flush=True)
        check(chip_q <= SSD_CHIP_TOL, f"ssd_pallas {name} off its oracle")

    # a llama3.2-1b MLP leaf (d_model x d_ff), group 256
    w = jax.random.normal(jax.random.PRNGKey(1), (2048, 8192)) * 3
    kern = _compiled(quantize_pallas, w)
    q1, s1 = kern(w)
    q2, s2 = jax.jit(ref.quantize_ref)(w)
    mismatched = int(jnp.sum(q1 != q2))
    print(f"kernels: quantize_pallas {w.shape} vs quantize_ref: "
          f"{mismatched} int8 values differ (bound 0)", flush=True)
    check(mismatched == 0, "quantize_pallas values differ from the oracle")
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def phase_serve():
    batch, prompt, new = 4, 512, 32
    fs = serve.open_fs()
    out = serve.main(["--arch", ARCH, "--batch", str(batch),
                      "--prompt-len", str(prompt), "--tokens", str(new)], fs=fs)
    check(out["completed"] == batch * new, out)
    fd = fs.open(serve.JOURNAL)
    lines = fs.pread(fd, fs.size(fd), 0).decode().splitlines()
    fs.close(fd)
    fs.nv.shutdown()
    recs = [json.loads(line) for line in lines]
    check(len(recs) == 2 and recs[0] == {"batch": batch, "prompt_len": prompt}
          and recs[1]["completed"] == batch * new, recs)
    print(f"serve: {ARCH} {FULL_LAYERS} layers, {out['completed']} tokens; "
          f"journal read back: {recs}", flush=True)


def _check_losses(losses, n):
    check(len(losses) == n and all(math.isfinite(x) for x in losses), losses)


def phase_train():
    print(f"train: {ARCH} at full widths (d_model 2048, 32/8 heads, head_dim "
          f"64, d_ff 8192, vocab 128256); n_layers {TRAIN_LAYERS} of "
          f"{FULL_LAYERS} (CUT); batch 1; seq {SEQ}", flush=True)
    args = ["--arch", ARCH, "--n-layers", str(TRAIN_LAYERS), "--batch", "1",
            "--seq", str(SEQ), "--ckpt-every", "2"]
    fs = train.open_fs()
    first = train.main(args + ["--steps", "4"], fs=fs)
    _check_losses(first["losses"], 4)
    # same target step: the loop restores step 4 and runs nothing
    t0 = time.perf_counter()
    restored = train.main(args + ["--steps", "4"], fs=fs)
    restore_s = time.perf_counter() - t0
    check(restored["steps"] == 0, restored)
    check(restored["state_sha256"] == first["state_sha256"],
          "restored state differs from the saved state")
    resumed = train.main(args + ["--steps", "6"], fs=fs)
    fs.nv.shutdown()
    check(resumed["resumed_from"] == 4, resumed)
    _check_losses(resumed["losses"], 2)
    print(f"train: losses {first['losses']} then resumed at step 4: "
          f"{resumed['losses']}", flush=True)
    print(f"train: save_s {first['save_s'] + resumed['save_s']} (steps 2, 4, "
          f"6); restore_s {restore_s:.3f} (step 4, loop resume path incl. "
          f"device_put and digest); restored state bitwise equal to saved "
          f"(sha256 {first['state_sha256'][:16]})", flush=True)


def four_chips(clock):
    """Sharded training on the 2x2 mesh against the same steps on one of
    its devices; the sharded restore must equal what was saved."""
    # batch 1 as in the train phase: its one row is replicated over the data
    # axis, which still shards parameters and moments (FSDP); batch 2 would
    # not fit the one-device run's 16 GB
    print(f"four-chips: {ARCH} n_layers {TRAIN_LAYERS} of {FULL_LAYERS} (CUT); "
          f"batch 1; seq {SEQ}; mesh data=2 x model=2", flush=True)
    args = ["--arch", ARCH, "--n-layers", str(TRAIN_LAYERS), "--batch", "1",
            "--seq", str(SEQ), "--steps", "4", "--ckpt-every", "4"]
    fs = train.open_fs()
    with Phase("sharded-train", clock):
        sharded = train.main(args + ["--mesh", "debug"], fs=fs)
        _check_losses(sharded["losses"], 4)
    with Phase("sharded-restore", clock):
        restored = train.main(args + ["--mesh", "debug"], fs=fs)
        fs.nv.shutdown()
        check(restored["steps"] == 0, restored)
        check(restored["state_sha256"] == sharded["state_sha256"],
              "sharded restore differs from the saved state")
    with Phase("one-device-train", clock):
        single = train.main(args)
        _check_losses(single["losses"], 4)
    rel = max(abs(a - b) / abs(b)
              for a, b in zip(sharded["losses"], single["losses"]))
    print(f"four-chips: sharded losses {sharded['losses']}; one device "
          f"{single['losses']}; max relative difference {rel:.3e} (bound "
          f"{LOSS_RTOL}); sharded restore bitwise equal to saved", flush=True)
    check(rel <= LOSS_RTOL, "sharded losses differ from the one-device run")


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 2x2 training path and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    dev = phase_device(4 if args.four_chips else 1)
    clock = CompileClock()
    if args.four_chips:
        four_chips(clock)
    else:
        with Phase("kernels", clock):
            phase_kernels()
        with Phase("serve", clock):
            phase_serve()
        with Phase("train", clock):
            phase_train()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
