import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, record memory/cost/collective analysis to JSON.

The two lines above MUST precede any other import (jax locks the device
count on first init); do not move them.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] --out .scratch/dryrun
"""

import argparse
import gzip
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.registry import all_archs, get_config
from repro.configs.shapes import SHAPES, applicable, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build
from repro.optim.adamw import AdamW
from repro.parallel import sharding as shd
from repro.train import steps as tsteps

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^\s]*\s+(all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes per collective kind from post-SPMD HLO.

    Post-partitioning shapes are per-device, so these are per-device bytes
    crossing the interconnect (all-gather results count received bytes;
    all-reduce counts one traversal — the ring factor is applied in the
    roofline, not here)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dt, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        out[kind] = out.get(kind, 0) + nbytes
        out[kind + "_count"] = out.get(kind + "_count", 0) + 1
    return out


def _analyze(compiled):
    res = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                res["mem_" + k] = int(v)
        res["memory_analysis"] = str(ma)
    except Exception as e:  # CPU backend may not implement everything
        res["memory_analysis_error"] = repr(e)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        res["flops"] = float(ca.get("flops", 0.0))
        res["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        res["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))}
    except Exception as e:
        res["cost_analysis_error"] = repr(e)
    try:
        res["collectives"] = collective_bytes(compiled.as_text())
    except Exception as e:
        res["collectives_error"] = repr(e)
    return res


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *, fsdp=True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build(cfg)
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            opt = AdamW(moment_dtype="bfloat16" if cfg.param_dtype == "bfloat16" else None)
            step = tsteps.bind_mesh(tsteps.make_train_step(model, opt), mesh)
            spec = input_specs(cfg, shape)
            (in_sh, b_sh), (out_sh, _m), state_abs = tsteps.train_shardings(
                model, opt, mesh, spec, fsdp=fsdp)
            jitted = jax.jit(step, in_shardings=(in_sh, b_sh),
                             out_shardings=(out_sh, None), donate_argnums=(0,))
            lowered = jitted.lower(state_abs, spec)
        elif shape.kind == "prefill":
            step = tsteps.bind_mesh(tsteps.make_prefill_step(model, shape.seq), mesh)
            spec = input_specs(cfg, shape)
            shards, params_abs = tsteps.serve_shardings(
                model, mesh, jax.eval_shape(
                    lambda: model.init_cache(shape.batch, shape.seq)),
                batch_like=spec)
            jitted = jax.jit(step, in_shardings=(shards["params"], shards["batch"]),
                             out_shardings=(None, shards["cache"]))
            lowered = jitted.lower(params_abs, spec)
        else:  # decode
            step = tsteps.bind_mesh(tsteps.make_serve_step(model), mesh)
            cache_abs, tokens_abs = input_specs(cfg, shape)
            shards, params_abs = tsteps.serve_shardings(model, mesh, cache_abs)
            tok_sh = shd.named(mesh, shd.batch_specs({"tokens": tokens_abs}, mesh))["tokens"]
            jitted = jax.jit(step, in_shardings=(shards["params"], shards["cache"], tok_sh),
                             out_shardings=(None, shards["cache"]),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, tokens_abs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "status": "ok", "fsdp": fsdp,
           "devices": int(mesh.devices.size),
           "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
           "params": cfg.param_count(), "active_params": cfg.active_param_count()}
    rec.update(_analyze(compiled))
    try:
        rec["_hlo_text"] = compiled.as_text()
    except Exception:
        pass
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default=".scratch/dryrun")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = all_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for arch in archs:
        for shape in shapes:
            for m in meshes:
                cells.append((arch, shape, m == "multi"))

    failures = 0
    for arch, shape, multi in cells:
        tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
        path = os.path.join(args.out, tag + ".json")
        try:
            rec = lower_cell(arch, shape, multi, fsdp=not args.no_fsdp)
        except Exception as e:
            rec = {"arch": arch, "shape": shape,
                   "mesh": "multi" if multi else "single",
                   "status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()}
            failures += 1
        hlo = rec.pop("_hlo_text", None)
        if hlo is not None:
            with gzip.open(os.path.join(args.out, tag + ".hlo.gz"), "wt") as f:
                f.write(hlo)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (f" flops={rec.get('flops', 0):.3e}"
                     f" coll={sum(v for k, v in rec.get('collectives', {}).items() if not k.endswith('_count')):.3e}B"
                     f" compile={rec.get('compile_s')}s")
        elif status == "error":
            extra = " " + rec["error"][:200]
        print(f"[dryrun] {tag}: {status}{extra}", flush=True)
        if status == "ok" and rec.get("memory_analysis"):
            print("  " + rec["memory_analysis"].replace("\n", "\n  ")[:400], flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
