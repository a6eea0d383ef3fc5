"""Expert layer, in percent: the held experts' grouped matmuls (forward and
backward) against their roofline.  The least time the chip could take for
a step's rows, max(FLOPs / bf16 peak, bytes / HBM bandwidth), with FLOPs
and bytes from the reference's ``expert_flops`` and ``expert_bytes`` over
the mean ``moe_rows`` of the window's steps (the program's counter on
``train.step_us``), over the device seconds a step spends in the
operations under the program's ``experts`` scope (device_trace)."""
from bench import moe_trace, timeline


def read(r: dict):
    tl = timeline.of_run(r)
    step = ((tl or {}).get("spans") or {}).get("train.step_us")
    run = moe_trace.run_of(r)
    if not step or not step["args"].get("moe_rows") or run is None \
            or run.device["platform"] == "cpu":
        return None
    seconds, steps = moe_trace.scope_seconds(run.trace_dir, "experts")
    if not seconds or not steps:
        return None
    rows = step["args"]["moe_rows"] / step["count"]
    m, peak = run.config["model"], moe_trace.peaks(run.device["kind"])
    least = max(run.ref.expert_flops(m, rows) / peak["bf16_flops"],
                run.ref.expert_bytes(m, rows) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
