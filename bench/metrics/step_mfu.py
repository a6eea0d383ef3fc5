"""Model step, in percent: model FLOPs of the train steps the trace holds inside the
window, over those steps' device time times the chip's bf16 peak."""


def read(r: dict):
    tr = r["trace"]
    if not tr or not tr["step_count"] or not r.get("peak_flops"):
        return None
    return 100.0 * tr["step_count"] * r["flops_per_step"] / (tr["step_device_s"] * r["peak_flops"])
