"""Device, in percent: device-idle time in the window that no foreground
program span covers, over all device-idle time (device_trace)."""
from bench import timeline


def read(r: dict):
    tl = timeline.of_run(r)
    if not tl or not tl["idle_s"] or not tl["spans"]:
        return None
    return 100.0 * tl["idle"].get(timeline.NONE, 0.0) / tl["idle_s"]
