"""Model step: device milliseconds per traced step under the ``attention``
scope, over the window's whole step executions, each instant to the
innermost operation running (device_trace)."""
from bench import timeline


def read(r: dict):
    tl = timeline.of_run(r)
    scopes = timeline.step_scopes(tl)
    if scopes is None:
        return None
    return 1e3 * scopes.get("attention", 0.0) / tl["step_count"]
