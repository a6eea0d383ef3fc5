"""Model step: device milliseconds per traced step under the ``mlp`` scope
(a dense layer's SwiGLU; an expert layer's router, dispatch, held experts,
combine and shared experts), over the window's whole step executions, each
instant to the innermost operation running (device_trace)."""
from bench import timeline


def read(r: dict):
    tl = timeline.of_run(r)
    scopes = timeline.step_scopes(tl)
    if scopes is None:
        return None
    return 1e3 * scopes.get("mlp", 0.0) / tl["step_count"]
