"""Expert layer: over the window's steps, the rows of the busiest held
expert (of any expert layer) over the mean rows per held expert, from the
counters ``moe_load_max`` and ``moe_rows`` that the program sets on each
``train.step_us`` span (program_counter).  1 is an even load; dropless
dispatch computes every row however uneven."""
from bench import moe_trace, timeline


def read(r: dict):
    tl = timeline.of_run(r)
    step = ((tl or {}).get("spans") or {}).get("train.step_us")
    run = moe_trace.run_of(r)
    if not step or not step["args"].get("moe_rows") or "moe_load_max" not in step["args"] \
            or run is None:
        return None
    m = run.config["model"]
    slots = m["experts_held"] * (m["num_hidden_layers"] - m["first_k_dense_replace"])
    return step["args"]["moe_load_max"] * slots / step["args"]["moe_rows"]
