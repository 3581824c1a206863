"""A percentile, in milliseconds, over the step records that the program
itself keeps (`paddle_tpu.observability.tracing.ring(owner, "steps")`: one a
`ServeEngine.step()` under the engine's name, which the harness makes the
cell's; one a `to_static` call under `jit.<function>`), of the phases named
in `sum`, or of the whole step less the phases named in `whole_less`. The
records kept are those that end in the last `window_s` seconds before the
tracer stopped: engine, harness and tracer all read `time.perf_counter`.
A program that keeps no such ring (the parent of the PR that brought it)
gives nothing to read."""
import numpy as np


def window_records(ctx, owner, kind, key):
    """The ring's records whose `key` time lies in the window."""
    from paddle_tpu.observability import tracing

    ring = getattr(tracing, "ring", None)
    tracer = ctx.get("tracer")
    if ring is None or tracer is None or tracer.t1 is None:
        return []
    t1 = tracer.t1
    t0 = t1 - ctx["window_s"]
    return [r for r in list(ring(owner or ctx["spec"].name, kind))
            if r.get(key) is not None and t0 < r[key] <= t1]


def read(params, ctx):
    records = window_records(ctx, params.get("owner"), "steps", "end")
    if "sum" in params:
        values = [sum(r["seconds"][p] for p in params["sum"])
                  for r in records]
    else:
        values = [r["end"] - r["begin"]
                  - sum(r["seconds"][p] for p in params["whole_less"])
                  for r in records]
    if not values:
        return None
    phases = ", ".join(
        f"{p} {np.mean([r['seconds'][p] for r in records]) * 1e3:.3f}"
        for p in records[0]["seconds"])
    ctx.setdefault("notes", {})[params["name"]] = (
        f"{len(values)} records; mean ms of a step by phase: {phases}")
    return float(np.percentile(np.asarray(values), params["q"])) * 1e3
