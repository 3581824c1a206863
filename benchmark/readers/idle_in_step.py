"""Share of the traced window in which no operation ran on the first chip
WHILE the engine was inside `step()`, and in the note what the engine was
doing then: the idle seconds by phase of the step, and the idle seconds
outside every step (no request, or the harness's own work between two
steps). This metric plus the outside's share is `device.idle_pct.*`.

The steps are the engine's own records (`paddle_tpu.observability.tracing
.ring(<cell>, "steps")`), whose `spans` are the phases' intervals `(phase,
start, end)` on the host's clock, joined with the trace's by the `bench.step`
anchor (`readers/trace_join.py`). An idle stretch is given to the spans by
the seconds each covers of it, innermost first (a prompt's dispatch lies
inside its admission and is the prefill's, not the admission's), and what
no span of the step covers is `other`: never whole to the one span that
covers most, as `breakdown.idle_gaps` gives it. A program whose steps keep
no `spans` (the parent of the PR that brought them) gives nothing."""
import numpy as np

from benchmark.harness import trace
from benchmark.readers import trace_join


def phase_segments(record):
    """[(phase, a, b)], disjoint, covering the step: each span's interval
    less the shorter spans inside it, and `other` for the rest."""
    out, covered = [], []
    for phase, a, b in sorted(record["spans"], key=lambda s: s[2] - s[1]):
        out += [(phase, x, y) for x, y in trace.subtract([(a, b)], covered)]
        covered = trace.union(covered + [(a, b)])
    out += [("other", x, y) for x, y in trace.subtract(
        [(record["begin"], record["end"])], covered)]
    return out


def busy_inside(busy, a, b):
    """Seconds of the disjoint sorted intervals `busy` inside each `[a[i],
    b[i]]`."""
    if not busy:
        return np.zeros(len(a))
    lo, hi = np.array(busy).T
    before = np.concatenate([[0.0], np.cumsum(hi - lo)])

    def upto(t):
        j = np.maximum(np.searchsorted(lo, t, side="right") - 1, 0)
        return before[j] + np.clip(t - lo[j], 0.0, hi[j] - lo[j])
    return upto(np.asarray(b, float)) - upto(np.asarray(a, float))


def read(params, ctx):
    if not ctx.get("events") or not ctx.get("trace_window_s"):
        return None
    steps = [r for r in trace_join.traced_steps(ctx, params.get("owner"))
             if "spans" in r]
    if not steps:
        return None
    anchor = trace_join.anchor(ctx, params.get("owner"))
    notes = ctx.setdefault("notes", {})
    if anchor["offset"] is None:
        notes[params["name"]] = anchor["note"]
        return None
    _, busy = trace_join.first_chip(ctx)
    tracer, off = ctx["tracer"], anchor["offset"]
    segments = [s for r in steps for s in phase_segments(r)]
    a = np.array([s[1] for s in segments]) + off
    b = np.array([s[2] for s in segments]) + off
    idle = (b - a) - busy_inside(busy, a, b)
    by_phase = {}
    for (phase, _, _), seconds in zip(segments, idle):
        by_phase[phase] = by_phase.get(phase, 0.0) + float(seconds)
    window = (tracer.t0 + off, tracer.t1 + off)
    all_idle = (window[1] - window[0]) - float(
        busy_inside(busy, [window[0]], [window[1]])[0])
    inside = sum(by_phase.values())
    notes[params["name"]] = (
        f"{inside:.4f} s idle inside {len(steps)} steps: " + ", ".join(
            f"{p} {s:.4f}" for p, s in sorted(by_phase.items(),
                                              key=lambda kv: -kv[1]))
        + f"; {all_idle - inside:.4f} s idle outside every step "
        f"({100.0 * (all_idle - inside) / ctx['trace_window_s']:.2f}% of "
        f"the window); the steps hold {float((b - a).sum()):.4f} s; "
        f"{anchor['note']}")
    return 100.0 * inside / ctx["trace_window_s"]
