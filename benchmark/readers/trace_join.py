"""One clock for the program's records and the device trace. Not a reader:
what `decode_program.py` and `idle_in_step.py` share.

The engine's rings (`paddle_tpu.observability.tracing.ring(<cell>, "steps" |
"programs")`) hold times of `time.perf_counter`; the trace's events count
nanoseconds from the profile's start. Every `engine.step()` of a window runs
inside exactly one of the harness's `bench.step` annotations, which the
loader keeps, so each ringed step of the traced part has its own interval on
the trace's clock: `anchor` pairs them and takes the offset between the two
clocks from the pairs. With it a decode program's record (dispatch, read,
tokens) lies on the clock of the device's `XLA Modules` events, and
`programs_with_modules` pairs each with the module that ran it.

Everything is cached in `ctx["joined"]`: the readers of one run share one
anchor. A program that keeps no such records (the parent of the PR that
brought them) gives nothing to pair, and nothing here raises for it."""
import numpy as np

from benchmark.harness import trace

ANCHOR_SPAN = "bench.step"
#: above this spread of the anchor's residuals (95th less 5th percentile),
#: or under this share of steps paired, no metric of the join has a value
SPREAD_LIMIT_S = 0.2e-3
PAIRED_SHARE = 0.9


def _ring(ctx, kind, owner=None):
    from paddle_tpu.observability import tracing

    ring = getattr(tracing, "ring", None)
    if ring is None:
        return []
    return list(ring(owner or ctx["spec"].name, kind))


def traced_steps(ctx, owner=None):
    """The ringed steps that began and ended while the tracer ran (engine,
    harness and tracer all read `time.perf_counter`)."""
    tracer = ctx.get("tracer")
    if tracer is None or tracer.t0 is None or tracer.t1 is None:
        return []
    return [r for r in _ring(ctx, "steps", owner)
            if tracer.t0 <= r["begin"] and r["end"] <= tracer.t1]


def anchor(ctx, owner=None) -> dict:
    """`{"offset": seconds to add to a ring's time for the trace's, "note":
    ...}`, or `{"offset": None, "note": why not}`. The offset is the median
    over the pairs of `bench.step`'s start less the ringed step's `begin`
    (the annotation opens a few microseconds before the engine's first clock
    read, so the ring's times land that much early on the trace's clock; the
    ends' median, which errs the other way, is in the note as `bracket`)."""
    joined = ctx.setdefault("joined", {})
    if "anchor" not in joined:
        joined["anchor"] = _anchor(ctx, owner)
    return joined["anchor"]


def _anchor(ctx, owner):
    steps = traced_steps(ctx, owner)
    spans = sorted((e for e in ctx.get("events") or ()
                    if e.name == ANCHOR_SPAN
                    and not trace.DEVICE_PLANE.match(e.plane)),
                   key=lambda e: e.start_ns)
    whole, n = max(len(steps), len(spans)), min(len(steps), len(spans))
    if not n:
        return {"offset": None, "note": f"no anchor: {len(steps)} ringed "
                f"steps in the traced part, {len(spans)} {ANCHOR_SPAN}"}
    # one `bench.step` wraps one `serve.step`; where the counts differ (a
    # ring too short, host events the profiler dropped) the window's end is
    # where both still agree, and a pair that does not is left out
    pairs = list(zip(spans[-n:], steps[-n:]))
    begin = np.array([e.start_ns / 1e9 - r["begin"] for e, r in pairs])
    end = np.array([e.end_ns / 1e9 - r["end"] for e, r in pairs])
    if n != whole:
        keep = np.abs(begin - np.median(begin)) <= SPREAD_LIMIT_S
        begin, end = begin[keep], end[keep]
    offset = float(np.median(begin))
    spread = float(np.percentile(begin, 95) - np.percentile(begin, 5))
    note = (f"anchor: {len(begin)} of {whole} steps paired with "
            f"{ANCHOR_SPAN}, residual spread {spread * 1e3:.4f} ms (95th "
            f"less 5th percentile), bracket "
            f"{(float(np.median(end)) - offset) * 1e3:.4f} ms")
    if len(begin) < PAIRED_SHARE * whole:
        return {"offset": None, "note": note + ": too few pairs agree"}
    if spread > SPREAD_LIMIT_S:
        return {"offset": None, "note": note + ": the spread is over "
                f"{SPREAD_LIMIT_S * 1e3:.1f} ms"}
    return {"offset": offset, "note": note}


def first_chip(ctx):
    """(plane of the first chip, its busy intervals in seconds of the
    trace's clock: disjoint, sorted), as `idle_gaps_by_host_span` finds
    them."""
    joined = ctx.setdefault("joined", {})
    if "busy" not in joined:
        events = ctx.get("events") or ()
        plane = next(iter(trace.device_planes(events)), None)
        joined["busy"] = (plane, [
            (a / 1e9, b / 1e9) for a, b in trace.union(
                (e.start_ns, e.end_ns) for e in trace.ops_of(events, plane))])
    return joined["busy"]


def modules_of(ctx):
    """The first chip's module events (one an executed program), in start
    order."""
    joined = ctx.setdefault("joined", {})
    if "modules" not in joined:
        plane, _ = first_chip(ctx)
        joined["modules"] = sorted(
            (e for e in ctx.get("events") or ()
             if e.plane == plane and e.line == trace.MODULES_LINE),
            key=lambda e: e.start_ns)
    return joined["modules"]


def programs_with_modules(ctx, contains, owner=None):
    """`(pairs, unpaired events, note)`: the decode programs' records in
    dispatch order against the module events whose name holds one of
    `contains` in start order. Each pair is `(record, start, end, free)`:
    the module's interval in the RING's clock, and when the device was free
    for it (the end of the module before it, of whatever program). Two facts
    always hold of a program and its module, `start >= dispatch` and `end <=
    tokens`, and the device runs programs in the order of their dispatch: a
    record whose tokens were there before a module ended ran earlier (the
    trace began inside it, or before it), and a module that began before
    the next record's dispatch is nobody's here (a warm-up's, another
    engine's). No pairs and no note where the program keeps no such
    records; no pairs and the anchor's note where the clocks cannot be
    joined."""
    records = sorted((r for r in _ring(ctx, "programs", owner)
                      if r.get("kind") == "decode"),
                     key=lambda r: r["dispatch"])
    if not records:
        return None, 0, None
    joined = anchor(ctx, owner)
    offset, note = joined["offset"], joined["note"]
    if offset is None:
        return None, 0, note
    pairs, unpaired, k, free = [], 0, 0, None
    for e in modules_of(ctx):
        start, end = e.start_ns / 1e9 - offset, e.end_ns / 1e9 - offset
        if any(c in e.name for c in contains):
            while k < len(records) and records[k]["tokens"] < end:
                k += 1
            if k < len(records) and records[k]["dispatch"] <= start:
                pairs.append((records[k], start, end, free))
                k += 1
            else:
                unpaired += 1
        free = end
    return pairs, unpaired, note
