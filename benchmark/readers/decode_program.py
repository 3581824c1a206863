"""A percentile, in milliseconds, over the decode programs of the traced
part of the window, each the engine's own record of it
(`paddle_tpu.observability.tracing.ring(<cell>, "programs")`: dispatch,
read, tokens, on the host's clock) paired with the device's module event
that ran it (`readers/trace_join.py`: the clocks joined by the `bench.step`
anchor). `value` says which:

- `device_ms`: the module event's duration, the program's time on the
  device. The note has the pairs, the events of that name left unpaired
  (a warm-up's, another engine's), the summed seconds, the median a live
  row (`rows` of the record), and every program of the chip by name with
  its runs and seconds.
- `handoff_ms`: the time in which host and device were both ready and the
  other side had not yet got the work: the launch, `module start - max(
  dispatch, the end of the module before it)`, plus the way back, `tokens -
  max(module end, read)`. The note has the two halves' medians, the
  median from dispatch to tokens, and what does not depend on how the
  profiler laid the device's clock on the host's (the two sessions of one
  chip differed by half a millisecond): for the programs that found the
  device idle, `tokens - dispatch` less the module's duration, both ways
  at once; how much later after the end of its dispatch span a program
  queued behind another starts than one that found the device idle, which
  is how long it lay enqueued before the device was free, the host's slack
  (less a bare launch); and the least `module start - dispatch` and `tokens
  - module end` of any pair, neither of which can be under zero, so the
  device's clock is off by no more than those two.

A program that keeps no such ring (the parent of the PR that brought it),
or a trace whose clock cannot be joined with the ring's, gives nothing."""
import numpy as np

from benchmark.readers import trace_join


def _ms(values, q=50):
    return float(np.percentile(np.asarray(values), q)) * 1e3


def _by_name(ctx):
    """`name runs seconds` of the chip's programs, most seconds first."""
    acc = {}
    for e in trace_join.modules_of(ctx):
        name = e.name.split("(")[0]
        runs, secs = acc.get(name, (0, 0.0))
        acc[name] = (runs + 1, secs + e.dur_ns / 1e9)
    return ", ".join(f"{n} {r} runs {s:.4f} s" for n, (r, s) in sorted(
        acc.items(), key=lambda kv: -kv[1][1])[:6])


def read(params, ctx):
    if not ctx.get("events"):
        return None
    pairs, unpaired, note = trace_join.programs_with_modules(
        ctx, params["modules"], params.get("owner"))
    notes = ctx.setdefault("notes", {})
    if not pairs:
        if note:
            notes[params["name"]] = f"no pairs; {note}"
        return None
    if params["value"] == "device_ms":
        device = [end - start for _, start, end, _ in pairs]
        notes[params["name"]] = (
            f"{len(pairs)} pairs, {unpaired} events unpaired, "
            f"{sum(device):.4f} s on the device, "
            f"{_ms([d / r['rows'] for d, (r, *_) in zip(device, pairs)]):.4f}"
            f" ms a live row at the median; programs of the chip: "
            f"{_by_name(ctx)}; {note}")
        return _ms(device, params["q"])
    # (the trace's first program: when the device was free is not known)
    pairs = [p for p in pairs if p[3] is not None]
    if not pairs:
        return None
    launch = [start - max(r["dispatch"], free) for r, start, _, free in pairs]
    back = [r["tokens"] - max(end, r["read"]) for r, _, end, _ in pairs]
    # apart: the programs that found the device idle, and those that lay
    # enqueued behind another
    alone = [p for p in pairs
             if not p[0]["overlapped"] and p[3] <= p[0]["dispatch"]]
    queued = [p for p in pairs if p[0]["overlapped"]]
    both_ways = [r["tokens"] - r["dispatch"] - (end - start)
                 for r, start, end, _ in alone]

    def after_dispatch(some):
        return _ms([start - r["dispatched"] for r, start, _, _ in some])
    notes[params["name"]] = (
        f"{len(pairs)} pairs; medians: launch {_ms(launch):.4f} ms, way "
        f"back {_ms(back):.4f} ms, dispatch to tokens "
        f"{_ms([r['tokens'] - r['dispatch'] for r, *_ in pairs]):.4f} ms; "
        + (f"{len(alone)} programs found the device idle: dispatch to tokens "
           f"less the module {_ms(both_ways):.4f} ms; " if alone else "")
        + ("a queued program starts "
           f"{after_dispatch(queued) - after_dispatch(alone):.4f} ms "
           "later after its dispatch than one that found the device idle; "
           if alone and queued else "")
        + "least module start less dispatch "
        f"{min(s - r['dispatch'] for r, s, _, _ in pairs) * 1e3:.4f} ms, "
        "least tokens less module end "
        f"{min(r['tokens'] - e for r, _, e, _ in pairs) * 1e3:.4f} ms; "
        f"{note}")
    return _ms(np.add(launch, back), params["q"])
