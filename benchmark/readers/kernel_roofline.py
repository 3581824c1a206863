"""A kernel's share of its roofline: the least time the chip could take
for the work the algorithm needs over the device time of the kernel's
events in the trace, found by the `pallas_call(name=)` names in `kernels`.
`work` names the count, a file of its own: `benchmark/work/<work>.py`,
whose `work(params, ctx, calls)` gives (flops, bytes) from shapes, or
nothing. A trace without the kernel's events gives nothing (never 0)."""
from benchmark.harness import counts, spec, trace


def read(params, ctx):
    events = ctx.get("events")
    if not events:
        return None
    secs, calls = trace.kernel_seconds(events, params["kernels"])
    total = sum(secs.values())
    if total <= 0 or not calls.get(params["count_by"]):
        return None
    work = spec.load_by_name("work", params["work"]).work(params, ctx, calls)
    if work is None:
        return None
    least, bound = counts.roofline_seconds(*work, ctx["peaks"])
    ctx.setdefault("notes", {})[params["name"]] = (
        f"{bound}-bound; " + ", ".join(
            f"{k} {calls[k]} calls {secs[k]:.4f} s" for k in secs))
    return 100.0 * least / total
