"""Share of the peak that the named programs reach while they run: model
FLOPs of the prompt tokens prefilled in the traced part of the window
(true lengths, not buckets) over the device time of the programs whose
name holds one of `modules`."""
from benchmark.harness import trace


def read(params, ctx):
    events, traced = ctx.get("events"), ctx.get("traced")
    if not events or not traced or not traced["prefill_flops"]:
        return None
    secs, runs = trace.module_seconds(events, params["modules"])
    if secs <= 0:
        return None
    ctx.setdefault("notes", {})[params["name"]] = (
        f"{runs} program runs, {traced['prefills']} prefills counted")
    return 100.0 * traced["prefill_flops"] / (
        secs * ctx["peaks"]["bf16_flops_per_s"])
