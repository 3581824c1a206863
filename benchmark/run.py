#!/usr/bin/env python3
"""One cell, once, in a new process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the model with weights made on the device from `--seed`, warms up
only that cell's shapes through the compile cache, measures for
`--seconds`, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard output.
A run that finds no TPU, fewer chips than the cell asks for or a
`device_kind` that `harness/peaks.py` lacks exits with code 3 and prints no
result; nothing falls back.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(workload: str, seed: int, seconds: float, trace_on: bool, *,
             device: dict | None = None, t_start: float | None = None,
             bench_dir: str | None = None, manifest: dict | None = None):
    """Drive one cell and return the result line as a dict. `device` is
    given only by the benchmark's own tests, which skip the look for a
    chip and drive the rest of a run at a small size."""
    from benchmark.harness import check, peaks, spec, trace

    kw = {} if bench_dir is None else {"bench_dir": bench_dir}
    sp = spec.Spec(workload, manifest=manifest, **kw)
    if device is None:
        device = peaks.require_device(sp.chips)
    driver = spec.load_by_name("harness", sp.kind)
    res = driver.run(sp, int(seed), float(seconds), bool(trace_on), device,
                     T_START if t_start is None else t_start)
    ctx = res["ctx"]
    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if not trace_on:
        units = {m["name"]: m["unit"] for m in sp.end_to_end()}
        line["metrics"] = {n: {"value": res["end_to_end"][n], "unit": u}
                           for n, u in units.items()
                           if res["end_to_end"].get(n) is not None}
    else:
        tracer = ctx["tracer"]
        events = tracer.events()
        ctx["events"] = events
        ctx["trace_window_s"] = tracer.window_s
        ctx["busy_s"] = trace.busy_seconds(events)
        metrics = {}
        for entry, params in sp.per_layer():
            reader = spec.load_by_name("readers", params["reader"])
            value = reader.read(dict(params, name=entry["name"]), ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        line["metrics"] = metrics
        dev.update(busy_s=ctx["busy_s"], window_s=tracer.window_s)
        line["breakdown"] = {
            "device_ops": trace.top_device_ops(events),
            "idle_gaps": trace.idle_gaps_by_host_span(events)}
    line["device"] = dev
    notes = dict(res.get("notes") or {}, **ctx.get("notes", {}))
    if notes:
        line["notes"] = notes
    line["compared"] = res["compared"]
    check.print_compared(res["compared"], res.get("notes"))
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
