#!/usr/bin/env python3
"""Device seconds by `jax.named_scope` for one benchmark cell, on the chip:

    chiprun -- python3 tools/scope_breakdown.py --workload <cell> --seed <n>

One traced run of the cell as `benchmark/run.py` makes it (its `run_cell`,
so nothing of the harness's loop lives here), keeping the device events its
loader read; then the cell's program once more from the harness's builder,
for the compiled texts (`ServeEngine.lowered()`, `StaticFunction.lowered()`).
`paddle_tpu.profiler.scope_seconds` joins the two, for each traced program
and for its `copy` and `fusion` instructions alone, layer indices folded
(`layer*/scatter_kv`; a latent-attention layer's are `layer*/mla/q`, `/kv`,
`/scatter_latent`, `/attn`, `/out`, with `/absorb_q` and `/absorb_o` in a
decode step and `/expand` in a prefill); for a serving cell the engine's
own counters (`tools/serve_counters.py`'s) go into the file too; the table
goes to
`chiprun_out/scope_breakdown.<cell>.json` beside the traced run's own result
line. Judges nothing.
"""
import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def by_program(events, trace):
    """{program as the trace names it: {instruction: device seconds}} of the
    first chip: an op goes to the program that was running when it began."""
    plane = trace.device_planes(events)[0]
    mods = sorted((e.start_ns, e.end_ns, e.name) for e in events
                  if e.plane == plane and e.line == trace.MODULES_LINE)
    out, j = collections.defaultdict(lambda: collections.defaultdict(float)), 0
    for e in trace.ops_of(events, plane):
        while j < len(mods) and mods[j][1] <= e.start_ns:
            j += 1
        if j < len(mods) and mods[j][0] <= e.start_ns:
            instr = e.name.strip().split(" ", 1)[0].lstrip("%")
            out[mods[j][2]][instr] += e.dur_ns / 1e9
    return out


def compiled_texts(sp, seed):
    """{label: compiled text} of the cell's programs."""
    if sp.kind == "serve":
        from benchmark.harness import serve

        engine = serve.build_engine(sp, seed)[1]
        lowered = engine.lowered(prompt_lens=[
            1 << k for k in range(3, engine.max_seq_len.bit_length())])
    else:
        from benchmark.harness import train

        prog = train.Program(sp, seed)
        float(prog.step())
        lowered = {"train step": prog.train_step.lowered()[0]}
    return {k: low.compile().as_text() for k, low in lowered.items()}


def fold(scopes):
    """`layer3/scatter_kv` -> `layer*/scatter_kv`, `(caches[3][0])` ->
    `(caches[*][*])`, summed."""
    out = collections.defaultdict(float)
    for scope, s in scopes.items():
        out[re.sub(r"(\blayers?\.?|\[)\d+\b", r"\1*", scope)] += s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def join(programs, texts):
    """{traced program: its text's label, device seconds, and these by
    scope: all of them, its `copy` and its `fusion` instructions}."""
    from paddle_tpu.profiler import scope_seconds

    module = {k: re.match(r"\s*HloModule ([\w.\-]+)", t).group(1)
              for k, t in texts.items()}
    # the instructions each text defines, found once: a 40-layer program
    # holds thousands, and a search of every text for each took hours
    defined = {k: set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", t, re.M))
               for k, t in texts.items()}
    out = {}
    for program, secs in sorted(programs.items(),
                                key=lambda kv: -sum(kv[1].values())):
        # a prefill program is told from the other buckets' by its
        # instructions: the text that holds most of the traced seconds
        held = {k: sum(s for i, s in secs.items() if i in defined[k])
                for k in texts if program.startswith(module[k])}
        if not held:
            continue
        text = texts[max(held, key=held.get)]
        out[program] = {
            "text": max(held, key=held.get), "device_s": sum(secs.values()),
            "by_scope": fold(scope_seconds(text, secs)),
            **{kind: fold(scope_seconds(text, {
                i: s for i, s in secs.items() if i.split(".")[0] == kind}))
               for kind in ("copy", "fusion")}}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench
    from benchmark.harness import common, spec, trace

    # compile-cache keys leave metadata out: a program loaded from a cache
    # that another version wrote would carry that version's scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    kept, load = [], trace.load
    trace.load = lambda path: kept.append(load(path)) or kept[-1]
    line = bench.run_cell(args.workload, args.seed, args.seconds, True)
    common.free_device()
    out = join(by_program(kept[-1], trace),
               compiled_texts(spec.Spec(args.workload), args.seed))
    for program, rep in out.items():
        total = rep["device_s"]
        print(f"\n== {program}: {total:.4f} device s, text of {rep['text']}")
        for title in ("by_scope", "copy", "fusion"):
            part = sum(rep[title].values()) or float("nan")
            print(f"  -- {title}: {part:.4f} s ({100 * part / total:.1f}%)")
            for scope, s in list(rep[title].items())[:10]:
                print(f"     {s:9.4f} s {100 * s / part:5.1f}%  {scope}")
    path = os.path.join(ROOT, "chiprun_out",
                        f"scope_breakdown.{args.workload}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    report = {"workload": args.workload, "seed": args.seed, "line": line,
              "programs": out}
    if spec.Spec(args.workload).kind == "serve":
        from tools.serve_counters import counters

        report["counters"] = counters(args.workload)
        print("\nserve counters: " + json.dumps(report["counters"]))
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"\nwritten {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
