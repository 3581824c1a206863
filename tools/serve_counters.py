#!/usr/bin/env python3
"""One benchmark cell as `benchmark/run.py` runs it, then the serving
engine's own counters of that run, on the chip:

    chiprun -- python3 tools/serve_counters.py --workload <cell> --seed <n> \
        --seconds 50 --trace 0

The arguments are `benchmark/run.py`'s and go to its `main` untouched: the
result line is still the last line of standard output. The counters (the
registry's series under the cell's name, which the harness gives its
engine: warm-up, ramp, window and drain together) go to standard error as
one JSON object: decode programs dispatched, how many of them went out
while the one before was unread, the share of them that found the device's
copy of the slot state, of the tables and of the temperatures good and
sent none up (`clean_share`: 1 - `serve.decode_uploads{what}` over the
programs), the pipeline's drains by reason, preemptions; for a model with
latent-attention layers the pools' bytes, the rows written into them and
the rows `mla_decode` read (`serve.mla_ctx_tokens`, and their mean a
decode step: the context the window really held) beside the pages its
products ran over (`serve.mla_pages_computed`; `computed_over_read` is
those pages' rows over the rows read: 1 plus half a page a stream where
the kernel computes what a stream holds); for a model with sparse
layers the tokens routed, the assignments that fell on held experts and,
under a group-limited router, the tokens whose kept groups include the
held one (3/8 in expectation for one group of eight, three kept). A
checkout from before a counter has it, and a model without such layers,
prints null there.
Then, from the engine's step ring (its last 16,384 steps), the five longest
steps with their seconds by phase and the five longest gaps between two
steps (the caller's time), and from its ring of programs the percentiles of
a decode program's time from dispatch to tokens and the five longest
programs of either kind, each with the step that dispatched it and the step
that read it: where a one-off stall of seconds lies (ROADMAP S8), if the
run held one, and whether a program or the host held it. Judges nothing.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def counters(engine: str, block_size=None) -> dict:
    from paddle_tpu import observability as obs

    def value(name, **labels):
        m = obs.registry.get(name)
        return None if m is None else m.value(engine=engine, **labels)

    steps = value("serve.decode_steps")
    overlapped = value("serve.decode_overlapped")
    uploads = {w: value("serve.decode_uploads", what=w)
               for w in ("state", "tables", "temps")}
    return {
        "engine": engine, "decode_steps": steps,
        "decode_overlapped": overlapped,
        "overlapped_share": (overlapped / steps
                             if steps and overlapped is not None else None),
        "decode_uploads": uploads,
        "clean_share": {w: (round(1 - n / steps, 4)
                            if steps and n is not None else None)
                        for w, n in uploads.items()},
        "pipeline_drains": {r: value("serve.pipeline_drains", reason=r)
                            for r in ("preempt", "burst", "idle")},
        "preemptions": value("serve.preemptions", reason="pool_exhausted"),
        "requests_finished": value("serve.requests_finished",
                                   reason="max_new_tokens"),
        "latent": latent(value, steps, block_size),
        "moe": moe(value),
    }


def _share(part, whole):
    return round(part / whole, 4) if part is not None and whole else None


def latent(value, steps, block_size=None) -> dict:
    """The latent-attention layers' cache: bytes held, rows written, rows
    read and pages computed (over the rows read, where the caller knows
    the rows of a page)."""
    ctx = value("serve.mla_ctx_tokens")
    pages = value("serve.mla_pages_computed")
    return {"cache_bytes": value("serve.latent_cache_bytes"),
            "rows_written": value("serve.latent_rows_written"),
            "mla_ctx_tokens": ctx,
            "ctx_tokens_a_step": (round(ctx / steps, 1)
                                  if ctx is not None and steps else None),
            "mla_pages_computed": pages,
            "computed_over_read": (_share(pages * block_size, ctx)
                                   if pages is not None and block_size
                                   else None)}


def moe(value) -> dict:
    """Where the sparse layers' tokens went."""
    routed = value("serve.moe_tokens_routed")
    held = value("serve.moe_assignments_held")
    group = value("serve.moe_tokens_to_held_group")
    return {"tokens_routed": routed, "assignments_held": held,
            "assignments_held_a_token": _share(held, routed),
            "tokens_to_held_group": group,
            "held_group_share": _share(group, routed)}


def _block_size(cell: str):
    """Rows of a cache page in the cell's engine, or nothing."""
    try:
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               cell + ".json")) as f:
            return json.load(f).get("engine", {}).get("block_size")
    except OSError:
        return None


def slowest(engine: str, k: int = 5) -> dict:
    """The ring's ``k`` longest steps and longest gaps between steps, in
    milliseconds, each with when it began (seconds before the ring's end)."""
    from paddle_tpu.observability import tracing

    steps = list(tracing.ring(engine, "steps"))
    if not steps:
        return {}
    t1 = steps[-1]["end"]
    by_len = sorted(steps, key=lambda r: r["end"] - r["begin"])[-k:]
    gaps = sorted(zip(steps, steps[1:]),
                  key=lambda ab: ab[1]["begin"] - ab[0]["end"])[-k:]
    return {
        "steps_in_ring": len(steps),
        "longest_steps": [
            {"step": r.get("step"),
             "before_end_s": round(t1 - r["begin"], 3),
             "ms": round((r["end"] - r["begin"]) * 1e3, 2),
             "phases_ms": {p: round(v * 1e3, 2)
                           for p, v in r["seconds"].items() if v > 5e-4}}
            for r in reversed(by_len)],
        "longest_gaps": [
            {"before_end_s": round(t1 - a["end"], 3),
             "ms": round((b["begin"] - a["end"]) * 1e3, 2)}
            for a, b in reversed(gaps)]}


def programs(engine: str, k: int = 5) -> dict:
    """Of the ring's decode programs the percentiles of dispatch-to-tokens
    in milliseconds, and the ``k`` longest programs of either kind (a
    prompt's runs from its dispatch to its first token) with the steps that
    dispatched and read them; each step's own record is in ``slowest``'s
    list if the step was long too."""
    import numpy as np

    from paddle_tpu.observability import tracing

    def whole(r):
        end = r["tokens"] if r["kind"] == "decode" else r["tokens_at"]
        return (end if end is not None else r["dispatched"]) - r["dispatch"]

    ring = list(tracing.ring(engine, "programs"))
    decodes = [whole(r) * 1e3 for r in ring if r["kind"] == "decode"]
    if not decodes:
        return {}
    t1 = max(r["dispatched"] for r in ring)
    return {
        "programs_in_ring": len(ring), "decode_programs": len(decodes),
        "dispatch_to_tokens_ms": {
            f"p{q}": round(float(np.percentile(decodes, q)), 3)
            for q in (5, 50, 95, 99, 100)},
        "overlapped_share": round(sum(
            r["overlapped"] for r in ring if r["kind"] == "decode")
            / len(decodes), 4),
        "longest_programs": [
            {"kind": r["kind"], "ms": round(whole(r) * 1e3, 2),
             "before_end_s": round(t1 - r["dispatch"], 3),
             "step": r["step"], "read_step": r.get("read_step", r["step"]),
             **{key: r[key] for key in ("rows", "ticks", "request", "bucket")
                if key in r},
             "dispatch_ms": round((r["dispatched"] - r["dispatch"]) * 1e3,
                                  2)}
            for r in sorted(ring, key=whole, reverse=True)[:k]]}


def main(argv=None):
    from benchmark import run

    argv = sys.argv[1:] if argv is None else argv
    rc = run.main(argv)
    cell = argv[argv.index("--workload") + 1]
    print("serve counters: " + json.dumps(counters(cell, _block_size(cell))),
          file=sys.stderr, flush=True)
    print("serve slowest: " + json.dumps(slowest(cell)), file=sys.stderr,
          flush=True)
    print("serve programs: " + json.dumps(programs(cell)), file=sys.stderr,
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
