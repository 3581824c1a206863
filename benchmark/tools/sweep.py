#!/usr/bin/env python3
"""Finds the knee of an open-loop cell once, by a sweep on the chip: the
cell's own engine and traffic at each of `--rates`, one after another in
one process. The highest rate with no growing backlog is the knee; the
cell's file then gets four fifths of it as a number. The benchmark's own
runs never search.

    python3 benchmark/tools/sweep.py --workload <cell> --rates 8 10 12 --seconds 20
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from benchmark.harness import common, peaks, serve, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=77)
    args = ap.parse_args()
    sp = spec.Spec(args.workload)
    device = peaks.require_device(sp.chips)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"sweep_{sp.name}.jsonl"), "a") as out:
        for rate in args.rates:
            sp.cell = dict(sp.cell, rate_per_s=rate)
            res = serve.run(sp, args.seed, args.seconds, False, device,
                            time.perf_counter())
            rec = {"rate_per_s": rate, "attempted": res["attempted"],
                   "failed": res["failed"], "backlog": res["backlog"],
                   "answered_per_s": (res["attempted"] - res["backlog"])
                   / res["ctx"]["window_s"],
                   **{k: v for k, v in res["end_to_end"].items()
                      if k != "setup_s"},
                   "late": res["ctx"]["late"][-1] if res["ctx"]["late"]
                   else None,
                   "step_ms_p50": 1e3 * sorted(res["ctx"]["spans"]["step"])[
                       len(res["ctx"]["spans"]["step"]) // 2],
                   "gap": res["compared"]["served_logit_gap"]["value"]}
            line = json.dumps(rec)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
            del res
            common.free_device()
    return 0


if __name__ == "__main__":
    sys.exit(main())
