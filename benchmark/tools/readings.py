#!/usr/bin/env python3
"""The readings that a cell's limits are set from, taken on the chip at the
cell's own size, many seeds in one process (set-up is long):

    python3 benchmark/tools/readings.py --workload <cell> --seeds 11 12 ... \\
        [--control-seeds 11 12 13] [--fault-seeds 11 12 13] [--seconds 12]

- program against reference on every seed of `--seeds` (the lower reading);
- the control, the reference in float8 put in the program's place, on
  `--control-seeds` (the upper reading);
- training only: the fault `half_batch` planted in the reference put in the
  program's place, on `--fault-seeds`.

Appends one JSON line a reading to `chiprun_out/readings_<cell>.jsonl`. The
benchmark's own runs never run this."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from benchmark.harness import check, common, peaks, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()
    sp = spec.Spec(args.workload)
    device = peaks.require_device(sp.chips)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"readings_{sp.name}.jsonl"), "a")

    def emit(**rec):
        rec["workload"] = sp.name
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    if sp.kind == "train":
        from benchmark.harness import train

        common.setup_program_cache()
        refs = {}

        def ref_of(seed):
            if seed not in refs:
                refs[seed] = train.reference_readings(sp, seed)
            return refs[seed]

        for seed in args.seeds:
            t = time.perf_counter()
            prog = train.Program(sp, seed)
            got = train.checked_steps(prog)
            prog.free()
            numbers, where = check.compare_train(got, ref_of(seed))
            emit(kind="program", seed=seed, numbers=numbers, where=where,
                 losses=got["losses"], ref_losses=ref_of(seed)["losses"],
                 seconds=time.perf_counter() - t)
        for seed in args.control_seeds:
            got = train.reference_readings(sp, seed, precision="fp8")
            numbers, where = check.compare_train(got, ref_of(seed))
            emit(kind="control_fp8", seed=seed, numbers=numbers, where=where)
        for seed in args.fault_seeds:
            got = train.reference_readings(sp, seed, fault="half_batch")
            numbers, where = check.compare_train(got, ref_of(seed))
            emit(kind="fault_half_batch", seed=seed, numbers=numbers,
                 where=where)
    else:
        from benchmark.harness import serve

        for seed in dict.fromkeys(args.seeds + args.control_seeds):
            t = time.perf_counter()
            res = serve.run(sp, seed, args.seconds, False, device, t,
                            controls=("fp8",) if seed in args.control_seeds
                            else ())
            emit(kind="program", seed=seed, correct=res["correct"],
                 numbers={k: v["value"] for k, v in res["compared"].items()},
                 control=res["control"], notes=res["notes"],
                 end_to_end=res["end_to_end"],
                 seconds=time.perf_counter() - t)
            common.free_device()
    return 0


if __name__ == "__main__":
    sys.exit(main())
