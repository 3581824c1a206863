#!/usr/bin/env python3
"""The spread a bound is set from: reads one file of result lines a set (one JSON
object a line, as `run.py` prints them) and prints, for every end-to-end metric, each set's median and
its spread: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.

    python3 benchmark/tools/spread.py chiprun_out/<cell>.set1.jsonl chiprun_out/<cell>.set2.jsonl
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths):
    sets = {}
    for k, path in enumerate(paths, 1):
        for line in open(path):
            line = line.strip()
            if not line.startswith("{"):
                continue
            rec = json.loads(line)
            if not rec.get("correct"):
                print(f"NOT CORRECT in {path}: {rec.get('compared')}")
            for name, m in rec.get("metrics", {}).items():
                sets.setdefault(name, {}).setdefault(k, []).append(
                    m["value"])
    for name, by_set in sorted(sets.items()):
        widest = 0.0
        for k, values in sorted(by_set.items()):
            if name == "setup_s":
                values = values[1:] if k == min(by_set) else values
            s = spread(values) if len(values) >= 2 else float("nan")
            widest = max(widest, s)
            print(f"{name:22s} set {k}: n={len(values)} median "
                  f"{statistics.median(values):.6g} spread {100 * s:.3f}% "
                  f"min {min(values):.6g} max {max(values):.6g}")
        print(f"{name:22s} widest spread {100 * widest:.3f}%  ->  bound "
              f"~{max(0.01, 5 * widest):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
