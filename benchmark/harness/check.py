"""The comparison that decides `correct`: each number compared stands
beside a limit of its own, set in the cell's file from readings on the
chip (PERF.md gives the readings)."""
from __future__ import annotations

import statistics
import sys


def worst_leaf_gap(prog: dict, ref: dict, skip=()):
    """(largest over the leaves of |prog - ref| / max(ref of that leaf,
    ref of the median leaf), the leaf): a gap between norms, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger, since some gradients are all but zero."""
    names = [n for n in ref if n not in skip]
    med = statistics.median(ref[n] for n in names)
    worst, where = 0.0, None
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if gap > worst or where is None:
            worst, where = gap, n
    return worst, where


def flat_leaves(ref_grad_norms: dict, share: float = 1e-3):
    """Leaves whose gradient is nought to rounding in the reference:
    under `share` of the median leaf's. Adam moves them by round-off
    alone, so they are left out of the change (by this rule, not by
    name)."""
    med = statistics.median(ref_grad_norms.values())
    return {n for n, g in ref_grad_norms.items() if g < share * med}


def compare_train(prog: dict, ref: dict) -> dict:
    """({number: value}, where the worst leaves are) of a training cell:
    the worst leaf's gap of the first gradient's norm and the worst leaf's
    gap of the norm of change. Each checked step's loss gap, relative to
    the reference's loss, is read too and returned apart as `seen`: it has
    no upper reading (PERF.md, §6) and is not compared."""
    out, seen = {}, {}
    for k, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        seen[f"loss{k}_gap"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], g_leaf = worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"])
    out["change_norm_gap"], c_leaf = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"],
        skip=flat_leaves(ref["grad_norms"]))
    return out, {"grad_norm_gap": g_leaf, "change_norm_gap": c_leaf,
                 "losses_not_compared": seen}


def verdict(numbers: dict, limits: dict, extra_ok: bool = True):
    """(`correct`, {name: {"value", "limit"}}); every number compared has
    to lie at or under its limit, and a number without a limit is a fault
    of the cell's file."""
    compared, ok = {}, bool(extra_ok)
    for name, value in numbers.items():
        limit = limits[name]
        compared[name] = {"value": value, "limit": limit}
        if not (value <= limit):          # NaN fails
            ok = False
    return ok, compared


def print_compared(compared: dict, notes: dict | None = None):
    """The last lines of standard error: each number beside its limit."""
    for name, c in compared.items():
        note = f"  ({notes[name]})" if notes and name in notes else ""
        sys.stderr.write(f"compared {name} = {c['value']:.6g}  limit "
                         f"{c['limit']:.6g}{note}\n")
    sys.stderr.flush()
