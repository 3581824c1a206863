"""Summary statistics over collected host events.

Reference parity: python/paddle/profiler/profiler_statistic.py (summary
tables by event type / name: calls, total, avg, max, min, ratio).
"""
from __future__ import annotations

from typing import Dict, List

from .host_tracer import HostEvent, flatten_events


class _Item:
    __slots__ = ("name", "calls", "total_ns", "max_ns", "min_ns")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.total_ns = 0
        self.max_ns = 0
        self.min_ns = None

    def add(self, ns: int):
        self.calls += 1
        self.total_ns += ns
        self.max_ns = max(self.max_ns, ns)
        self.min_ns = ns if self.min_ns is None else min(self.min_ns, ns)

    @property
    def avg_ns(self):
        return self.total_ns / self.calls if self.calls else 0.0


def collect_statistic(roots: List[HostEvent]) -> Dict[str, _Item]:
    items: Dict[str, _Item] = {}
    for ev in flatten_events(roots):
        it = items.setdefault(ev.name, _Item(ev.name))
        it.add(ev.duration_ns)
    return items


def _fmt_ms(ns) -> str:
    return f"{ns / 1e6:.3f}"


def summary_table(roots: List[HostEvent], sorted_by: str = "total",
                  time_unit: str = "ms") -> str:
    items = sorted(collect_statistic(roots).values(),
                   key=lambda it: -it.total_ns if sorted_by == "total"
                   else -it.avg_ns)
    wall = sum(r.duration_ns for r in roots) or 1
    header = (f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
              f"{'Max(ms)':>10}{'Min(ms)':>10}{'Ratio(%)':>10}")
    lines = ["-" * len(header), header, "-" * len(header)]
    for it in items:
        lines.append(
            f"{it.name[:39]:<40}{it.calls:>8}{_fmt_ms(it.total_ns):>12}"
            f"{_fmt_ms(it.avg_ns):>10}{_fmt_ms(it.max_ns):>10}"
            f"{_fmt_ms(it.min_ns or 0):>10}{100.0 * it.total_ns / wall:>10.2f}")
    lines.append("-" * len(header))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Device (XLA op) statistics over the xplane-decoded chrome events the
# profiler exports (reference: profiler_statistic.py's kernel/op summary
# tables — there fed by CUPTI kernel records, here by the TPU xplane).
# ---------------------------------------------------------------------------

#: chrome-trace lanes that carry actual op executions (xplane.py emits
#: async DMA lanes and step/module framing lanes alongside)
_OP_LANES = ("XLA Ops",)


def op_class(base_name: str) -> str:
    """Map an HLO op base name to a coarse class for the overview table."""
    n = base_name.lower()
    if "convolution" in n:
        return "convolution"
    if "dot" in n or "matmul" in n or "gemm" in n:
        return "matmul"
    if (n.startswith(("_", "flash_", "rms_norm_", "paged_"))
            or "custom-call" in n):
        # the repo's kernels carry their pallas_call name= as the HLO
        # instruction name (flash_fwd.3, rms_norm_bwd.1, paged_decode.2)
        return "custom-call (pallas)"
    if n.startswith(("copy", "slice", "async-copy", "dynamic-slice",
                     "dynamic-update-slice", "bitcast", "transpose",
                     "reshape")):
        return "data-movement"
    if "fusion" in n:
        return "fusion"
    if n.startswith(("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all")):
        return "collective"
    return "other"


def _base_name(name: str) -> str:
    # HLO instruction names are <op>.<id>; strip the numeric id so all
    # instances of one op aggregate (fusion.1, fusion.42 -> fusion)
    head, _, tail = name.rpartition(".")
    if head and tail.isdigit():
        return head
    return name


def collect_device_statistic(trace_events, by: str = "op",
                             lanes=_OP_LANES) -> Dict[str, _Item]:
    """Aggregate exported chrome events with cat == 'device'.

    by='op' groups HLO base names; by='class' groups op_class buckets.
    Durations in the chrome export are microseconds; items store ns so
    the host/device tables share formatting.
    """
    items: Dict[str, _Item] = {}
    for ev in trace_events:
        if not isinstance(ev, dict) or ev.get("cat") != "device":
            continue
        if lanes is not None and ev.get("tid") not in lanes:
            continue
        base = _base_name(str(ev.get("name", "")))
        key = op_class(base) if by == "class" else base
        it = items.setdefault(key, _Item(key))
        it.add(int(float(ev.get("dur", 0.0)) * 1e3))
    return items


def device_summary_table(trace_events, sorted_by: str = "total",
                         by: str = "op", top: int = 30) -> str:
    """Per-op device-time table (the kernel summary of the reference)."""
    items = sorted(collect_device_statistic(trace_events, by=by).values(),
                   key=lambda it: -it.total_ns if sorted_by == "total"
                   else -it.avg_ns)
    wall = sum(it.total_ns for it in items) or 1
    title = "Device (XLA op) Summary" if by == "op" \
        else "Device Op-Class Summary"
    header = (f"{'Op':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
              f"{'Max(ms)':>10}{'Min(ms)':>10}{'Ratio(%)':>10}")
    lines = [title, "-" * len(header), header, "-" * len(header)]
    for it in items[:top]:
        lines.append(
            f"{it.name[:39]:<40}{it.calls:>8}{_fmt_ms(it.total_ns):>12}"
            f"{_fmt_ms(it.avg_ns):>10}{_fmt_ms(it.max_ns):>10}"
            f"{_fmt_ms(it.min_ns or 0):>10}{100.0 * it.total_ns / wall:>10.2f}")
    lines.append("-" * len(header))
    return "\n".join(lines)


def statistic_from_trace(path: str, by: str = "op") -> Dict[str, _Item]:
    """Per-op device statistics from a saved chrome trace (the file
    ``Profiler.export`` / bench.py write)."""
    import json

    with open(path) as f:
        d = json.load(f)
    evs = d.get("traceEvents", d) if isinstance(d, dict) else d
    return collect_device_statistic(evs, by=by)
