"""From the profiler's trace to numbers: the benchmark's own reduction.

`load` reads an `.xplane.pb` with `jax.profiler.ProfileData` (nothing of the
program's `profiler/xplane.py`) into plain `Event`s; everything below works
on lists of `Event`s, so tests feed it hand-made ones.

Planes of a TPU trace: one `/device:TPU:<n>` per chip, whose line
`XLA Ops` holds one event per executed HLO instruction (a Pallas kernel
under its `pallas_call(name=)`) and whose line `XLA Modules` holds one per
executed program; `/host:CPU` holds the host threads, where the harness's
own `jax.profiler.TraceAnnotation`s (`bench.*`) appear by name.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, NamedTuple, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> List[Event]:
    """Device events of the op and module lines, and the harness's host
    spans; everything else in the file is dropped here."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(HOST_SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def instr_name(event_name: str) -> str:
    """`%flash_fwd.3 = bf16[...] custom-call(...)` -> `flash_fwd`: an op
    event is named by its HLO text; the instruction's name is its first
    word, less the `%` and the `.N`, `.remat`, `.clone` that XLA appends."""
    word = event_name.strip().split(" ", 1)[0].lstrip("%")
    return word.split(".", 1)[0] or event_name


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)},
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def ops_of(events: Iterable[Event], plane: str) -> List[Event]:
    return sorted((e for e in events
                   if e.plane == plane and e.line == OPS_LINE),
                  key=lambda e: e.start_ns)


def union(intervals: Iterable[Tuple[float, float]]):
    """Sorted disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a_ints, b_ints):
    """Points of the disjoint sorted `a_ints` not in disjoint sorted
    `b_ints`."""
    out, j = [], 0
    for a, b in a_ints:
        cur = a
        while j < len(b_ints) and b_ints[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_ints) and b_ints[k][0] < b:
            if b_ints[k][0] > cur:
                out.append((cur, b_ints[k][0]))
            cur = max(cur, b_ints[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def busy_seconds(events: Sequence[Event]) -> float:
    """Seconds in which an operation ran on a device: the union of the op
    intervals of each chip, averaged over the chips in the trace."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        total += length(union((e.start_ns, e.end_ns)
                              for e in ops_of(events, p)))
    return total / len(planes) / 1e9


def kernel_seconds(events: Sequence[Event], names: Sequence[str]):
    """({kernel name: device seconds, averaged over chips}, {name: calls
    on one chip}) of the op events whose instruction is one of `names`."""
    planes = device_planes(events)
    secs = {n: 0.0 for n in names}
    calls = {n: 0 for n in names}
    for e in events:
        if e.line != OPS_LINE or e.plane not in planes:
            continue
        n = instr_name(e.name)
        if n in secs:
            secs[n] += e.dur_ns / 1e9 / len(planes)
            if e.plane == planes[0]:
                calls[n] += 1
    return secs, calls


def module_seconds(events: Sequence[Event], contains: Sequence[str]):
    """(device seconds averaged over chips, executions on one chip) of the
    programs whose name holds one of `contains`."""
    planes = device_planes(events)
    secs, runs = 0.0, 0
    for e in events:
        if e.line == MODULES_LINE and e.plane in planes \
                and any(c in e.name for c in contains):
            secs += e.dur_ns / 1e9 / len(planes)
            runs += e.plane == planes[0]
    return secs, runs


def top_device_ops(events: Sequence[Event], k: int = 10):
    """[[instruction, seconds]] of the op names that took most device time
    (averaged over chips)."""
    planes = device_planes(events)
    acc = {}
    for e in events:
        if e.line == OPS_LINE and e.plane in planes:
            n = instr_name(e.name)
            acc[n] = acc.get(n, 0.0) + e.dur_ns / 1e9 / len(planes)
    return [[n, s] for n, s in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:k]]


def idle_gaps_by_host_span(events: Sequence[Event], k: int = 10):
    """[[host span, seconds]]: the first chip's idle time between its
    first and last operation, each gap given to the harness span (`bench.*`)
    that covers most of it, `(no span)` where none does; longest first."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = union((e.start_ns, e.end_ns) for e in ops_of(events, planes[0]))
    if not busy:
        return []
    gaps = subtract([(busy[0][0], busy[-1][1])], busy)
    spans = sorted(((e.start_ns, e.end_ns, e.name) for e in events
                    if e.name.startswith(HOST_SPAN_PREFIX)
                    and not DEVICE_PLANE.match(e.plane)))
    acc, j = {}, 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        best, cover, i = "(no span)", 0.0, j
        while i < len(spans) and spans[i][0] < b:
            c = min(b, spans[i][1]) - max(a, spans[i][0])
            if c > cover:
                best, cover = spans[i][2], c
            i += 1
        acc[best] = acc.get(best, 0.0) + (b - a) / 1e9
    return [[n, s] for n, s in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:k]]
