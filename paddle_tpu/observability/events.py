"""Structured span events riding the profiler's host-tracer timeline.

Two complementary records per interesting runtime moment:

- a structured :class:`Event` (kind + JSON-serializable fields + unix
  timestamp) appended to a bounded ring buffer, exported by
  ``observability.dump()``;
- a ``profiler.RecordEvent`` host span, so the same moment lands in the
  Chrome-trace timeline (and, under an active device capture, as a
  ``jax.profiler.TraceAnnotation`` next to the XLA xplane lanes) —
  one timeline for host spans, device ops and observability events.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional

from ..profiler.utils import RecordEvent
from . import _gate, flight
from .metrics import Histogram

#: ring-buffer capacity; read once from core.flags at first use so the
#: flag can be set before any event is emitted.
_MAX_EVENTS_FLAG = "observability_max_events"

_events: Optional[collections.deque] = None


def _buffer() -> collections.deque:
    global _events
    if _events is None:
        from ..core import flags

        try:
            maxlen = int(flags.get_flag(_MAX_EVENTS_FLAG))
        except KeyError:
            maxlen = 4096
        _events = collections.deque(maxlen=max(1, maxlen))
    return _events


class Event:
    __slots__ = ("ts", "kind", "fields")

    def __init__(self, kind: str, fields: Dict[str, Any]):
        self.ts = time.time()
        self.kind = kind
        self.fields = fields

    def to_dict(self) -> Dict[str, Any]:
        return {"ts": self.ts, "kind": self.kind, **self.fields}


def emit(kind: str, **fields):
    """Record a structured event (no-op while observability is off).

    The event lands in two rings: the large export buffer read by
    ``observability.dump()`` and the smaller flight-recorder ring that
    survives into crash dumps (see ``observability.flight``)."""
    if not _gate.state.on:
        return
    ev = Event(kind, fields)
    _buffer().append(ev)
    flight.recorder.record(kind, fields, ts=ev.ts)


def events(kind: Optional[str] = None) -> List[Event]:
    evs = list(_buffer())
    if kind is not None:
        evs = [e for e in evs if e.kind == kind]
    return evs


def clear():
    _buffer().clear()


def ring_len() -> int:
    """Events currently buffered (0 when the ring was never created) —
    probed by observability/timeseries.py as a host-side leak series."""
    return len(_events) if _events is not None else 0


class span:
    """Context manager bracketing a named runtime moment.

    Always opens a ``profiler.RecordEvent`` carrying ``fields`` as its
    attributes (so the moment shows up in any live host/device trace,
    whoever started it), and takes ONE pair of clock reads: ``start``,
    ``end`` and ``seconds`` are what every consumer of the moment is fed
    from — the caller's step record and always-on histograms after the
    block, and, when observability is on, ``histogram`` and the
    ``event`` record emitted here. ``clock`` is the owner's injectable
    clock (default ``time.perf_counter``, the clock the engine, the
    benchmark's harness and its tracer all read).
    """

    __slots__ = ("name", "_hist", "_hist_labels", "_event", "_fields",
                 "_rec", "_clock", "start", "end", "seconds")

    def __init__(self, name: str, *, histogram: Optional[Histogram] = None,
                 hist_labels: Optional[Dict[str, Any]] = None,
                 event: Optional[str] = None, clock=time.perf_counter,
                 **fields):
        self.name = name
        self._hist = histogram
        self._hist_labels = hist_labels or {}
        self._event = event
        self._fields = fields
        self._clock = clock
        self._rec = None
        self.start = self.end = None
        self.seconds = 0.0

    def note(self, **fields):
        """Attributes known only inside the block."""
        self._fields.update(fields)
        if self._rec is not None:
            self._rec.annotate(**fields)

    def __enter__(self):
        self._rec = RecordEvent(self.name, attrs=self._fields)
        self._rec.begin()
        self.start = self._clock()
        return self

    def __exit__(self, *exc):
        self.end = self._clock()
        self.seconds = self.end - self.start
        self._rec.end()
        self._rec = None
        if _gate.state.on:
            if self._hist is not None:
                self._hist.observe(self.seconds, **self._hist_labels)
            if self._event is not None:
                emit(self._event, seconds=self.seconds, **self._fields)
        return False
