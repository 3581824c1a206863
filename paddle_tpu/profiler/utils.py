"""RecordEvent and profiler-mode helpers.

Reference parity: python/paddle/profiler/utils.py:43 (RecordEvent),
:153 (load_profiler_result), :182 (in_profiler_mode). TPU-native twist:
each span is also a jax.profiler.TraceAnnotation, so under ANY live trace
(the program's Profiler, or a plain jax.profiler.start_trace from a
benchmark or an operator using XProf) host spans land on the xplane's host
plane, on the clock of the XLA device ops. With no trace session the
annotation costs well under a microsecond.
"""
from __future__ import annotations

import json
from typing import Any, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .host_tracer import TracerEventType, get_host_tracer

_profiler_active = False


def _native_tracer():
    """Native C++ tracer class, or None (lazy; see csrc/ptpu_tracer.cc)."""
    global _NATIVE_TRACER
    if _NATIVE_TRACER is False:
        try:
            from paddle_tpu import native

            _NATIVE_TRACER = native.NativeTracer if native.is_available() \
                else None
        except Exception:
            _NATIVE_TRACER = None
    return _NATIVE_TRACER


_NATIVE_TRACER: Any = False


def _set_profiler_mode(on: bool):
    global _profiler_active
    _profiler_active = on


def in_profiler_mode() -> bool:
    return _profiler_active


class RecordEvent:
    """Context-manager/decorator marking a named host span.

    Usage::

        with profiler.RecordEvent("forward"):
            loss = model(x)
    """

    def __init__(self, name: str,
                 event_type: str = TracerEventType.PythonUserDefined,
                 attrs: Optional[dict] = None):
        self.name = name
        self.event_type = event_type
        self.attrs = attrs
        self._ev = None
        self._jax_ann = None

    def begin(self):
        tracer = get_host_tracer()
        if tracer.enabled:
            self._ev = tracer.push(self.name, self.event_type)
            nat = _native_tracer()
            if nat is not None and nat.enabled():
                nat.begin(self.name, self.event_type)
                self._nat_open = True
        self._jax_ann = _TraceAnnotation(self.name, **(self.attrs or {}))
        self._jax_ann.__enter__()

    def annotate(self, **attrs):
        """Attributes known only once the span is under way (how many
        were admitted, tokens out): they land on the open annotation."""
        if self._jax_ann is not None:
            self._jax_ann.set_metadata(**attrs)

    def end(self):
        if self._jax_ann is not None:
            self._jax_ann.__exit__(None, None, None)
            self._jax_ann = None
        if self._ev is not None:
            get_host_tracer().pop(self._ev)
            self._ev = None
            if getattr(self, "_nat_open", False):
                self._nat_open = False
                nat = _native_tracer()
                if nat is not None:
                    nat.end()

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with RecordEvent(self.name, self.event_type):
                return fn(*args, **kwargs)

        return wrapper


def load_profiler_result(filename: str) -> Any:
    """Load a chrome-trace json previously exported by the profiler."""
    with open(filename) as f:
        return json.load(f)
