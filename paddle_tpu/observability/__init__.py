"""paddle_tpu.observability — runtime metrics + structured span events.

The runtime counterpart of the PR-1 static diagnostics layer: where
``static.analysis`` tells you what is *wrong* with a program,
observability tells you where *time and recompiles go* at runtime. Three
hot layers are instrumented with it out of the box:

- ``core/dispatch.py`` — per-primitive call counts (eager vs traced vs
  capture), ``_jitted_forward`` executable-cache hits/misses, and
  retrace causes (new static-args vs new input avals);
- ``static/program.py`` Executor — compile events carrying the program
  fingerprint, feed signature and compile wall time, replay counts,
  cache invalidations and recompiles saved by fingerprint keying;
- ``distributed/passes`` PassManager — per-pass wall time, op-count
  delta, verifier runs and diagnostic counts.

Usage::

    import paddle_tpu.observability as obs
    obs.enable()                  # or FLAGS_observability=1 in the env
    ...run workload...
    print(obs.summary())          # human table
    obs.dump("metrics.json")      # JSON; render with tools/metrics_report.py

Gating: recording at the instrumentation sites is OFF by default and
costs two attribute loads per dispatch when disabled. It turns on via
``enable()``, the ``FLAGS_observability`` env/flag (core/flags.py), or
automatically when ``PADDLE_TPU_METRICS_DUMP=<path>`` is set — that env
var also registers an atexit hook writing the dump to ``<path>``.
Metric objects themselves always record when called directly; the gate
belongs to the hot-path instrumentation, not the registry.

Spans reuse ``profiler.RecordEvent``/host-tracer machinery, so compile
and pass events land in the same Chrome-trace timeline as user spans
and XLA device ops.

Claiming metric names: every name is ``subsystem.noun_verb``; claim your
subsystem prefix in ``observability.metrics.CLAIMED_SUBSYSTEMS`` (the
``PTLxxx``-code convention applied to metrics). ``tools/lint_registry.py``
audits the registry once per test session.
"""
from __future__ import annotations

import atexit
import os

from ._gate import state
from .metrics import (CLAIMED_SUBSYSTEMS, Counter, Gauge, Histogram,
                      MetricsRegistry, NAME_RE, registry)
from .events import Event, emit, events, span
from .report import (dump, dump_dict, render_flight, render_health,
                     render_report, render_trend_table, sparkline,
                     summary)
from . import flight
from .flight import FlightRecorder
from . import fleet
from .fleet import FleetAggregator, FleetReporter
from .runtime import (FakeClock, StepTimer, default_peak_flops,
                      measure_step_flops, sample_device_memory,
                      step_region)
from . import slo
from .slo import SloMonitor, SloRule
from . import timeseries
from .timeseries import SeriesRecorder, merge_timeseries
from . import health
from .health import HealthMonitor, HealthRule
from . import tracing
from .tracing import (RequestTrace, ServeTracer, Span, TailExemplars,
                      check_tracing_overhead, validate_trace)
from . import chrome
from . import opprof
from .opprof import (OpCalibration, OpProfile, OpProfiler, OpSpan,
                     attribute_profile, calibrate_op_costs,
                     check_opprof_overhead, lint_op_profile,
                     load_op_calibration, render_op_profile,
                     resolve_op_calibration, save_op_calibration)

__all__ = [
    "state", "enabled", "enable", "disable", "reset",
    "registry", "counter", "gauge", "histogram",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Event", "emit", "events", "span",
    "dump", "dump_dict", "render_report", "render_flight", "summary",
    "render_health", "render_trend_table", "sparkline",
    "CLAIMED_SUBSYSTEMS", "NAME_RE",
    "flight", "FlightRecorder", "fleet", "FleetAggregator",
    "FleetReporter", "StepTimer", "step_region", "FakeClock",
    "sample_device_memory", "measure_step_flops", "default_peak_flops",
    "slo", "SloMonitor", "SloRule",
    "timeseries", "SeriesRecorder", "merge_timeseries",
    "health", "HealthMonitor", "HealthRule",
    "tracing", "Span", "RequestTrace", "ServeTracer", "TailExemplars",
    "check_tracing_overhead", "validate_trace",
    "chrome", "opprof", "OpSpan", "OpProfile", "OpProfiler",
    "OpCalibration", "attribute_profile", "calibrate_op_costs",
    "save_op_calibration", "load_op_calibration",
    "resolve_op_calibration", "lint_op_profile", "check_opprof_overhead",
    "render_op_profile",
]

counter = registry.counter
gauge = registry.gauge
histogram = registry.histogram


def enabled() -> bool:
    return state.on


def enable():
    """Turn on metric/event recording at the instrumentation sites."""
    state.on = True
    # arm the crash-dump hook too (idempotent): it still no-ops at fire
    # time unless PADDLE_TPU_FLIGHT_DIR is set, but a process that
    # enables observability after import must not lose the headline
    # unhandled-exception dump
    flight.install_excepthook()


def disable():
    state.on = False


_reset_hooks = []


def add_reset_hook(fn):
    """Register a callable run by :func:`reset` — instrumented modules
    use it to clear private bookkeeping (e.g. dispatch's seen-key set)."""
    _reset_hooks.append(fn)


def reset():
    """Zero all metric series, drop buffered events (both rings), run
    reset hooks."""
    registry.reset()
    from .events import clear as _clear_events
    from .runtime import _clear_watermarks

    _clear_events()
    flight.recorder.clear()
    _clear_watermarks()
    health._reset_active()
    tracing.clear_rings()
    for fn in _reset_hooks:
        fn()


def _init_from_env():
    from ..core import flags

    try:
        if flags.get_flag("observability"):
            state.on = True
    except KeyError:
        pass
    if os.environ.get("PADDLE_TPU_METRICS_DUMP"):
        state.on = True
        atexit.register(dump)
    if os.environ.get(flight.FLIGHT_DIR_ENV):
        # a configured crash-dump dir implies recording (same convention
        # as PADDLE_TPU_METRICS_DUMP) and arms the excepthook
        state.on = True
        flight.install_excepthook()
    if health.monitor_from_env() is not None:
        # PADDLE_TPU_HEALTH implies recording: detectors read the
        # registry, which only fills while the gate is on
        state.on = True


_init_from_env()
