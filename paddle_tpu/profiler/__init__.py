"""paddle_tpu.profiler — tracing/profiling subsystem.

Reference parity: python/paddle/profiler/__init__.py:28 (__all__ surface).
Host spans via HostTracer; device tracing via XLA/jax.profiler (xplane).
"""
from .host_tracer import TracerEventType
from .profiler import (Profiler, ProfilerState, ProfilerTarget, SummaryView,
                       export_chrome_tracing, export_protobuf, get_profiler,
                       make_scheduler)
from .utils import RecordEvent, in_profiler_mode, load_profiler_result
from .scopes import scope_of, scope_seconds
from .statistic import (collect_device_statistic, device_summary_table,
                        op_class, statistic_from_trace, summary_table)

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "SortedKeys",
    "SummaryView", "TracerEventType", "RecordEvent", "make_scheduler",
    "export_chrome_tracing", "export_protobuf", "load_profiler_result",
    "in_profiler_mode", "get_profiler", "collect_device_statistic",
    "device_summary_table", "op_class", "statistic_from_trace",
    "summary_table", "scope_seconds", "scope_of",
]


class SortedKeys:
    """Summary-table sort orders (reference: profiler/profiler_statistic.py
    SortedKeys enum)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7
