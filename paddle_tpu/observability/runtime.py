"""Step-level training telemetry: wall time, throughput, MFU, HBM gauges.

The runtime counterpart of the compile-time metrics PR 2 shipped: where
dispatch/Executor/PassManager telemetry answers "where do recompiles
go?", this module answers "how fast is the training loop actually
running and how close to the roofline is it?" per step:

- ``step_region()`` / :class:`StepTimer` bracket one optimizer step and
  record ``train.step_seconds``, ``train.items_per_second`` and — when a
  per-step FLOP count is known — ``train.mfu`` (model FLOPs utilization
  against the chip's peak), emitting a ``train.step`` event that rides
  both the export ring and the flight recorder;
- :func:`sample_device_memory` reads ``device/memory.py`` stats into
  ``device.hbm_bytes_in_use`` / ``device.hbm_watermark_bytes`` gauges,
  with a live-array scan as the safe CPU fallback (CPU PJRT reports no
  allocator stats);
- :func:`measure_step_flops` computes the FLOP count from XLA's compiled
  cost analysis (``utils/flops.xla_flops`` — the post-fusion count the
  hardware executes), so MFU is cost-analysis-driven, not hand-counted.

Everything is behind the ``observability.state.on`` gate: a disabled
process pays two attribute loads per region and allocates nothing.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

from . import _gate, flight
from .events import emit
from .metrics import registry

PEAK_FLOPS_ENV = "PADDLE_TPU_PEAK_FLOPS"


class FakeClock:
    """Deterministic injectable clock for timing-sensitive tests.

    Serves BOTH clock protocols in the codebase: calling it (or
    ``.time()``) returns the current fake time — the callable protocol
    ``ServeEngine(clock=...)``, ``StepTimer(clock=...)`` and
    ``step_region(clock=...)`` take — and ``.sleep(dt)`` advances it,
    the object protocol ``serve.load.run_load(clock=...)`` takes.

    ``tick`` advances the clock by a fixed amount on every read, so a
    code path that reads the clock twice always measures a positive,
    exactly reproducible duration — the deflaking device for the
    load-generator and step-telemetry tests that used to assert on real
    ``time.sleep`` under CI load."""

    __slots__ = ("now", "tick")

    def __init__(self, start: float = 0.0, tick: float = 0.0):
        self.now = float(start)
        self.tick = float(tick)

    def time(self) -> float:
        t = self.now
        self.now += self.tick
        return t

    __call__ = time

    def sleep(self, dt: float):
        self.now += max(float(dt), 0.0)

    def advance(self, dt: float):
        self.now += float(dt)

M_STEP_SECONDS = registry.histogram(
    "train.step_seconds",
    "wall seconds per training step bracketed by obs.step_region()")
M_STEPS = registry.counter(
    "train.steps", "training steps completed, by region name")
M_ITEMS_PER_SEC = registry.gauge(
    "train.items_per_second",
    "throughput of the last step (tokens- or samples-per-second — the "
    "unit label says which), by region name")
M_MFU = registry.gauge(
    "train.mfu",
    "model FLOPs utilization of the last step (0-1): step FLOPs / wall "
    "seconds / peak chip FLOPs, by region name")
M_HBM_IN_USE = registry.gauge(
    "device.hbm_bytes_in_use",
    "device memory currently allocated, by device index (CPU fallback: "
    "sum of live jax array bytes)")
M_HBM_WATERMARK = registry.gauge(
    "device.hbm_watermark_bytes",
    "high-water mark of device memory, by device index (allocator "
    "peak_bytes_in_use where the platform reports it, else the max "
    "in-use value this process has sampled)")
M_HBM_LIMIT = registry.gauge(
    "device.hbm_bytes_limit",
    "device memory capacity, by device index (0 when the platform "
    "reports no limit)")

# host-side watermark per device label, for platforms whose allocator
# reports no peak (CPU PJRT): max bytes_in_use ever sampled here.
_seen_watermark: Dict[str, int] = {}


def _clear_watermarks():
    _seen_watermark.clear()


def default_peak_flops() -> float:
    """Peak chip FLOPs/s for MFU: ``PADDLE_TPU_PEAK_FLOPS`` env override,
    else the bf16 peak of the TPU this process runs on from the one
    table (``device/chip.py``; an unknown chip raises), else — off-TPU,
    where nothing is a device metric — a nominal 1 TF/s."""
    env = os.environ.get(PEAK_FLOPS_ENV)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    from ..device import chip

    if chip.on_tpu():
        return chip.chip_peaks()["bf16_flops_per_sec"]
    return 1e12


def measure_step_flops(fn, *args, **kwargs) -> int:
    """FLOPs of one ``fn(*args)`` step from XLA's compiled cost analysis
    (post-fusion, what the hardware executes). Returns 0 when the
    backend reports no cost analysis rather than raising."""
    from ..utils.flops import xla_flops

    try:
        return int(xla_flops(fn, *args, **kwargs))
    except Exception:
        return 0


def sample_device_memory(device_id: Optional[int] = None) -> Dict[str, int]:
    """Read device memory stats into the ``device.*`` gauges.

    Uses the PJRT allocator stats where the platform reports them
    (``device/memory.py:memory_stats``); on CPU — whose PJRT client
    reports None — falls back to summing live jax array bytes, so tests
    and CPU rigs still see a meaningful curve. Never raises; returns
    ``{"bytes_in_use", "watermark_bytes", "bytes_limit"}``."""
    from ..device import memory as dev_mem

    stats = dev_mem.memory_stats(device_id)
    in_use = int(stats.get("bytes_in_use", 0))
    peak = int(stats.get("peak_bytes_in_use", 0))
    limit = int(stats.get("bytes_limit", 0))
    if "bytes_in_use" not in stats:
        # platform reports no allocator stats (CPU PJRT): process-wide
        # live-array scan — a host-level approximation, so on a forced
        # multi-device CPU mesh every device label sees the same total.
        # A real allocator's genuine 0 reading is left untouched.
        in_use = dev_mem.live_array_bytes()
    label = str(device_id or 0)
    watermark = max(peak, in_use, _seen_watermark.get(label, 0))
    _seen_watermark[label] = watermark
    if _gate.state.on:
        M_HBM_IN_USE.set(in_use, device=label)
        M_HBM_WATERMARK.set(watermark, device=label)
        M_HBM_LIMIT.set(limit, device=label)
    return {"bytes_in_use": in_use, "watermark_bytes": watermark,
            "bytes_limit": limit}


class _StepRegion:
    """One bracketed step: a profiler host span + the train.* metrics.

    On a clean exit it records step wall time, throughput and MFU; on an
    exception it emits a ``train.step_failed`` event and writes the
    flight-recorder dump (reason ``step_exception``) before re-raising.
    """

    __slots__ = ("name", "step", "items", "unit", "flops", "peak_flops",
                 "sample_memory", "fields", "_rec", "_t0", "seconds",
                 "mfu", "items_per_second", "_clock")

    def __init__(self, name: str, step: Optional[int], items: Optional[int],
                 unit: str, flops: Optional[int], peak_flops: Optional[float],
                 sample_memory: bool, fields: Dict[str, Any],
                 clock=None):
        self.name = name
        self.step = step
        self.items = items
        self.unit = unit
        self.flops = flops
        self.peak_flops = peak_flops
        self.sample_memory = sample_memory
        self.fields = fields
        self._rec = None
        self.seconds = 0.0
        self.mfu: Optional[float] = None
        self.items_per_second: Optional[float] = None
        self._clock = clock if clock is not None else time.perf_counter

    def __enter__(self):
        from ..profiler.utils import RecordEvent

        self._rec = RecordEvent(f"{self.name}.step")
        self._rec.begin()
        self._t0 = self._clock()
        return self

    def abandon(self):
        """Close the profiler span without recording any metrics — for a
        region superseded before its ``end()`` ran (e.g. a fit loop that
        died between batch-begin and batch-end), so the host-tracer span
        stack stays balanced."""
        if self._rec is not None:
            self._rec.end()
            self._rec = None

    def __exit__(self, exc_type, exc, tb):
        self.seconds = max(self._clock() - self._t0, 1e-12)
        if self._rec is not None:
            self._rec.end()
            self._rec = None
        if not _gate.state.on:
            return False
        if exc is not None:
            emit("train.step_failed", name=self.name, step=self.step,
                 seconds=self.seconds, error=f"{exc_type.__name__}: {exc}")
            flight.recorder.dump("step_exception", exc)
            return False
        M_STEP_SECONDS.observe(self.seconds, name=self.name)
        M_STEPS.inc(name=self.name)
        ev: Dict[str, Any] = {"name": self.name, "seconds": self.seconds}
        if self.step is not None:
            ev["step"] = self.step
        if self.items:
            self.items_per_second = self.items / self.seconds
            M_ITEMS_PER_SEC.set(self.items_per_second, name=self.name,
                                unit=self.unit)
            ev["items"] = self.items
            ev[f"{self.unit}_per_second"] = round(self.items_per_second, 2)
        if self.flops:
            peak = self.peak_flops or default_peak_flops()
            self.mfu = self.flops / self.seconds / peak
            M_MFU.set(round(self.mfu, 5), name=self.name)
            ev["mfu"] = round(self.mfu, 5)
        ev.update(self.fields)
        emit("train.step", **ev)
        if self.sample_memory:
            sample_device_memory()
        from . import health
        health.maybe_on_step(self._clock())
        return False


class _DisabledRegion:
    """Shared no-op returned by :func:`step_region` while observability is
    off — the disabled hot path allocates nothing and opens no span.
    Mirrors the _StepRegion surface callers may poke at."""

    seconds = 0.0
    mfu = None
    items_per_second = None
    items = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def abandon(self):
        pass


_DISABLED_REGION = _DisabledRegion()


def step_region(name: str = "train", *, step: Optional[int] = None,
                items: Optional[int] = None, unit: str = "items",
                flops: Optional[int] = None,
                peak_flops: Optional[float] = None,
                sample_memory: bool = False, clock=None, **fields):
    """Context manager bracketing ONE training step.

    ``items`` is the tokens/samples consumed this step (drives
    ``train.items_per_second``); ``flops`` the per-step FLOP count
    (drives ``train.mfu`` against ``peak_flops``, defaulting to the
    chip's peak). Extra keyword fields ride the ``train.step`` event.

    Usage::

        for step, batch in enumerate(loader):
            with obs.step_region("train", step=step, items=bs * seq,
                                 unit="tokens", flops=step_flops):
                loss = train_step(batch)
    """
    if not _gate.state.on:
        return _DISABLED_REGION
    return _StepRegion(name, step, items, unit, flops, peak_flops,
                       sample_memory, fields, clock=clock)


class StepTimer:
    """Loop-lifetime helper over :func:`step_region`: counts steps,
    remembers the per-step FLOP/item constants, samples device memory
    every ``sample_memory_every`` steps, and supports the split
    ``begin()``/``end()`` form callback-style loops need (hapi's
    ``MetricsCallback`` drives it from on_train_batch_begin/end).
    """

    def __init__(self, name: str = "train", *,
                 flops_per_step: Optional[int] = None,
                 items_per_step: Optional[int] = None, unit: str = "items",
                 peak_flops: Optional[float] = None,
                 sample_memory_every: int = 16, clock=None):
        self.name = name
        self.flops_per_step = flops_per_step
        self.items_per_step = items_per_step
        self.unit = unit
        self.peak_flops = peak_flops
        self.clock = clock        # injectable (FakeClock) for determinism
        self.sample_memory_every = max(0, int(sample_memory_every))
        self.count = 0
        self.last: Optional[_StepRegion] = None
        self._open: Optional[_StepRegion] = None

    def measure_flops(self, fn, *args, **kwargs) -> int:
        """Fix ``flops_per_step`` from XLA cost analysis of ``fn``."""
        self.flops_per_step = measure_step_flops(fn, *args, **kwargs)
        return self.flops_per_step

    def region(self, items: Optional[int] = None, **fields) -> _StepRegion:
        sample = (self.sample_memory_every > 0
                  and self.count % self.sample_memory_every == 0)
        r = step_region(
            self.name, step=self.count,
            items=self.items_per_step if items is None else items,
            unit=self.unit, flops=self.flops_per_step,
            peak_flops=self.peak_flops, sample_memory=sample,
            clock=self.clock, **fields)
        self.count += 1
        self.last = r
        return r

    # -- split form for callback-driven loops ------------------------------
    def begin(self, **fields):
        if self._open is not None:
            self.abandon()
        self._open = self.region(**fields)
        self._open.__enter__()

    def abandon(self):
        """Discard an open region without recording it (balances the
        profiler span stack when end() will never arrive)."""
        r, self._open = self._open, None
        if r is not None:
            r.abandon()

    def end(self, items: Optional[int] = None, failed: bool = False):
        r, self._open = self._open, None
        if r is None:
            return
        if items is not None:
            r.items = items
        if failed:
            # synthesize an exception-shaped exit without a live traceback
            r.__exit__(RuntimeError, RuntimeError("step failed"), None)
        else:
            r.__exit__(None, None, None)
