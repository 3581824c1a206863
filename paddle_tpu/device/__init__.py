"""paddle_tpu.device — device management (paddle.device parity).

Reference parity: python/paddle/device/__init__.py (set_device :277,
get_device :309, get_all_device_type :349, Event :457, Stream :633,
current_stream :857, stream_guard :953, synchronize :1020).

TPU-native design: there is no user-visible stream on TPU — XLA owns
scheduling and JAX dispatch is async by default. ``Stream``/``Event`` are
kept as ordering facades: recording an event captures the set of in-flight
arrays; synchronizing blocks until they are ready. This preserves the
reference's compute/comm-overlap idioms without pretending to own the
hardware queues.
"""
from __future__ import annotations

from typing import List, Optional

from ..core.place import (device_count, get_device, is_compiled_with_cuda,
                          set_device)
from . import memory
from . import cuda  # noqa: F401
from . import xpu  # noqa: F401  # noqa: F401
from .memory import (empty_cache, max_memory_allocated, max_memory_reserved,
                     memory_allocated, memory_reserved, memory_stats)

__all__ = [
    "set_device", "get_device", "device_count", "is_compiled_with_cuda",
    "get_all_device_type", "get_available_device", "synchronize",
    "Stream", "Event", "current_stream", "set_stream", "stream_guard",
    "is_compiled_with_xpu", "is_compiled_with_ipu",
    "is_compiled_with_custom_device", "get_all_custom_device_type",
    "get_available_custom_device", "memory_allocated", "memory_reserved",
    "max_memory_allocated", "max_memory_reserved", "memory_stats",
    "empty_cache",
]


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    # TPU is our first-class device, surfaced the way the reference surfaces
    # plugin devices (reference: phi/backends/device_manager.h:134).
    return device_type in ("tpu",)


def get_all_device_type() -> List[str]:
    import jax
    return sorted({d.platform for d in jax.devices()} | {"cpu"})


def get_all_custom_device_type() -> List[str]:
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device() -> List[str]:
    import jax
    out = []
    for d in jax.devices():
        name = d.platform if d.platform == "cpu" else f"{d.platform}:{d.id}"
        out.append(name)
    return out


def get_available_custom_device() -> List[str]:
    return [d for d in get_available_device() if not d.startswith(("cpu", "gpu"))]


def synchronize(device: Optional[str] = None) -> None:
    """Block until all dispatched work on the device is complete."""
    import jax
    # The per-device dispatch queue is FIFO: enqueue a trivial computation and
    # drain it — everything dispatched earlier has then finished (the TPU
    # analog of cudaDeviceSynchronize). effects_barrier alone would only wait
    # on side-effecting computations, not plain jit dispatches.
    (jax.device_put(0.0) + 0).block_until_ready()
    jax.effects_barrier()


class Event:
    """Ordering fence. ``record`` snapshots in-flight arrays; ``synchronize``
    blocks on them; ``query`` polls readiness."""

    def __init__(self, device=None, enable_timing: bool = False,
                 blocking: bool = False, interprocess: bool = False):
        self._arrays: list = []
        self._time = None
        self.enable_timing = enable_timing

    def record(self, stream: Optional["Stream"] = None):
        import time
        if stream is not None:
            self._arrays = list(stream._pending)
        if self.enable_timing:
            synchronize()
            self._time = time.perf_counter()

    def query(self) -> bool:
        for a in self._arrays:
            if hasattr(a, "is_ready") and not a.is_ready():
                return False
        return True

    def synchronize(self):
        for a in self._arrays:
            if hasattr(a, "block_until_ready"):
                a.block_until_ready()
        if not self._arrays:
            synchronize()

    def elapsed_time(self, end_event: "Event") -> float:
        if self._time is None or end_event._time is None:
            return 0.0
        return (end_event._time - self._time) * 1e3


class Stream:
    """Async-dispatch facade. JAX dispatch is already asynchronous; a Stream
    tracks arrays launched "on" it so waits/events have real semantics."""

    def __init__(self, device=None, priority: int = 2):
        self._pending: list = []
        self.device = device
        self.priority = priority

    def track(self, *arrays):
        self._pending.extend(a for a in arrays if hasattr(a, "block_until_ready"))
        if len(self._pending) > 256:
            self._pending = self._pending[-256:]

    def record_event(self, event: Optional[Event] = None) -> Event:
        event = event or Event()
        event.record(self)
        return event

    def wait_event(self, event: Event):
        event.synchronize()

    def wait_stream(self, stream: "Stream"):
        for a in stream._pending:
            a.block_until_ready()

    def query(self) -> bool:
        return all(not hasattr(a, "is_ready") or a.is_ready()
                   for a in self._pending)

    def synchronize(self):
        for a in self._pending:
            a.block_until_ready()
        self._pending = []


_current_stream = Stream()


def current_stream(device=None) -> Stream:
    return _current_stream


def set_stream(stream: Stream) -> Stream:
    global _current_stream
    prev, _current_stream = _current_stream, stream
    return prev


class stream_guard:
    def __init__(self, stream: Stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


def get_cudnn_version():
    """No cuDNN on TPU (reference returns None when not compiled with CUDA)."""
    return None


def is_compiled_with_cinn() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    return True


class XPUPlace:
    def __init__(self, *a, **k):
        raise NotImplementedError("XPU devices are not part of the TPU build")


class IPUPlace:
    def __init__(self, *a, **k):
        raise NotImplementedError("IPU devices are not part of the TPU build")


# ---------------------------------------------------------------------------
# Host-side model construction (TPU-first init path).
#
# Reference: the LazyGuard / LazyInit flow (python/paddle/nn/initializer/
# lazy_init.py) exists because materializing parameters one op at a time
# on the accelerator is slow. host_init() runs construction on the host
# CPU backend, and to_accelerator() then moves the finished parameter
# set in ONE bulk jax.device_put.
# ---------------------------------------------------------------------------

class host_init:
    """Context manager: build models on the host CPU backend.

    >>> with paddle.device.host_init():
    ...     model = UNet2DConditionModel(cfg)   # fast host-side init
    ...     model.bfloat16()
    >>> paddle.device.to_accelerator(model)      # one bulk transfer

    No-op (but harmless) when the process has no accelerator.

    When it pays: where the bulk transfer is fast and eager init
    dispatches are the cost. Against on-device init on the current
    machine: not measured, so nothing in-tree forces this path; it's an
    opt-in.
    """

    def __enter__(self):
        import jax

        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            self._ctx = None
            return self
        self._ctx = jax.default_device(cpu)
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            self._ctx.__exit__(*exc)
        return False


def to_accelerator(layer_or_tensors, device=None):
    """Move a Layer's parameters+buffers (or a list of Tensors) to the
    accelerator in one bulk ``jax.device_put`` — a single transfer
    instead of one dispatch per tensor."""
    import jax

    if device is None:
        accel = [d for d in jax.devices() if d.platform == "tpu"]
        if not accel:
            return layer_or_tensors
        device = accel[0]

    if hasattr(layer_or_tensors, "parameters"):
        tensors = list(layer_or_tensors.parameters())
        try:
            tensors += [b for b in layer_or_tensors.buffers()]
        except Exception:
            pass
    else:
        tensors = list(layer_or_tensors)
    values = [t._value for t in tensors]
    moved = jax.device_put(values, device)
    for t, v in zip(tensors, moved):
        t._replace_value(v)
    return layer_or_tensors
