"""Device places.

TPU-native re-design of the reference Place hierarchy
(reference: paddle/phi/common/place.h — CPUPlace/GPUPlace/XPUPlace/CustomPlace).
A Place names a jax.Device; TPUPlace is the first-class accelerator.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base place: names a logical device."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    # -- jax bridge --------------------------------------------------------
    def get_device(self):
        """Resolve to a jax.Device (raises if the backend is unavailable)."""
        devs = _devices_for(self.device_type)
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: only {len(devs)} {self.device_type} device(s) visible"
            )
        return devs[self.device_id]

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    """The accelerator place. Reference GPUPlace analog (place.h)."""

    device_type = "tpu"


# Compat alias: code written against the reference uses CUDAPlace for "the
# accelerator"; on this framework that is the TPU.
CUDAPlace = TPUPlace


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


@functools.lru_cache(maxsize=None)
def _devices_for(device_type: str):
    if device_type == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:
            return tuple(jax.devices())
    try:
        return tuple(jax.devices("tpu"))
    except RuntimeError:
        # no TPU backend: the CPU tests address the host's (virtual)
        # devices through TPUPlace. Nothing that reports a device metric
        # may come this way — those scripts check platform == "tpu"
        # (device/chip.py) before they measure.
        return tuple(jax.devices())


@functools.lru_cache(maxsize=None)
def default_place() -> Place:
    devs = jax.devices()
    if devs and devs[0].platform == "tpu":
        return TPUPlace(0)
    return CPUPlace(0)


_expected_place = None


def get_device() -> str:
    """paddle.device.get_device() parity: 'tpu:0' or 'cpu'."""
    p = _expected_place or default_place()
    return "cpu" if isinstance(p, CPUPlace) else f"{p.device_type}:{p.device_id}"


def set_device(device: str) -> Place:
    """paddle.device.set_device parity ('tpu', 'tpu:0', 'cpu', 'gpu'→tpu)."""
    global _expected_place
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name == "cpu":
        _expected_place = CPUPlace(idx)
    elif name in ("tpu", "gpu", "xpu", "npu"):
        _expected_place = TPUPlace(idx)
    else:
        _expected_place = CustomPlace(name, idx)
    return _expected_place


def expected_place() -> Place:
    return _expected_place or default_place()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return len(_devices_for("tpu"))


class CUDAPinnedPlace(Place):
    """Reference: paddle.CUDAPinnedPlace — page-locked host staging memory.
    On TPU, host staging buffers are managed by PJRT; this place maps to
    host memory."""

    device_type = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(gpu_pinned)"
