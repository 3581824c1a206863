"""What this process runs on: the device line every measuring script
prints, the one table of chip peaks, and the place of the compile cache.

A number is a device metric only if it was taken on a chip in this
table; scripts decide that by ``platform == "tpu"`` (never ``!= "cpu"``)
and an unknown ``device_kind`` is an error, not a default.
"""
from __future__ import annotations

import os
from typing import Dict

#: Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" system architecture
#: (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_sec": 197e12,
        "hbm_bytes_per_sec": 819e9,
        "hbm_bytes": 16e9,
    },
}

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def device_info() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` as JAX reports the devices
    (initialises the backend)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def on_tpu() -> bool:
    return device_info()["platform"] == "tpu"


def chip_peaks(kind: str | None = None) -> Dict[str, float]:
    """Peaks of ``kind`` (default: the device this process runs on).
    Raises for a chip the table does not hold."""
    if kind is None:
        kind = device_info()["kind"]
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise LookupError(
            f"no peak figures for device_kind {kind!r}; known: "
            f"{sorted(CHIP_PEAKS)} (add the chip to "
            f"paddle_tpu/device/chip.py:CHIP_PEAKS with its source)"
        ) from None


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory comes from
    outside and nothing sets another in code (JAX reads the variable
    itself); else it is ``<checkout>/.jax_cache`` — a fixed path,
    because a cache that moves between runs is never found again. The
    threshold is low enough that every serving-engine step and prefill
    bucket (seconds each) is cached, not only the train steps.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir
