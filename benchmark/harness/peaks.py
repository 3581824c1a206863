"""Published peaks of one chip, keyed by `jax.devices()[0].device_kind`.

The yardstick's copy (the program keeps its own in
`paddle_tpu/device/chip.py`; the benchmark may not move with it). A device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(kind: str) -> dict:
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise LookupError(
            f"no peak figures for device_kind {kind!r}; the benchmark's "
            f"table holds {sorted(CHIP_PEAKS)}") from None


def require_device(chips: int) -> dict:
    """The device line of a run, or SystemExit(3) where JAX finds no TPU,
    fewer chips than the cell asks for, or a chip the table lacks. Never
    falls back."""
    import sys

    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        sys.stderr.write(f"benchmark: no TPU (JAX reports {info}); "
                         f"no result\n")
        raise SystemExit(3)
    if info["count"] < chips:
        sys.stderr.write(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {info}; no result\n")
        raise SystemExit(3)
    try:
        peaks_for(info["kind"])
    except LookupError as e:
        sys.stderr.write(f"benchmark: {e}; no result\n")
        raise SystemExit(3)
    return info
