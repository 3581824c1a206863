"""Runtime flags registry.

TPU-native equivalent of the reference flag registry
(reference: paddle/common/flags.cc — 177 PHI_DEFINE_EXPORTED_* flags,
python/paddle/base/framework.py set_flags/get_flags).

Flags are process-global, overridable via environment variables named
``FLAGS_<name>`` (checked at first read), and via ``set_flags``.

When the native runtime (csrc/ptpu_flags.cc) is available, the C++
registry is the source of truth — values written from either side are
visible to both, mirroring how the reference shares one gflags registry
between C++ and Python (core.globals()).
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict

_LOCK = threading.RLock()


def _native():
    """The native module if its library is ALREADY loaded, else None.

    Deliberately never triggers a build: flags are touched on `import
    paddle_tpu`, and the first import must not block on a g++ link. When
    some other component loads the library, _on_native_loaded() below syncs
    this registry into the native one and subsequent calls delegate.
    """
    global _NATIVE_MOD
    if _NATIVE_MOD is None:
        try:
            from paddle_tpu import native

            _NATIVE_MOD = native
        except Exception:
            return None
    return _NATIVE_MOD if _NATIVE_MOD.loaded() else None


_NATIVE_MOD = None


def _flag_str(value) -> str:
    return str(int(value)) if isinstance(value, bool) else str(value)


def _on_native_loaded(lib=None):
    """Called by paddle_tpu.native right after the C++ library loads:
    mirror every Python-registered flag (and any explicit overrides) into
    the native registry so C++ and Python share one flag state."""
    from paddle_tpu import native

    with _LOCK:
        for name, f in _REGISTRY.items():
            native.flag_define(name, _flag_str(f.default), f.doc)
            if f.env_checked:
                # Python already resolved env/explicit sets; push the result.
                native.flag_set(name, _flag_str(f.value))


class _Flag:
    __slots__ = ("name", "default", "value", "doc", "type", "env_checked")

    def __init__(self, name, default, doc, type_):
        self.name = name
        self.default = default
        self.value = default
        self.doc = doc
        self.type = type_
        self.env_checked = False


_REGISTRY: Dict[str, _Flag] = {}


def _coerce(type_, raw: str):
    if type_ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return type_(raw)


def define_flag(name: str, default: Any, doc: str = "", type_=None):
    """Register a flag (analog of PHI_DEFINE_EXPORTED_* at common/flags.cc:31)."""
    with _LOCK:
        if name in _REGISTRY:
            return _REGISTRY[name]
        f = _Flag(name, default, doc, type_ or type(default))
        _REGISTRY[name] = f
        nat = _native()
        if nat is not None:
            sd = str(int(default)) if isinstance(default, bool) else str(default)
            nat.flag_define(name, sd, doc)
        return f


def get_flag(name: str):
    with _LOCK:
        f = _REGISTRY[name]
        nat = _native()
        if nat is not None:
            raw = nat.flag_get(name)
            if raw is not None:
                return _coerce(f.type, raw)
        if not f.env_checked:
            f.env_checked = True
            raw = os.environ.get("FLAGS_" + name)
            if raw is not None:
                f.value = _coerce(f.type, raw)
        return f.value


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags equivalent."""
    with _LOCK:
        for k, v in flags.items():
            k = k.removeprefix("FLAGS_")
            if k not in _REGISTRY:
                raise KeyError(f"Unknown flag: {k}")
            f = _REGISTRY[k]
            f.env_checked = True
            f.value = _coerce(f.type, v) if isinstance(v, str) else f.type(v)
            nat = _native()
            if nat is not None:
                sv = str(int(f.value)) if isinstance(f.value, bool) \
                    else str(f.value)
                nat.flag_set(k, sv)


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {"FLAGS_" + n: get_flag(n) for n in names}


def all_flags():
    with _LOCK:
        return {n: get_flag(n) for n in list(_REGISTRY)}


# ---------------------------------------------------------------------------
# Core flag set (subset of the reference's 177, the ones with TPU meaning).
# ---------------------------------------------------------------------------
define_flag("default_dtype", "float32", "default floating dtype for tensor creation")
define_flag("check_nan_inf", False, "NaN/Inf watchdog on op outputs (flags.cc:72)")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; >0: warn only (flags.cc:86)")
define_flag("eager_op_jit", True, "jit-compile per-op eager executions with caching")
define_flag("deterministic", False, "force deterministic kernels (cudnn_deterministic analog)")
define_flag("allocator_strategy", "auto_growth", "kept for API parity; XLA/PJRT owns HBM")
define_flag("use_stride_kernel", True, "views share storage where jax allows aliasing")
define_flag("embedding_deterministic", 0, "deterministic embedding grad scatter")
define_flag("flash_attn_version", 2, "flash-attention kernel generation")
define_flag("tpu_matmul_precision", "default", "jax matmul precision: default|float32|tensorfloat32")
define_flag("log_level", 0, "VLOG analog verbosity")
define_flag("benchmark", False, "sync after each op for timing")
define_flag("stop_check_timeout", 900, "collective watchdog timeout seconds (parallel.py:1133)")
define_flag("cache_inference_while_scope", False, "parity placeholder")
define_flag("check_embedding_bounds", True,
            "eager-mode embedding id range check (one blocking "
            "device->host sync per call; disable in eager inner loops "
            "where throughput matters — jit paths never pay it)")
define_flag("observability", False,
            "record runtime metrics/events at the instrumented hot paths "
            "(dispatch, Executor, PassManager, jit) — see "
            "paddle_tpu.observability; also enabled by "
            "PADDLE_TPU_METRICS_DUMP=<path>")
define_flag("observability_max_events", 4096,
            "ring-buffer capacity of the observability structured-event "
            "log (oldest events drop first)")
define_flag("observability_flight_events", 512,
            "ring-buffer capacity of the flight recorder (last-N runtime "
            "events serialized to PADDLE_TPU_FLIGHT_DIR on crash/timeout)")
define_flag("optimize_programs", False,
            "run the lint->rewrite optimization pipeline "
            "(static.analysis.optimize_program: CSE, cast/transpose-chain "
            "collapse, dead-op and unused-feed pruning) on a cached clone "
            "of every Program before Executor.run compiles it; also "
            "enabled by PADDLE_TPU_OPTIMIZE=1")
define_flag("use_pallas_flash_attention", True,
            "use the Pallas flash-attention kernel on TPU backends")
define_flag("use_pallas_rms_norm", True,
            "use the Pallas fused RMSNorm kernel when shapes are lane-aligned")
define_flag("pallas_force_interpret", False,
            "run Pallas kernels in interpret mode on non-TPU backends "
            "(testing only — the interpreter is orders slower than XLA)")

_pallas_mode_override = None


def pallas_mode() -> str:
    """How Pallas kernels run in this process — the ONE predicate every
    kernel gate and every ``pallas_call`` reads, so a caller can assert
    which path ran:

    - ``"compiled"``: Mosaic-compiled (the default backend is ``tpu``);
    - ``"interpret"``: the Pallas interpreter, off-TPU with the
      ``pallas_force_interpret`` test flag set. Interpret mode checks
      the kernel's arithmetic, NOT that Mosaic accepts it;
    - ``"off"``: the gates route to the XLA compositions (kernels called
      directly through ``ops.pallas.*`` still interpret).
    """
    if _pallas_mode_override is not None:
        return _pallas_mode_override
    import jax

    if jax.default_backend() == "tpu":
        return "compiled"
    return "interpret" if get_flag("pallas_force_interpret") else "off"


@contextlib.contextmanager
def pallas_mode_override(mode: str):
    """Pin :func:`pallas_mode` — for compile-only tooling that lowers
    for a TPU topology from a host whose default backend is the CPU
    (tests/test_tpu_aot_compile.py)."""
    global _pallas_mode_override
    if mode not in ("compiled", "interpret", "off"):
        raise ValueError(f"unknown pallas mode {mode!r}")
    prev, _pallas_mode_override = _pallas_mode_override, mode
    try:
        yield
    finally:
        _pallas_mode_override = prev
define_flag("observability_ts_points", 512,
            "ring-buffer capacity per metric time-series (points kept by "
            "observability/timeseries.SeriesRecorder; oldest samples drop "
            "first — bounds health-monitoring memory no matter how long "
            "the job runs)")
