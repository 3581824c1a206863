"""What both drivers share: the clock, the tracer and the device's
memory."""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time

from . import spec as _spec

clock = time.perf_counter


def log(msg: str):
    sys.stderr.write(f"[bench {clock():.1f}] {msg}\n")
    sys.stderr.flush()


def setup_program_cache():
    """The program's own helper places JAX's persistent compile cache:
    `JAX_COMPILATION_CACHE_DIR` where set, else `<checkout>/.jax_cache`."""
    from paddle_tpu.device import chip

    return chip.setup_compile_cache()


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def bytes_in_use() -> int:
    import jax

    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices()))


def free_device():
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


class Tracer:
    """The profiler round a part of the window. The trace lands in
    `<checkout>/.bench_trace/<workload>` (a fixed place inside the
    checkout, emptied before each traced run) and is reduced after the
    window by `harness/trace.py`."""

    def __init__(self, workload: str):
        self.dir = os.path.join(_spec.ROOT, ".bench_trace", workload)
        self.t0 = self.t1 = None

    @property
    def running(self):
        return self.t0 is not None and self.t1 is None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = clock()

    def stop(self):
        import jax

        self.t1 = clock()
        jax.profiler.stop_trace()

    @property
    def window_s(self):
        return self.t1 - self.t0

    def events(self):
        from . import trace

        ev = trace.load(trace.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return ev


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)
