#!/usr/bin/env python3
"""Records the small trace that `tests/test_trace.py` reads, on the chip:
three steps of a little program (a matrix product and the program's Pallas
flash forward kernel) under the harness's own spans, with the host asleep
between them, and prints what the file holds (planes, lines, first event
names). Run once by hand: `chiprun -- python3 benchmark/tools/record_trace.py`;
the file comes back as `chiprun_out/sample.xplane.pb`."""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def summarise(path, limit=8):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = []
            for e in events:
                if e.name not in names:
                    names.append(e.name)
                if len(names) >= limit:
                    break
            print(f"  LINE {line.name!r}: {len(events)} events; {names}")


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401
    from benchmark.harness import common, peaks, trace
    from paddle_tpu.ops.pallas.flash_attention import _flash_fwd_bhsd

    peaks.require_device(1)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (1, 2, 512, 128), jnp.bfloat16)
               for kk in ks[:3])
    w = jax.random.normal(ks[3], (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(q, k, v, w):
        out, _ = _flash_fwd_bhsd(q, k, v, None, None, causal=True,
                                 scale=128 ** -0.5, dropout_rate=0.0)
        return out.sum() + (w @ w).sum()

    step(q, k, v, w).block_until_ready()
    tracer = common.Tracer("sample")
    tracer.start()
    for _ in range(3):
        with common.span("bench.train_step"):
            step(q, k, v, w).block_until_ready()
        with common.span("bench.idle"):
            time.sleep(0.005)
    tracer.stop()
    src = trace.find_xplane(tracer.dir)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(src, os.path.join(out, "sample.xplane.pb"))
    print("bytes", os.path.getsize(src), "window_s", tracer.window_s)
    summarise(src)
    events = trace.load(src)
    print("busy_s", trace.busy_seconds(events))
    print("kernels", trace.kernel_seconds(events, ["flash_fwd"]))
    print("top", trace.top_device_ops(events))
    print("gaps", trace.idle_gaps_by_host_span(events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
