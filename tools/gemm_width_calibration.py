"""GEMM width-scaling calibration on this chip.

Measures achieved TF/s of bf16 ``[M, K] x [K, W]`` as the output width
W varies — the curve that explains most single-chip MFU differences in
this repo (llama 0.695 at W=5632 FFN widths vs MoE 0.546 at W=1408
expert widths vs resnet 0.131 at conv-class widths), feeds the
auto-tuner's cost model (distributed/auto_tuner width_efficiency), and
motivated the measured-null experiments recorded in
models/llama.py (fused_qkv) and incubate .../moe/moe_layer.py (swiglu).

MEASURED RECORDS (v5e, bf16, M=16384, K=2048):

    round-3 harness (bounce-chained pair, counts both GEMMs):
        W=5632 -> 115 TF/s   W=2816 -> 72   W=1536 -> 59   W=1408 -> 49
    this tool (pool-of-8 cycled inputs, single GEMM, 2026-07-31):
        W=5632 -> 68         W=2816 -> ~43  W=1536 -> ~28  W=1408 -> 34

ABSOLUTE TF/s is protocol-dependent (the bounce variant amortizes
operand traffic across two GEMMs; this tool streams a fresh [M,K]
per iteration). The LOAD-BEARING, protocol-INVARIANT fact is the
monotone collapse with output width — 2-2.9x between W=5632 and
W=1408 across protocols, 2.3x in the round-3 record — which is what the auto-tuner's
width_efficiency ranking and the MoE/conv ceiling analyses consume
(all relative). Single digits at conv-class widths under every
protocol tried.

Protocol notes (hard-won, see rounds 2-4):
- NEVER time independent iterations inside one jit without data
  dependence or per-iter inputs: XLA hoists/CSEs the op and reports
  fantasy numbers (a multiply-by-zero dependency gets folded too —
  183 "TF/s" was measured that way);
- a bounce-chain ([K,W] then [W,K]) measures the PAIR and goes
  pathological at some widths (6 TF/s at W=1408);
- >= 30 iterations, so the per-call dispatch latency is amortized.

The records above were taken on 2026-07-31 through a set-up that no
longer exists; on the current machine: not measured.

Run: python tools/gemm_width_calibration.py [--widths 1408,2816,5632]
[--m 16384] [--k 2048] [--iters 50]
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def measure_width(m: int, k: int, w: int, iters: int) -> float:
    """Achieved TF/s of [m,k]x[k,w] bf16, carry-chained over iters."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    key = jax.random.PRNGKey(0)
    # DISTINCT input per iteration, consumed by lax.scan: XLA cannot
    # hoist or CSE any matmul (each sees fresh data), and no auxiliary
    # GEMM pollutes the number (an earlier [w,k] bounce-chain variant
    # measured pathological at some widths). The per-iter max-reduction
    # keeps only a scalar live; its cost is O(m*w) reads ≪ 2*m*k*w.
    # A small cycled POOL (not one buffer per iteration) keeps HBM
    # bounded however high --iters goes.
    pool = 8
    xs = jax.random.normal(key, (pool, m, k), jnp.bfloat16)
    a = jax.random.normal(key, (k, w), jnp.bfloat16)

    @jax.jit
    def run(xs_in):
        global_idx = jnp.arange(iters) % pool

        def body(carry, idx):
            h = jnp.dot(xs_in[idx], a,
                        preferred_element_type=jnp.bfloat16)
            return carry, jnp.max(h)

        _, outs = lax.scan(body, jnp.bfloat16(0.0), global_idx)
        return outs

    run(xs).block_until_ready()         # compile
    t0 = time.perf_counter()
    out = run(xs)
    np.asarray(out)                     # full sync
    dt = time.perf_counter() - t0
    flops = 2.0 * m * k * w * iters
    return flops / dt / 1e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default="1408,1536,2816,5632")
    ap.add_argument("--m", type=int, default=16384)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    print(f"# device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    if dev.platform != "tpu":
        raise SystemExit("gemm_width_calibration measures the chip: no "
                         "TPU found, and a CPU number is not a TF/s")
    print(f"# [M={args.m}, K={args.k}] x [K, W] bf16, "
          f"{args.iters}-iter carry-chained scan")
    for w in (int(s) for s in args.widths.split(",")):
        tf = measure_width(args.m, args.k, w, args.iters)
        print(f"W={w:<6d} {tf:7.1f} TF/s")


if __name__ == "__main__":
    main()
