"""ResNet-50 conv ceiling calibration on this chip.

Answers the question "would a Pallas implicit-GEMM conv beat the XLA
conv lowering?" with measurements instead of conjecture, per the three
bounds that order any conv implementation on a TPU:

  conv_tf      — what XLA's conv_general_dilated actually achieves at
                 each ResNet-50 shape (the current bench path);
  gemm_tf      — the SAME arithmetic expressed as its implicit-GEMM
                 matmul [M=N*H*W, K=C_in*kh*kw] x [K, C_out] via XLA's
                 matmul emitter: an UPPER bound for any matmul-based
                 conv kernel, because an implicit-GEMM kernel does this
                 matmul PLUS in-VMEM patch assembly and halo handling;
  pallas_tf    — a naively-tiled Pallas matmul at the same shape: what
                 hand-written Mosaic achieves without deep tuning (on
                 this stack it trails the XLA emitter even on pure
                 GEMMs — see bench history).

Run: python tools/conv_calibration.py [--iters 30] (or --shape i to
measure one shape per process, so a driving shell can give each shape
its own timeout).
Prints a per-shape table and the FLOP-weighted ResNet-50 forward bound.

MEASURED CONCLUSION (v5e, bf16, batch 64, 20-iter carry-chained scans,
2026-07-31 — the round-3 calibration this module exists to reproduce):

    shape                      conv lowering   implicit-GEMM bound
    64x56x56  -> 64  3x3       3.4 TF/s        3.3 TF/s  [M=200704,K=576,N=64]
    128x28x28 -> 128 3x3       4.1 TF/s        3.4 TF/s  [M=50176,K=1152,N=128]
    512x7x7   -> 512 3x3       2.5 TF/s        3.8 TF/s  [M=3136,K=4608,N=512]
    64x56x56  -> 256 1x1       1.6 TF/s        1.5 TF/s  [M=200704,K=64,N=256]

The conv lowering is ALREADY at (or above) the throughput of its own
implicit-GEMM formulation: ResNet's K=64..4608 / N=64..512 GEMM shapes
sit at the floor of this chip's width-scaling curve (same harness:
[16k,2048]x[2048,W] reaches 115 TF/s at W=5632 but 49 at W=1408 — and
collapses to single digits at the K/N widths conv produces). A Pallas
implicit-GEMM conv is bounded by its inner matmul plus patch-assembly
and halo overheads, and a naively-tiled Pallas matmul measures ~30%
BELOW the XLA emitter on this stack (36 vs 52 TF/s at the MoE expert
shape). Therefore the bench's ResNet-50 MFU (~0.13 end-to-end, within
the 0.12-0.19 bare-conv band measured in round 2) is this chip's
ceiling for conv-shaped arithmetic in any matmul-based formulation —
not a lowering deficiency a custom kernel could bypass. The chip's MXU
wants wide GEMMs; ResNet at 224px does not produce them.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

# (C_in, H, W, C_out, kernel, stride, count_in_resnet50)
RESNET50_CONVS = [
    (3, 224, 224, 64, 7, 2, 1),      # stem
    (64, 56, 56, 64, 1, 1, 1),       # conv2 reduce (first block)
    (64, 56, 56, 64, 3, 1, 3),       # conv2 3x3
    (64, 56, 56, 256, 1, 1, 4),      # conv2 expand (+projection)
    (256, 56, 56, 64, 1, 1, 2),
    (256, 56, 56, 128, 1, 1, 1),
    (128, 56, 56, 128, 3, 2, 1),     # conv3 entry stride
    (128, 28, 28, 128, 3, 1, 3),
    (128, 28, 28, 512, 1, 1, 5),
    (512, 28, 28, 128, 1, 1, 3),
    (512, 28, 28, 256, 1, 1, 1),
    (256, 28, 28, 256, 3, 2, 1),
    (256, 14, 14, 256, 3, 1, 5),
    (256, 14, 14, 1024, 1, 1, 7),
    (1024, 14, 14, 256, 1, 1, 5),
    (1024, 14, 14, 512, 1, 1, 1),
    (512, 14, 14, 512, 3, 2, 1),
    (512, 7, 7, 512, 3, 1, 2),
    (512, 7, 7, 2048, 1, 1, 4),
    (2048, 7, 7, 512, 1, 1, 2),
]


def _timed(fn, x0, iters, tries=3):
    import jax
    import jax.numpy as jnp

    def body(carry, _):
        y = fn((x0 * (1.0 + carry)).astype(x0.dtype))
        s = (jnp.mean(y.astype(jnp.float32)) * 1e-12).astype(jnp.float32)
        return s, ()

    g = jax.jit(
        lambda: jax.lax.scan(body, jnp.float32(0), jnp.arange(iters))[0])
    for attempt in range(tries):
        try:
            r = g()
            r.block_until_ready()
            t0 = time.perf_counter()
            float(g())
            return (time.perf_counter() - t0) / iters
        except Exception:
            if attempt == tries - 1:
                raise
            time.sleep(10)


def measure_shape(cin, h, w, cout, kk, stride, batch, iters):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rng = np.random.RandomState(0)
    ho, wo = h // stride, w // stride
    flops = 2.0 * batch * ho * wo * cout * cin * kk * kk

    # --- XLA conv (NCHW, same-padding) ---
    x = jnp.asarray(rng.randn(batch, cin, h, w), jnp.bfloat16)
    wgt = jnp.asarray(rng.randn(cout, cin, kk, kk) * 0.05, jnp.bfloat16)
    pad = ((kk // 2, kk // 2),) * 2

    def conv(xx):
        return jax.lax.conv_general_dilated(
            xx, wgt, (stride, stride), pad,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    t_conv = _timed(conv, x, iters)

    # --- implicit-GEMM equivalent via the XLA matmul emitter ---
    m = batch * ho * wo
    k = cin * kk * kk
    a = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
    b = jnp.asarray(rng.randn(k, cout) * 0.05, jnp.bfloat16)
    t_gemm = _timed(lambda aa: aa @ b, a, iters)

    # --- naively-tiled Pallas matmul at the same shape ---
    t_pallas = None
    bm = 512
    kp = ((k + 127) // 128) * 128
    np_ = ((cout + 127) // 128) * 128
    if m % bm == 0 and (bm * kp + kp * np_ + bm * np_) * 2 * 2 < 14e6:
        ap = jnp.zeros((m, kp), jnp.bfloat16).at[:, :k].set(a)
        bp = jnp.zeros((kp, np_), jnp.bfloat16).at[:k, :cout].set(b)

        def mk(x_ref, w_ref, o_ref):
            o_ref[...] = jnp.dot(
                x_ref[...], w_ref[...],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

        def pallas_mm(aa):
            return pl.pallas_call(
                mk, grid=(m // bm,),
                in_specs=[pl.BlockSpec((bm, kp), lambda i: (i, 0)),
                          pl.BlockSpec((kp, np_), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((bm, np_), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((m, np_), aa.dtype),
            )(aa)

        try:
            t_pallas = _timed(pallas_mm, ap, iters)
        except Exception:
            t_pallas = None

    return flops, t_conv, t_gemm, t_pallas


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--shape", type=int, default=None,
                    help="measure only RESNET50_CONVS[i] (emit one "
                         "json line) — lets a driving shell give each "
                         "shape its own timeout")
    args = ap.parse_args()

    if args.shape is not None:
        import json

        cin, h, w, cout, kk, stride, cnt = RESNET50_CONVS[args.shape]
        flops, t_conv, t_gemm, t_pal = measure_shape(
            cin, h, w, cout, kk, stride, args.batch, args.iters)
        print(json.dumps({
            "desc": f"{cin}x{h}x{w}->{cout} k{kk}s{stride}",
            "flops": flops, "count": cnt, "t_conv": t_conv,
            "t_gemm": t_gemm, "t_pallas": t_pal}), flush=True)
        return

    peak = 197e12
    rows = []
    tot_flops = tot_conv = tot_gemm = 0.0
    print(f"{'shape':>34} | {'conv TF/s':>9} | {'gemm TF/s':>9} | "
          f"{'pallas':>7} | count")
    for cin, h, w, cout, kk, stride, cnt in RESNET50_CONVS:
        flops, t_conv, t_gemm, t_pal = measure_shape(
            cin, h, w, cout, kk, stride, args.batch, args.iters)
        conv_tf = flops / t_conv / 1e12
        gemm_tf = flops / t_gemm / 1e12
        pal_tf = flops / t_pal / 1e12 if t_pal else float("nan")
        desc = f"{cin}x{h}x{w}->{cout} k{kk}s{stride}"
        print(f"{desc:>34} | {conv_tf:9.1f} | {gemm_tf:9.1f} | "
              f"{pal_tf:7.1f} | x{cnt}", flush=True)
        rows.append((desc, conv_tf, gemm_tf, pal_tf, cnt))
        tot_flops += flops * cnt
        tot_conv += t_conv * cnt
        tot_gemm += t_gemm * cnt
    conv_mfu = tot_flops / tot_conv / peak
    gemm_mfu = tot_flops / tot_gemm / peak
    print(f"\nFLOP-weighted ResNet-50 fwd: conv lowering MFU "
          f"{conv_mfu:.3f}; implicit-GEMM matmul UPPER BOUND MFU "
          f"{gemm_mfu:.3f} (a real conv kernel lands below it: patch "
          f"assembly + halos come out of the same budget)")


if __name__ == "__main__":
    main()
