"""Probe: paged-attention decode kernels vs this repo's jnp
block-gather decode attention — equivalence + carry-chained speed at
645M serving shapes. Three contenders over the same pool:

1. jax's official TPU Pallas ``paged_attention`` (generic long-context
   kernel: per-compute-block async-copy pipeline);
2. the jnp gather reference (what ``block_mha_p`` decode does);
3. THIS repo's decode-specialized kernel
   (``paddle_tpu/ops/pallas/paged_attention.py``): grid
   ``(batch, pages)``, whole page per program for all heads, block
   tables/lengths in SMEM via scalar prefetch, online-softmax scratch
   in VMEM, fused length masking — the short-context overhead the
   official kernel pays is exactly what it strips.

MEASURED (v5e, 2026-07-31, B=8/NH=16/DH=128, 256-slot pool; official
kernel vs gather): official kernel matches the masked-softmax
reference (max err 1e-3, bf16 scale) and runs 1350 us/step vs 2155
for the jnp gather — 1.6x faster, but still ~6x the dense scan's
ENTIRE per-layer decode budget (~200 us incl. matmuls) at this
context length, because its multi-compute-block pipeline is
overhead-bound at 2 pages/seq.

MEASURED (CPU interpret, 2026-08-04, decode-specialized kernel): the
new kernel is numerically equivalent to the masked-softmax reference
(max abs err < 2e-3 at bf16 scale, bit-level vs the fp32 reference in
f32 — pinned by tests/test_paged_attention_kernel.py, which is this
probe's equivalence check promoted to pytest). TPU wall-clock: rerun
this probe on a v5e to refresh the numbers; the decode kernel issues
one fused pass per (sequence, page) with zero gathered K/V
materialization, eliminating both the gather's HBM round-trip (path 2)
and the per-compute-block pipeline overhead (path 1) that dominate at
short context.

Equivalence runs on every backend (CPU uses interpret mode for the
decode kernel and skips the official kernel, which has no interpret
path); timing loops run on TPU only.
"""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    paged_attention_decode_kernel, paged_attention_decode_reference)

from paddle_tpu.core.flags import pallas_mode  # noqa: E402
from paddle_tpu.device import chip  # noqa: E402

chip.setup_compile_cache()
print(f"# device: {chip.device_info()}  pallas_mode: {pallas_mode()}")
ON_TPU = pallas_mode() == "compiled"

B, NH, KVH, DH = 8, 16, 16, 128
PAGE = 128
PAGES_PER_SEQ = 2          # 256 max positions
NPAGES = B * PAGES_PER_SEQ
STEPS = 50

rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(size=(B, NH, DH)) * 0.3, jnp.bfloat16)
k_pages = jnp.asarray(rng.normal(size=(KVH, NPAGES, PAGE, DH)) * 0.3,
                      jnp.bfloat16)
v_pages = jnp.asarray(rng.normal(size=(KVH, NPAGES, PAGE, DH)) * 0.3,
                      jnp.bfloat16)
lengths = jnp.asarray(rng.integers(100, 250, size=(B,)), jnp.int32)
page_indices = jnp.asarray(
    np.arange(NPAGES, dtype=np.int32).reshape(B, PAGES_PER_SEQ))


def official_kernel(q, kp, vp, lens, idx):
    from jax.experimental.pallas.ops.tpu.paged_attention import \
        paged_attention

    return paged_attention(q, kp, vp, lens, idx,
                           pages_per_compute_block=PAGES_PER_SEQ)


def decode_kernel(q, kp, vp, lens, idx):
    return paged_attention_decode_kernel(q, kp, vp, lens, idx,
                                         interpret=not ON_TPU)


def reference(q, kp, vp, lens, idx):
    # the masked-softmax oracle == block_mha_p's decode gather
    return paged_attention_decode_reference(q, kp, vp, lens, idx)


def reference_unscaled(q, kp, vp, lens, idx):
    # jax's official paged_attention applies NO sm scale (the caller
    # pre-scales q) — compare it against the same unscaled softmax
    return paged_attention_decode_reference(q, kp, vp, lens, idx,
                                            sm_scale=1.0)


def _err(a, b):
    return np.max(np.abs(np.asarray(a, np.float32)
                         - np.asarray(b, np.float32)))


out_r = jax.jit(reference)(q, k_pages, v_pages, lengths, page_indices)
out_d = jax.jit(decode_kernel)(q, k_pages, v_pages, lengths, page_indices)
err_d = _err(out_d, out_r)
print(f"decode-kernel-vs-reference max abs err: {err_d:.4f} (bf16 scale)")
assert err_d < 0.05, \
    "decode kernel output diverges from masked-softmax reference"
if ON_TPU:
    out_k = jax.jit(official_kernel)(q, k_pages, v_pages, lengths,
                                     page_indices)
    out_ru = jax.jit(reference_unscaled)(q, k_pages, v_pages, lengths,
                                         page_indices)
    err_k = _err(out_k, out_ru)
    print(f"official-kernel-vs-reference max abs err: {err_k:.4f}")
    assert err_k < 0.05, \
        "official kernel output diverges from masked-softmax reference"


def bench(fn):
    # carry-chain: feed the output back as q so steps serialize on-device
    @jax.jit
    def chained(q0):
        def body(qc, _):
            o = fn(qc, k_pages, v_pages, lengths, page_indices)
            o = (o / (jnp.max(jnp.abs(o)).astype(o.dtype) + 1)).astype(
                qc.dtype)
            return o, ()
        out, _ = jax.lax.scan(body, q0, None, length=STEPS)
        return out
    o = chained(q); jax.block_until_ready(o)
    t0 = time.perf_counter()
    o = chained(q); jax.block_until_ready(o)
    return (time.perf_counter() - t0) / STEPS


if ON_TPU:
    t_k = bench(official_kernel)
    t_r = bench(reference)
    t_d = bench(decode_kernel)
    print(f"official pallas paged_attention: {t_k*1e6:.0f} us/step")
    print(f"jnp gather reference:            {t_r*1e6:.0f} us/step")
    print(f"decode-specialized kernel:       {t_d*1e6:.0f} us/step")
else:
    print("no TPU attached: equivalence verified (interpret mode); "
          "timing loops skipped")
