"""Probe: paged-attention decode kernels vs this repo's jnp
block-gather decode attention — equivalence + carry-chained speed at
645M serving shapes. Three contenders over the same pool:

1. jax's official TPU Pallas ``paged_attention`` (generic long-context
   kernel: per-compute-block async-copy pipeline);
2. the jnp gather reference (what ``block_mha_p`` decode does);
3. THIS repo's decode-specialized kernel
   (``paddle_tpu/ops/pallas/paged_attention.py``): one program a row,
   a loop over the pages the row holds with the pools in HBM and the
   copies double-buffered by hand, block tables/lengths in SMEM via
   scalar prefetch, online-softmax scratch in VMEM, fused length
   masking — the short-context overhead the official kernel pays is
   exactly what it strips.

Then the same kernel at the benchmark cells' shapes (``CELLS``: 128
slots, the cells' pools and tables, streams of the cells' lengths), which
is where its time is judged: microseconds a call beside the least the
HBM allows for the pages the streams hold.

MEASURED (v5e, 2026-07-31, B=8/NH=16/DH=128, 256-slot pool; official
kernel vs gather): official kernel matches the masked-softmax
reference (max err 1e-3, bf16 scale) and runs 1350 us/step vs 2155
for the jnp gather — 1.6x faster, but still ~6x the dense scan's
ENTIRE per-layer decode budget (~200 us incl. matmuls) at this
context length, because its multi-compute-block pipeline is
overhead-bound at 2 pages/seq.

MEASURED (v5e, 2026-10-02, PR 28, the same B=8 shapes, 50 calls chained
in one scan): official kernel 90 us a call, jnp gather 65, this kernel
41; max abs err 0.0005 against the reference. At the cells' shapes,
200 calls chained, the kernel that walks the live pages against the
grid ``(slots, table width)`` it replaced (PR 27's tree, same process):

  cgpt590m decode-closed128 (128 streams, 677 live pages of 2,048
    entries, 650 us at the HBM's peak):   761 us a call   (1,639 before)
  cgpt590m prefill-open (6 streams, 68 live pages):
                                          102 us a call   (1,324 before)
  kexaone full layer (128 streams, 2,104 live pages of 8,192 entries,
    1,347 us at the HBM's peak):        1,795 us a call   (5,314 before)
  kexaone ring layer (256 live pages of 256, 164 us at the peak):
                                          253 us a call   (not measured)

Two pages a turn of the loop (an earlier form of this PR's kernel) took
733 and 99 us in the two cgpt590m rows.

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

    # jax's kernel mixes Python ints into int32 arithmetic, which
    # `jax_enable_x64` (on with `import paddle_tpu`) turns into int64
    with jax.enable_x64(False):
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


# --- the benchmark cells' shapes (BENCHMARK.json; PERF.md section 4) ----
# name: (slots, q heads, kv heads, pool pages, table width, live slots,
#        shortest and longest stream, window or None for a full table)
CELLS = {
    "cgpt590m decode-closed128": (128, 12, 12, 736, 16, 128, 100, 1100, None),
    "cgpt590m prefill-open": (128, 12, 12, 736, 16, 6, 900, 1900, None),
    "kexaone full layer": (128, 64, 8, 4096, 64, 128, 500, 3500, None),
    "kexaone ring layer": (128, 64, 8, 256, 2, 128, 500, 3500, 128),
}


def cell_case(name, seed=0):
    """(args, kwargs, live pages, table entries) of one decode call at a
    cell's shape: ``live`` of the slots hold a stream of a length drawn
    evenly from the cell's range, each page of its own in the pool."""
    b, nh, kvh, pages, pps, live, lo, hi, window = CELLS[name]
    r = np.random.default_rng(seed)
    lens = np.zeros(b, np.int32)
    lens[r.permutation(b)[:live]] = r.integers(lo, hi, size=live)
    held = -(-lens // PAGE) if window is None else np.full(b, pps)
    free = iter(r.permutation(pages))
    tables = np.zeros((b, pps), np.int32)
    for row, n_held in enumerate(held):
        tables[row, :n_held] = [next(free) for _ in range(n_held)]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    args = (jax.random.normal(ks[0], (b, nh, DH), jnp.bfloat16) * 0.3,
            jax.random.normal(ks[1], (kvh, pages, PAGE, DH), jnp.bfloat16),
            jax.random.normal(ks[2], (kvh, pages, PAGE, DH), jnp.bfloat16),
            jnp.asarray(lens), jnp.asarray(tables))
    kw = {}
    if window is not None:
        kw = dict(starts=jnp.maximum(args[3] - window, 0), ring=True)
        held = np.minimum(held, -(-lens // PAGE))
    return args, kw, int(held.sum()), b * pps


def time_call(fn, args, kw, steps=200):
    """Seconds a call, ``steps`` calls chained through the query."""
    q, *rest = args

    @jax.jit
    def chained(q0):
        def body(qc, _):
            return fn(qc, *rest, **kw).astype(qc.dtype), ()
        return jax.lax.scan(body, q0, None, length=steps)[0]

    jax.block_until_ready(chained(q))
    t0 = time.perf_counter()
    jax.block_until_ready(chained(q))
    return (time.perf_counter() - t0) / steps


def small_shape_section():
    """Equivalence and speed at B=8 against jax's kernel and the gather."""
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
    if ON_TPU:
        small = (q, k_pages, v_pages, lengths, page_indices)
        t_k, t_r, t_d = (time_call(fn, small, {}, steps=STEPS)
                         for fn in (official_kernel, reference,
                                    decode_kernel))
        print(f"official pallas paged_attention: {t_k*1e6:.0f} us/step")
        print(f"jnp gather reference:            {t_r*1e6:.0f} us/step")
        print(f"decode-specialized kernel:       {t_d*1e6:.0f} us/step")
    else:
        print("no TPU attached: equivalence verified (interpret mode); "
              "timing loops skipped")


def cells_section():
    """The kernel at the cells' shapes: equivalence on eight rows (the
    reference's gather of a whole K-EXAONE table is 16 GB) and time."""
    for name in CELLS:
        args, kw, pages_live, entries = cell_case(name)
        got = jax.jit(lambda *a: paged_attention_decode_kernel(*a, **kw))(
            *args)
        top = np.argsort(-np.asarray(args[3]))[:8]      # the longest rows
        err = _err(got[top], paged_attention_decode_reference(
            args[0][top], args[1], args[2], args[3][top], args[4][top],
            **{k: (v[top] if k == "starts" else v) for k, v in kw.items()}))
        assert err < 0.05, (name, err)
        page_bytes = 2 * args[1].shape[0] * PAGE * DH * 2
        t = time_call(paged_attention_decode_kernel, args, kw)
        print(f"{name}: {t*1e6:.0f} us/call, {pages_live} live pages of "
              f"{entries} table entries, {pages_live * page_bytes / 1e6:.0f}"
              f" MB ({pages_live * page_bytes / 819e9 * 1e6:.0f} us at the "
              f"HBM's peak), max abs err {err:.4f}")


if __name__ == "__main__":
    chip.setup_compile_cache()
    print(f"# device: {chip.device_info()}  pallas_mode: {pallas_mode()}")
    small_shape_section()
    if ON_TPU:
        cells_section()
