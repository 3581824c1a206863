"""Probe: the two kernels of a DeepSeek-sparse-attention decode step alone
at the GLM-5 cell's shape, as ``tools/mla_kernel_probe.py`` is for
``mla_decode``:

    python3 tools/dsa_kernel_probe.py        # on one TPU chip

32 streams over a pool of 4,096 pages of 128 rows (latent rows of 640
lanes, index keys of 128), tables of 160 pages, the streams' lengths drawn
as the cell's are (a prompt of lognormal(7,168, 0.25) in 3,072-12,288 and
a uniform share of an output of lognormal(4,096, 0.4) in 1,536-8,192),
2,048 rows selected a stream. It prints

1. each kernel against its jnp reference on the cell's lengths (every
   backend; the CPU runs the kernels interpreted and stops there: a time
   comes from the chip only);
2. microseconds a call (one layer) and a row: ``dsa_index`` beside its
   XLA form (``dsa_index_reference``), the decode step's selection
   (``ops/dsa.top_mask``: the k-th score by 32 halvings of its bits and
   the tie count) beside one ``lax.top_k`` of the scores (a sort), a
   prompt's block of 256 queries' mask by halvings and by a top-k,
   ``mla_sparse_decode`` (the page walk under the selection) and, for the
   same lengths, the dense page walk (``mla_decode`` over every row), each
   beside the least the HBM allows for the bytes it needs
   (``benchmark/work/``).

``--tiny`` runs the same control flow at toy sizes (here, on the CPU,
the kernels interpreted): it prints no result. Copied into another
checkout's ``tools/`` it measures that tree.
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

from paddle_tpu.core.flags import pallas_mode  # noqa: E402
from paddle_tpu.device import chip  # noqa: E402
from paddle_tpu.ops import dsa  # noqa: E402
from paddle_tpu.ops.pallas import mla_sparse_decode as sparse  # noqa: E402
from paddle_tpu.ops.pallas.dsa_index import (dsa_index_kernel,  # noqa: E402
                                             dsa_index_reference)
from paddle_tpu.ops.pallas.mla_decode import mla_decode_kernel  # noqa: E402

from benchmark.harness.peaks import CHIP_PEAKS  # noqa: E402
from benchmark.work import dsa_index as index_work  # noqa: E402
from benchmark.work import mla_decode as mla_work  # noqa: E402

ON_TPU = pallas_mode() == "compiled"
TINY = "--tiny" in sys.argv

# the cell's shape (BENCHMARK.json; PERF.md section 4)
B, NH, LANES, DV, RANK, ROPE = 32, 64, 640, 512, 512, 64
IH, IDIM, TOPK = 32, 128, 2048
PAGE, PAGES, PPS = 128, 4096, 160
SCALE, ISCALE = 256 ** -0.5, 128 ** -0.5
STEPS = 50
if TINY:
    B, NH, TOPK, PAGES, PPS, STEPS = 4, 4, 512, 64, 16, 2


def cell_lengths(seed=0):
    rng = np.random.default_rng(seed)
    prompt = np.clip(np.exp(np.log(7168) + 0.25 * rng.normal(size=B)),
                     3072, 12288)
    out = np.clip(np.exp(np.log(4096) + 0.4 * rng.normal(size=B)),
                  1536, 8192)
    lens = (prompt + rng.uniform(size=B) * out).astype(np.int32)
    return np.minimum(lens, PPS * PAGE)


def arrays(lens, seed=0):
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    pool = jax.random.normal(k1, (1, PAGES, PAGE, LANES), jnp.bfloat16)
    ipool = jax.random.normal(k2, (1, PAGES, PAGE, IDIM), jnp.bfloat16)
    q = jax.random.normal(k3, (B, NH, LANES), jnp.bfloat16)
    qi = jax.random.normal(k4, (B, IH, IDIM), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(B, IH)), jnp.float32)
    # B x PPS table entries over a pool of fewer pages: the probe only
    # reads, so streams may share a page
    tables = jnp.asarray((rng.permutation(B * PPS) % PAGES).reshape(B, PPS),
                         jnp.int32)
    return q, qi, w, pool, ipool, jnp.asarray(lens, jnp.int32), tables


def time_call(fn, first, *rest, steps=STEPS):
    """Seconds a call of ``fn(first, *rest)``, ``steps`` calls chained
    through ``first``; the pools go in as arguments (closed over, their
    gigabytes would be constants of the program)."""
    @jax.jit
    def chained(x0, *rest):
        def body(x, _):
            out = fn(x, *rest)
            tie = sum(jnp.sum(o.astype(jnp.float32)) for o in
                      jax.tree.leaves(out)) * 0
            return x + tie.astype(x.dtype), ()
        return jax.lax.scan(body, x0, None, length=steps)[0]

    jax.block_until_ready(chained(first, *rest))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(first, *rest))
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


# --- selection forms the probe compares (the program's is dsa.top_mask)
def select_mask(scores, lengths, k):
    """The decode step's selection as the program makes it."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
    return dsa.top_mask(scores, pos < lengths[:, None], k)


def select_by_sort(scores, lengths, k):
    """One ``lax.top_k`` of the scores seen (a sort; ties as the backend
    breaks them)."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
    return jax.lax.top_k(
        jnp.where(pos < lengths[:, None], scores, -jnp.inf), k)[1]


def _mask_by_sort(scores, seen, k):
    """dsa.top_mask with the k-th score from a ``lax.top_k`` (a sort)."""
    s = jnp.where(seen, scores, -jnp.inf)
    theta = jax.lax.top_k(s, k)[0][:, -1:]
    above = seen & (s > theta)
    tie = seen & (s == theta)
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= need))


def least(flops, nbytes):
    pk = CHIP_PEAKS["TPU v5 lite"]
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def main():
    if ON_TPU:
        chip.setup_compile_cache()
    print(f"# device: {chip.device_info()}  pallas_mode: {pallas_mode()}",
          flush=True)
    lens = cell_lengths()
    q, qi, w, pool, ipool, lengths, tables = arrays(lens)
    sum_ctx, rows = int(lens.sum()), int(np.minimum(lens, TOPK).sum())
    print(f"# {B} streams, {sum_ctx} rows ({sum_ctx / B:.0f} a stream), "
          f"{rows} selected ({rows / sum_ctx:.3f} of them)", flush=True)

    # 1. equivalence
    def index_call(qi, w, ipool, lengths, tables):
        return dsa_index_kernel(qi, w, ipool, lengths, tables, scale=ISCALE,
                                interpret=not ON_TPU)

    got = jax.jit(index_call)(qi, w, ipool, lengths, tables)
    want = jax.jit(lambda *a: dsa_index_reference(*a, scale=ISCALE))(
        qi, w, ipool, lengths, tables)
    seen = np.arange(PPS * PAGE)[None, :] < lens[:, None]
    gap = np.abs(np.asarray(got)[seen] - np.asarray(want)[seen]).max()
    print(f"dsa_index against its reference: largest gap {gap:.3e} "
          f"(scores of spread {float(np.std(np.asarray(want)[seen])):.3f})",
          flush=True)
    chosen = jax.jit(lambda s, l: select_mask(s, l, TOPK))(want, lengths)

    def sparse_call(q, pool, chosen, lengths, tables):
        return sparse.mla_sparse_decode_kernel(
            q, pool, chosen, lengths, tables, dv=DV, sm_scale=SCALE,
            interpret=not ON_TPU)

    got = jax.jit(sparse_call)(q, pool, chosen, lengths, tables)
    want = jax.jit(lambda *a: sparse.mla_sparse_decode_reference(
        *a, dv=DV, sm_scale=SCALE))(q, pool, chosen, lengths, tables)
    print(f"mla_sparse_decode against its reference: largest gap "
          f"{float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))):.3e}",
          flush=True)
    if not ON_TPU and not TINY:
        print("# not a TPU: no times", flush=True)
        return 0

    # 2. times
    f_i, b_i = index_work.count(sum_ctx, B, IH, IDIM)
    f_s, b_s = mla_work.count(rows, B, NH, RANK, ROPE)
    f_d, b_d = mla_work.count(sum_ctx, B, NH, RANK, ROPE)
    scores = jax.jit(index_call)(qi, w, ipool, lengths, tables)
    mask = np.asarray(jax.jit(lambda s, l: select_mask(s, l, TOPK))(
        scores, lengths))
    idx = np.asarray(jax.jit(lambda s, l: select_by_sort(s, l, TOPK))(
        scores, lengths))
    same = all(sorted(np.flatnonzero(m)) == sorted(i[:min(n, TOPK)])
               for m, i, n in zip(mask, idx, lens))
    print(f"one top-k selects as the program: {same}", flush=True)
    t = min(PPS * PAGE, 20480)
    block_s = jax.random.normal(jax.random.PRNGKey(9), (256, t),
                                jnp.float32)
    block_m = (jnp.arange(t)[None, :]
               <= t - 256 + jnp.arange(256)[:, None])

    cases = [
        ("dsa_index (kernel)", lambda qi, *a: index_call(qi, *a),
         (qi, w, ipool, lengths, tables), least(f_i, b_i), sum_ctx),
        ("dsa_index (XLA gather form)",
         lambda qi, *a: dsa_index_reference(qi, *a, scale=ISCALE),
         (qi, w, ipool, lengths, tables), least(f_i, b_i), sum_ctx),
        ("selection, the program's (halvings, tie count)",
         lambda s, l: select_mask(s, l, TOPK), (scores, lengths),
         least(0, sum_ctx * 4), sum_ctx),
        ("selection by one top-k (a sort)",
         lambda s, l: select_by_sort(s, l, TOPK), (scores, lengths),
         least(0, sum_ctx * 4), sum_ctx),
        ("prefill mask, a block of 256 x 20,480 (halvings)",
         lambda s, m: dsa.top_mask(s, m, TOPK), (block_s, block_m),
         least(0, block_s.size * 4), block_s.size),
        ("prefill mask, a block of 256 x 20,480 (top-k)",
         lambda s, m: _mask_by_sort(s, m, TOPK), (block_s, block_m),
         least(0, block_s.size * 4), block_s.size),
        ("mla_sparse_decode (the page walk under the selection)",
         sparse_call, (q, pool, jnp.asarray(mask), lengths, tables),
         least(f_s, b_s), rows),
        ("dense page walk (mla_decode, every row)",
         lambda q, p, l, t: mla_decode_kernel(q, p, l, t, dv=DV,
                                              sm_scale=SCALE,
                                              interpret=not ON_TPU),
         (q, pool, lengths, tables), least(f_d, b_d), sum_ctx),
    ]
    for name, fn, args, lo, n in cases:
        try:
            s = time_call(fn, *args)
        except Exception as e:  # noqa: BLE001 — report and go on
            print(f"{name}: failed: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
            continue
        if not ON_TPU:
            print(f"{name}: ran (no time off the chip)", flush=True)
            continue
        print(f"{name}: {s * 1e6:.1f} us a call, {s * 1e9 / n:.2f} ns a "
              f"row over {n} rows; least {lo * 1e6:.1f} us "
              f"({100 * lo / s:.1f}%)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
