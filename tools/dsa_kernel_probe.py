"""Probe: the kernels of DeepSeek sparse attention alone at the GLM-5
cell's shape, as ``tools/mla_kernel_probe.py`` is for ``mla_decode``:

    python3 tools/dsa_kernel_probe.py        # on one TPU chip

32 streams over a pool of 4,096 pages of 128 rows (latent rows of 640
lanes, index keys of 128), tables of 160 pages, the streams' lengths drawn
as the cell's are (a prompt of lognormal(7,168, 0.25) in 3,072-12,288 and
a uniform share of an output of lognormal(4,096, 0.4) in 1,536-8,192),
2,048 rows selected a stream. It prints

1. each kernel against its jnp reference on the cell's lengths (every
   backend; the CPU runs the kernels interpreted and stops there: a time
   comes from the chip only): ``dsa_select`` against ``top_mask`` (then
   ``keep_of``) bit for bit, on the scores and on them rounded (ties at
   the k-th), and ``dsa_prefill_select`` against ``prefill_select`` at
   the 8,192 bucket, on random operands (the pairs on which they differ:
   a different order of a score's sums may move it across the k-th) and
   on small integers (exact scores, many ties);
2. microseconds a call (one layer) and a row: ``dsa_index`` beside its
   XLA form (``dsa_index_reference``), the decode step's selection in VMEM
   (``dsa_select``, with a sweep of its streams a program and chunk of
   lanes) beside the
   XLA form (``ops/dsa.top_mask``: the k-th score by 32 passes over the
   scores in the HBM, the tie count, ``keep_of``) and one ``lax.top_k``
   of the scores (a sort), a prompt's block of 256 queries' mask by
   halvings and by a top-k, a prompt's whole selection (one layer) at the
   8,192 and 16,384 buckets scored and selected in VMEM
   (``dsa_prefill_select``, with a sweep of its block, key tile, summed
   rows and selected rows at 16,384) beside ``ops/dsa.prefill_select``
   (XLA: a head
   loop and the halvings over ``[256, T]`` in the HBM, a block at a
   time), ``mla_sparse_decode`` (the page walk under the selection) and,
   for the same lengths, the dense page walk (``mla_decode`` over every
   row), each beside the least the HBM allows for the bytes it needs
   (``benchmark/work/``; the prompt's selection beside nothing).

``--tiny`` runs the same control flow at toy sizes (here, on the CPU,
the kernels interpreted): it prints no result.
"""
import functools
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
from paddle_tpu.ops.pallas import dsa_select as vmem  # noqa: E402
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
#: a prompt's buckets, and the calls chained for a prompt's selection
PROMPTS, PROMPT_STEPS = (8192, 16384), 2
if TINY:
    B, NH, TOPK, PAGES, PPS, STEPS = 4, 4, 512, 64, 16, 2
    IH, PROMPTS = 4, (1024,)


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


# --- selection forms the probe compares (the program's is dsa_select)
def select_mask(scores, lengths, k):
    """The decode step's selection in XLA (``top_mask``)."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
    return dsa.top_mask(scores, pos < lengths[:, None], k)


def _with(names, values, fn):
    """``fn()`` with the ``dsa_select`` module's constants ``names`` set
    to ``values`` where not None (read as a kernel is traced)."""
    raw = [getattr(vmem, n) for n in names]
    for n, v in zip(names, values):
        if v is not None:
            setattr(vmem, n, v)
    try:
        return fn()
    finally:
        for n, v in zip(names, raw):
            setattr(vmem, n, v)


def select_in_vmem(scores, lengths, k, rows=None, chunk=None):
    """The program's decode selection (``dsa_select``), its streams a
    program and chunk of lanes set where given."""
    return _with(("DECODE_ROWS", "DECODE_CHUNK"), (rows, chunk),
                 lambda: vmem.dsa_select_kernel(scores, lengths, topk=k,
                                                interpret=not ON_TPU))


def prompt_in_vmem(q, w, k, block=None, tile=None, sums=None, rows=None,
                   chunk=None):
    """A prompt's selection as the program makes it
    (``dsa_prefill_select``), its block, key tile, rows summed at once,
    rows selected at once and chunk of lanes set where given."""
    return _with(("KEY_TILE", "SUM_ROWS", "PREFILL_ROWS", "PREFILL_CHUNK"),
                 (tile, sums, rows, chunk),
                 lambda: vmem.dsa_prefill_select_kernel(
                     q, w, k, TOPK, ISCALE, block=block,
                     interpret=not ON_TPU))


def prompt_arrays(t, seed=5):
    """A prompt's index queries, weights and keys (bfloat16 and float32,
    the cell's widths)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (t, IH, IDIM), jnp.bfloat16),
            jax.random.normal(k2, (t, IH), jnp.float32),
            jax.random.normal(k3, (t, IDIM), jnp.bfloat16))


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
    for what, scores in ((" on scores rounded (ties at the k-th)",
                          jnp.round(want / 2)), ("", want)):
        keep = jax.jit(lambda s, l: vmem.dsa_select_reference(
            s, l, topk=TOPK))(scores, lengths)
        got = jax.jit(lambda s, l: select_in_vmem(s, l, TOPK))(scores,
                                                              lengths)
        print(f"dsa_select against top_mask{what}: "
              f"{int(jnp.sum(got != keep))} positions differ of "
              f"{keep.size}", flush=True)

    def sparse_call(q, pool, keep, lengths, tables):
        return sparse.mla_sparse_decode_kernel(
            q, pool, keep, lengths, tables, dv=DV, sm_scale=SCALE,
            interpret=not ON_TPU)

    got = jax.jit(sparse_call)(q, pool, keep, lengths, tables)
    want = jax.jit(lambda *a: sparse.mla_sparse_decode_reference(
        *a, dv=DV, sm_scale=SCALE))(q, pool, keep, lengths, tables)
    print(f"mla_sparse_decode against its reference: largest gap "
          f"{float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))):.3e}",
          flush=True)
    parts = prompt_arrays(PROMPTS[0])
    for what, (pq, pw, pk) in (
            ("", parts),
            (" on small integers (exact scores, ties at the k-th)",
             [jnp.round(a * 1.5).astype(a.dtype) for a in parts])):
        got = jax.jit(prompt_in_vmem)(pq, pw, pk)
        want = jax.jit(lambda *a: dsa.prefill_select(*a, TOPK, ISCALE))(
            pq, pw, pk)
        differ = int(jnp.sum(jnp.any(got != want, axis=1)))
        print(f"dsa_prefill_select against prefill_select at {PROMPTS[0]}"
              f"{what}: {int(jnp.sum(got != want))} pairs differ of "
              f"{int(jnp.sum(want))} selected, in {differ} of {PROMPTS[0]} "
              f"rows", flush=True)
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
        ("selection in VMEM (dsa_select, the program's)",
         lambda s, l: select_in_vmem(s, l, TOPK), (scores, lengths),
         least(0, sum_ctx * 4), sum_ctx),
    ] + [
        (f"selection in VMEM, {r} streams a program, chunks of {c} lanes",
         lambda s, l, r=r, c=c: select_in_vmem(s, l, TOPK, r, c),
         (scores, lengths), least(0, sum_ctx * 4), sum_ctx)
        for r, c in ((8, 2048), (16, 512), (32, 256), (32, 512))
    ] + [
        ("selection in XLA (halvings, tie count, keep_of)",
         lambda s, l: vmem.dsa_select_reference(s, l, topk=TOPK),
         (scores, lengths), least(0, sum_ctx * 4), sum_ctx),
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
         sparse_call, (q, pool, vmem.dsa_select_reference(
             scores, lengths, topk=TOPK), lengths, tables),
         least(f_s, b_s), rows),
        ("dense page walk (mla_decode, every row)",
         lambda q, p, l, t: mla_decode_kernel(q, p, l, t, dv=DV,
                                              sm_scale=SCALE,
                                              interpret=not ON_TPU),
         (q, pool, lengths, tables), least(f_d, b_d), sum_ctx),
    ]
    for t in PROMPTS:
        parts = prompt_arrays(t)
        pairs = t * (t + 1) // 2
        cases += [
            (f"a prompt's selection at {t} in VMEM (dsa_prefill_select)",
             prompt_in_vmem, parts, 0.0, pairs),
            (f"a prompt's selection at {t} in XLA (prefill_select)",
             lambda *a: dsa.prefill_select(*a, TOPK, ISCALE), parts, 0.0,
             pairs),
        ]
        if t == PROMPTS[-1] and not TINY:
            cases += [
                (f"a prompt's selection at {t} in VMEM, block {b}, key "
                 f"tile {kt}, {sr} rows summed, {r} rows x {c} lanes "
                 f"selected at once",
                 functools.partial(prompt_in_vmem, block=b, tile=kt,
                                   sums=sr, rows=r, chunk=c), parts, 0.0,
                 pairs)
                for b, kt, sr, r, c in (
                    (128, 128, 64, 32, 128), (256, 256, 32, 32, 256),
                    (256, 128, 32, 32, 256), (256, 128, 64, 64, 256),
                    (256, 128, 64, 128, 128), (256, 128, 64, 256, 128))]
    for name, fn, args, lo, n in cases:
        try:
            s = time_call(fn, *args, steps=PROMPT_STEPS
                          if name.startswith("a prompt") else STEPS)
        except Exception as e:  # noqa: BLE001 — report and go on
            print(f"{name}: failed: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
            continue
        if not ON_TPU:
            print(f"{name}: ran (no time off the chip)", flush=True)
            continue
        print(f"{name}: {s * 1e6:.1f} us a call, {s * 1e9 / n:.2f} ns a "
              f"row over {n} rows" + (f"; least {lo * 1e6:.1f} us "
                                      f"({100 * lo / s:.1f}%)" if lo else ""),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
