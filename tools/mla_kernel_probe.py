"""Probe: the latent decode kernel (``mla_decode``) alone at the
DeepSeek-V2 cell's shape, as ``tools/paged_kernel_probe.py`` is for
``paged_decode``:

    chiprun -- python3 tools/mla_kernel_probe.py

192 streams of 128 absorbed queries over a pool of 6,656 pages of 128
rows in 640 lanes, the value the first 512 lanes, tables of 64 pages.
It prints

1. the kernel against ``mla_decode_reference`` on the eight longest
   streams (every backend; the CPU runs the kernel interpreted and
   stops there: a time comes from the chip only);
2. microseconds a call over the cell's lengths (a prompt of
   lognormal(512, 0.8) in 128-2,048 and a share of an output of
   lognormal(4,096, 0.5) in 512-6,144, scaled to the 1,689 rows a stream
   the cell's window held: PERF.md section 5) and over uniform ones,
   beside the least the chip allows for the rows read
   (``benchmark/work/mla_decode.py`` counts them, the roofline the
   benchmark's ``mla_decode_roofline`` reads in the cell);
3. the fit ``us a page + us a turn + us a stream`` (least squares over
   2, 4 and 8 pages a turn and three sets of lengths; a page is one the
   products run over, ``pages_computed``, or a whole turn's on a tree
   from before it);
4. what holds the page, by switches that exist here only: the same call
   with the score product replaced by a stand-in the vector unit makes
   (``values alone``), with the value product replaced (``scores
   alone``), and with both (``copies alone``: the walk, the copies and
   the softmax on stand-ins). The stand-ins go in over
   ``mla_decode._scores`` / ``._values``; a tree without them skips the
   section.

Copied into another checkout's ``tools/`` it measures that tree.

MEASURED (v5e, 2026-10-05, PR 36, both trees in one call; us a call,
the tree before that PR -> after it):

  cell lengths, seed 0 (324,190 rows):   999 -> 833   (52.2 -> 62.3% of
                                         the roofline's 521 us; 1.292 ->
                                         1.038 rows computed a row read)
  cell lengths, seed 1:                1,006 -> 840
  uniform, 13 pages a stream:            942 -> 784
  uniform, 13 pages less a row:          942 -> 784
  fit, us:   0.216 a page + 0.545 a turn + 0.500 a stream
          -> 0.178 a page + 0.572 a turn + 0.685 a stream
  after only: copies alone 649, values alone 710, scores alone 763,
  both products 833.
"""
import os
import sys
import time
from unittest import mock

import numpy as np
import jax
import jax.numpy as jnp

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from paddle_tpu.ops.pallas import mla_decode as kernel_module  # noqa: E402
from paddle_tpu.ops.pallas.mla_decode import (  # noqa: E402
    mla_decode_kernel, mla_decode_reference)
from paddle_tpu.core.flags import pallas_mode  # noqa: E402
from paddle_tpu.device import chip  # noqa: E402

from benchmark.harness.peaks import CHIP_PEAKS  # noqa: E402
from benchmark.work.mla_decode import count as work_of  # noqa: E402

ON_TPU = pallas_mode() == "compiled"

# the cell's shape (BENCHMARK.json; PERF.md section 4)
B, NH, LANES, DV, RANK, ROPE = 192, 128, 640, 512, 512, 64
PAGE, PAGES, PPS = 128, 6656, 64
SCALE = 0.1147
MEAN_ROWS = 1689              # a stream's rows in the cell's window
STEPS = 100


def cell_lengths(seed=0):
    """192 lengths as the cell's streams hold them mid-run."""
    r = np.random.default_rng(seed)
    prompt = np.clip(r.lognormal(np.log(512), 0.8, B), 128, 2048)
    output = np.clip(r.lognormal(np.log(4096), 0.5, B), 512, 6144)
    lens = prompt + r.uniform(0, 1, B) * output
    return np.clip(lens * (MEAN_ROWS / lens.mean()), 1,
                   PPS * PAGE).astype(np.int32)


def case(lens, seed=0):
    """(q, pool, lengths, tables) of one decode call: every stream's pages
    its own, scattered over the pool."""
    r = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    held = -(-lens // PAGE)
    free = iter(r.permutation(PAGES))
    tables = np.zeros((len(lens), PPS), np.int32)
    for row, n in enumerate(held):
        tables[row, :n] = [next(free) for _ in range(n)]
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (len(lens), NH, LANES), jnp.bfloat16)
            * 0.3,
            jax.random.normal(ks[1], (1, PAGES, PAGE, LANES), jnp.bfloat16),
            jnp.asarray(lens), jnp.asarray(tables))


def call(q, pool, lengths, tables):
    return mla_decode_kernel(q, pool, lengths, tables, dv=DV,
                             sm_scale=SCALE, interpret=not ON_TPU)


def time_call(args, steps=STEPS):
    """Seconds a call, ``steps`` calls chained through the query. The
    kernel is traced anew, so a stand-in patched in is what runs; the
    pool goes in as an argument (closed over, its gigabyte would be a
    constant of the program and of its cache entry)."""
    @jax.jit
    def chained(q0, *rest):
        def body(qc, _):
            out = call(qc, *rest)
            return qc + jnp.pad(out, ((0, 0), (0, 0),
                                      (0, LANES - DV))) * 0, ()
        return jax.lax.scan(body, q0, None, length=steps)[0]

    jax.block_until_ready(chained(*args))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(*args))
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def pages_and_turns(lens):
    """(pages the products run over, turns) of streams of these lengths."""
    lens = np.asarray(lens)
    held = -(-lens // PAGE)
    turns = -(-held // kernel_module.TURN)
    computed = getattr(kernel_module, "pages_computed", None)
    pages = (computed(lens, PAGE, PPS) if computed is not None
             else turns * kernel_module.TURN)     # a tree of whole turns
    return int(np.sum(pages)), int(np.sum(turns))


def least_seconds(lens):
    """The roofline's least time for one call over these lengths."""
    peaks = CHIP_PEAKS["TPU v5 lite"]
    flops, nbytes = work_of(int(np.sum(lens)), len(lens), NH, RANK, ROPE)
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def equivalence_section():
    lens = cell_lengths()
    args = case(lens)
    top = np.argsort(-lens)[:8]
    small = (args[0][top], args[1], args[2][top], args[3][top])
    got = jax.jit(call)(*small)
    want = mla_decode_reference(*small, dv=DV, sm_scale=SCALE)
    err = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want, np.float32))))
    print(f"kernel-vs-reference max abs err on the eight longest "
          f"streams ({int(lens[top].min())}-{int(lens[top].max())} rows): "
          f"{err:.4f} (bf16 scale)")
    assert err < 0.05, "mla_decode diverges from its masked-softmax reference"


def report(name, lens):
    t = time_call(case(lens))
    pages, turns = pages_and_turns(lens)
    floor = least_seconds(lens)
    print(f"{name}: {t * 1e6:.0f} us/call, {int(np.sum(lens))} rows of "
          f"{len(lens)} streams, {pages} pages computed in {turns} turns "
          f"({pages * PAGE / max(int(np.sum(lens)), 1):.3f} rows computed a "
          f"row read), {t * 1e9 / max(pages, 1):.0f} ns a page computed, "
          f"{floor * 1e6:.0f} us at the roofline ({100 * floor / t:.1f}%)")
    return t


def cells_section():
    for seed in (0, 1):
        report(f"cell lengths (seed {seed})", cell_lengths(seed))
    report("uniform, 13 pages a stream", np.full(B, 13 * PAGE))
    report("uniform, 13 pages less a row", np.full(B, 13 * PAGE - 1))


def fit_section():
    """us a page + us a turn + us a stream: least squares over 2, 4 and 8
    pages a turn (the module's ``TURN``, set here only) and three sets of
    lengths."""
    sets = {"cell lengths": cell_lengths(),
            "13 pages a stream": np.full(B, 13 * PAGE),
            "32 pages a stream": np.full(B, 32 * PAGE)}
    rows, times = [], []
    for turn in (2, 4, 8):
        with mock.patch.object(kernel_module, "TURN", turn):
            for name, lens in sets.items():
                pages, turns = pages_and_turns(lens)
                rows.append((pages / B, turns / B, 1.0))
                times.append(time_call(case(lens)) / B * 1e6)
                print(f"  {turn} pages a turn, {name}: {times[-1]:.3f} us a "
                      f"stream ({pages / B:.1f} pages computed, "
                      f"{turns / B:.1f} turns)")
    (page, turn, stream), *_ = np.linalg.lstsq(np.asarray(rows),
                                               np.asarray(times), rcond=None)
    print(f"fit: {page:.3f} us a page + {turn:.3f} us a turn + "
          f"{stream:.3f} us a stream (a page is "
          f"{PAGE * LANES * 2 / 819e9 * 1e6:.2f} us at the HBM's peak and "
          f"{NH * (2 * RANK + ROPE) * 2 * PAGE / 197e12 * 1e6:.2f} at the "
          f"MXU's)")


def _no_scores(q, k):
    """Stand-in for the score product: a tile of ``k``'s own numbers a
    page, made by the vector unit."""
    return jnp.concatenate([k[:q.shape[0], :128].astype(jnp.float32)]
                           * (k.shape[0] // 128), axis=1)


def _no_values(p, k, dv):
    """Stand-in for the value product: the weights' row sums over one row
    of values."""
    return (jnp.sum(p, axis=-1, keepdims=True)
            + k[:1, :dv].astype(jnp.float32))


def switches_section():
    if not (hasattr(kernel_module, "_scores")
            and hasattr(kernel_module, "_values")):
        print("switches: this tree's kernel has no _scores/_values to "
              "stand in for; skipped")
        return
    lens = cell_lengths()
    whole = report("both products", lens)
    for name, stubs in (("values alone", {"_scores": _no_scores}),
                        ("scores alone", {"_values": _no_values}),
                        ("copies alone", {"_scores": _no_scores,
                                          "_values": _no_values})):
        with mock.patch.multiple(kernel_module, **stubs):
            t = time_call(case(lens))
        print(f"{name}: {t * 1e6:.0f} us/call "
              f"({100 * t / whole:.0f}% of both products)")


if __name__ == "__main__":
    if ON_TPU:
        chip.setup_compile_cache()
    print(f"# device: {chip.device_info()}  pallas_mode: {pallas_mode()}  "
          f"TURN: {kernel_module.TURN}")
    equivalence_section()
    if ON_TPU:
        cells_section()
        fit_section()
        switches_section()
    else:
        print("no TPU attached: equivalence verified (interpret mode); "
              "timing skipped")
