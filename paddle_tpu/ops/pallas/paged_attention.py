"""Decode-specialized paged-attention Pallas kernel.

The serving decode step reads a PAGED KV cache: each sequence's context
lives in fixed-size pages scattered across a shared pool, addressed
through a per-sequence block table (the PagedAttention / vLLM layout;
reference surface: incubate/nn/functional/block_multihead_attention.py,
whose jnp gather program is the semantics oracle here).

Why a decode-shape-specialized kernel: the official generic Pallas
``paged_attention`` is built for long contexts — a multi-stage pipeline
of per-compute-block async copies whose fixed overhead dominates at
serving shapes (tools/paged_kernel_probe.py MEASURED: 90 us a call at
B=8/NH=16/DH=128 with 2 pages/seq, where the gather takes 65 and this
kernel 41). At short context the problem is overhead, not reuse, so
this kernel strips the machinery down to the decode case:

- ONE query token per sequence (q ``[B, NH, DH]``), no q-block grid
  axis and no query-side masking;
- one program a row of the batch (grid ``(B,)``), and inside it a loop
  with a DYNAMIC trip count over the pages the row holds,
  ``ceil(length / page_size)`` of them: a page past a row's length is
  not visited, fetched or computed on, and a row of length 0 (an
  inactive serving slot) is one empty program that writes zeros. A
  call's time so follows the pages its rows hold, not ``B`` times the
  table's width (a grid ``(B, pages_per_seq)`` that clamped and masked
  the trailing pages paid 0.65-0.8 us for each of them: PERF.md, PR 28);
- the pools stay in HBM (``pl.ANY``) and each turn of the loop consumes
  one whole page for ALL heads of the row, ``[KVH, page_size, DH]`` of K
  and of V, copied by hand (``pltpu.make_async_copy``) into one of two
  VMEM buffers: the copy of page ``i + 1`` starts before the wait for
  page ``i``, and a row's last turn starts the first page of the next
  row that holds one, so the copies run back to back through the whole
  call and the online-softmax state (m, l, acc in VMEM scratch) is all
  that a row carries. One page a turn and two buffers, from the shapes:
  a page of all heads is half a megabyte and more at serving widths
  (KVH x 128 x 128 in bf16, twice), 0.6-1 us at the HBM's peak, which
  covers the turn's scalar work and the body's on the page before (at
  the benchmark's shapes a call reads at 75-86% of the HBM's peak; two
  pages a turn measured 4% faster there and are left to ROADMAP S2);
- the block table, the lengths and the lower bounds ride in SMEM via
  scalar prefetch (``pltpu.PrefetchScalarGridSpec``), so the loop
  resolves logical page ``i`` of row ``b`` to its physical pool page as
  it starts the copy: the gather IS the DMA schedule, no gathered copy
  of K/V ever materializes;
- GQA folds into the head axis: q heads are grouped by kv head
  (``[KVH, G, DH]``) and each page is fetched ONCE per sequence, never
  repeated per q head;
- length masking is fused: the lanes of a row's last page past its
  length are masked out of the softmax, so ragged batches cost the
  pages they hold.

A WINDOW is a lower bound beside the length: with ``starts`` ``[B]`` a
row attends positions ``[starts, lengths)`` only. A window layer's cache
may be a RING (``ring=True``): the table then holds ``R`` pages a row for
good, position ``p`` lives in page ``(p // page_size) % R``, and page
``i`` of the table holds the newest logical page ``<= (lengths - 1) //
page_size`` that is congruent to ``i``; what an older lap left in it lies
past ``lengths`` or before ``starts`` and is masked. The loop is the
same for all three: it walks the logical pages from ``starts //
page_size`` (0 without a bound; no further back than ``R`` pages in a
ring) to the row's last, and reads table entry ``p``, or ``p % R`` of a
ring. A page wholly before ``starts`` leaves the softmax state as it
was, so skipping it changes no bit.

Layouts match jax's kernel convention: ``k_pages``/``v_pages`` are
``[KVH, total_pages, page_size, DH]`` (the serve engine stores its pool
this way; ``_bmha_fwd``'s ``[nb, kvh, bs, dh]`` transposes into it).

CPU CI runs :func:`paged_attention_decode_reference` — the same masked
softmax as a plain jnp gather program — or the kernel itself under
``interpret=True`` (tests/test_paged_attention_kernel.py pins kernel ==
reference == the block_mha gather path). The interpreter does not check
that Mosaic accepts the kernel (it let int64 index-map literals through):
tests/test_tpu_aot_compile.py compiles it for the chip and chip_smoke.py
runs it there against the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from ...core.flags import pallas_mode
from .flash_attention import Z

_i32 = np.int32

__all__ = [
    "paged_attention_decode",
    "paged_attention_decode_reference",
    "paged_attention_decode_kernel",
    "resolve_backend",
]


def _check_shapes(q, k_pages, v_pages, lengths, block_tables,
                  starts=None, ring=False):
    if starts is not None and starts.shape != lengths.shape:
        raise ValueError(
            f"starts must be shaped as lengths {lengths.shape}, got "
            f"{starts.shape}")
    if ring and starts is None:
        raise ValueError("a ring of pages needs `starts`: without a lower "
                         "bound an older lap's rows would be read")
    if q.ndim != 3:
        raise ValueError(f"q must be [B, NH, DH], got {q.shape}")
    if k_pages.ndim != 4 or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"k_pages/v_pages must both be [KVH, pages, page_size, DH], "
            f"got {k_pages.shape} / {v_pages.shape}")
    b, nh, dh = q.shape
    kvh = k_pages.shape[0]
    if k_pages.shape[-1] != dh:
        raise ValueError(
            f"head_dim mismatch: q has {dh}, k_pages has "
            f"{k_pages.shape[-1]}")
    if nh % kvh:
        raise ValueError(
            f"num q heads ({nh}) must be a multiple of kv heads ({kvh})")
    if lengths.shape != (b,):
        raise ValueError(
            f"lengths must be [B]={b}, got {lengths.shape}")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(
            f"block_tables must be [B, pages_per_seq], got "
            f"{block_tables.shape}")


def _ring_first_pos(lengths, page, pps):
    """[B, pps] first position of the logical page each ring entry holds
    (see the module docstring); negative where the row has not reached
    that entry yet."""
    cur = jnp.maximum(lengths.astype(jnp.int32) - 1, 0) // page
    i = jnp.arange(pps, dtype=jnp.int32)
    return (cur[:, None] - (cur[:, None] - i[None, :]) % pps) * page


def paged_attention_decode_reference(q, k_pages, v_pages, lengths,
                                     block_tables, *, sm_scale=None,
                                     starts=None, ring=False):
    """jnp gather reference: the masked-softmax program the kernel must
    match (one q token per row, GQA by repeat, -inf beyond ``lengths``).

    This is the CPU-CI code path AND the equivalence oracle promoted
    from tools/paged_kernel_probe.py. fp32 softmax, output in q.dtype.
    """
    _check_shapes(q, k_pages, v_pages, lengths, block_tables, starts, ring)
    b, nh, dh = q.shape
    kvh, _, page, _ = k_pages.shape
    pps = block_tables.shape[1]
    s_pad = pps * page
    scale = dh ** -0.5 if sm_scale is None else sm_scale
    # [KVH, B, PPS, PAGE, DH] -> [B, S_pad, KVH, DH]
    k_rows = k_pages[:, block_tables].transpose(1, 2, 3, 0, 4).reshape(
        b, s_pad, kvh, dh)
    v_rows = v_pages[:, block_tables].transpose(1, 2, 3, 0, 4).reshape(
        b, s_pad, kvh, dh)
    if kvh != nh:
        k_rows = jnp.repeat(k_rows, nh // kvh, axis=2)
        v_rows = jnp.repeat(v_rows, nh // kvh, axis=2)
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        k_rows.astype(jnp.float32)) * scale
    pos = jnp.arange(s_pad)[None, :]
    if ring:
        pos = (_ring_first_pos(lengths, page, pps)[:, :, None]
               + jnp.arange(page)[None, None, :]).reshape(b, s_pad)
    valid = pos < lengths[:, None]
    if starts is not None:
        valid &= pos >= starts[:, None]
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # a zero-length row is fully masked -> NaN; serve engines carry such
    # rows for inactive slots, so return 0 instead (matches the kernel)
    probs = jnp.where(valid[:, None, :], probs, 0.0)
    return jnp.einsum("bhs,bshd->bhd", probs,
                      v_rows.astype(jnp.float32)).astype(q.dtype)


def _for(n, body, carry):
    """``lax.fori_loop(0, n, body, carry)`` counting in int32: with
    static bounds and ``jax_enable_x64`` on, fori_loop counts in int64,
    which Mosaic does not lower. ``n`` may be a traced int32."""
    n = jnp.asarray(n, jnp.int32)
    return jax.lax.while_loop(
        lambda c: c[0] < n,
        lambda c: (c[0] + _i32(1), body(c[0], c[1])),
        (_i32(0), carry))[1]


def _decode_kernel_body(len_ref, tbl_ref, *refs, kvh, group, page, pps,
                        scale, windowed=False, ring=False):
    """One row of the batch a program. ``k_hbm`` / ``v_hbm`` are the
    whole pools in HBM; ``kbuf`` / ``vbuf`` ``[2, KVH, page, DH]`` hold
    the page being computed on and the one in flight; ``turn`` (SMEM)
    carries from program to program the buffer the next page lands in
    and the row whose first page is already on its way."""
    if windowed:
        start_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref,
     kbuf, vbuf, sems, turn, m_scr, l_scr, acc_scr) = refs
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    dh = q_ref.shape[-1]

    def live(r):
        """[lo, hi): the logical pages row ``r`` holds and may attend."""
        hi = jax.lax.div(len_ref[r] + _i32(page - 1), _i32(page))
        lo = _i32(0)
        if windowed:
            lo = jax.lax.div(start_ref[r], _i32(page))
        if ring:
            # a ring keeps the newest `pps` logical pages and no older
            lo = jnp.maximum(lo, hi - _i32(pps))
        else:
            hi = jnp.minimum(hi, _i32(pps))
        return lo, jnp.maximum(hi, lo)

    def copies(r, p, buf):
        """The K and V copies of logical page ``p`` of row ``r`` into
        buffer ``buf``: table entry ``p``, or ``p % pps`` of a ring."""
        pid = tbl_ref[r, jax.lax.rem(p, _i32(pps)) if ring else p]
        return (pltpu.make_async_copy(k_hbm.at[:, pid], kbuf.at[buf],
                                      sems.at[buf, _i32(0)]),
                pltpu.make_async_copy(v_hbm.at[:, pid], vbuf.at[buf],
                                      sems.at[buf, _i32(1)]))

    def start(r, p, buf):
        for copy in copies(r, p, buf):
            copy.start()

    @pl.when(b == 0)
    def _first():
        turn[0] = _i32(0)
        turn[1] = _i32(-1)

    m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    lo, hi = live(b)

    @pl.when(hi > lo)
    def _row():
        buf0 = turn[0]

        @pl.when(turn[1] != b)
        def _():                  # the call's first live row
            start(b, lo, buf0)

        def holds_none(r):
            r_lo, r_hi = live(jnp.minimum(r, rows - 1))
            return (r < rows) & (r_hi == r_lo)

        # the next row that holds a page: its first page follows this
        # row's last into the free buffer
        nxt = jax.lax.while_loop(holds_none, lambda r: r + _i32(1),
                                 b + _i32(1))
        nxt_lo = live(jnp.minimum(nxt, rows - 1))[0]
        length = len_ref[b]
        # q heads grouped by kv head: head h = kv_head * group + g
        q = q_ref[0].astype(jnp.float32).reshape(kvh, group, dh)

        def one_page(i, c):
            pg = lo + i
            buf = jax.lax.rem(buf0 + i, _i32(2))

            @pl.when(pg + 1 < hi)
            def _():
                start(b, pg + _i32(1), _i32(1) - buf)

            @pl.when((pg + 1 == hi) & (nxt < rows))
            def _():
                start(nxt, nxt_lo, _i32(1) - buf)

            for copy in copies(b, pg, buf):
                copy.wait()
            k = kbuf[buf].astype(jnp.float32)      # [KVH, PAGE, DH]
            v = vbuf[buf].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # [KVH, G, PAGE]
            pos = pg * _i32(page) + jax.lax.broadcasted_iota(
                jnp.int32, (kvh, group, page), 2)
            in_len = pos < length
            if windowed:
                in_len &= pos >= start_ref[b]
            s = jnp.where(in_len, s, -jnp.inf)

            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.where(in_len, jnp.exp(s - m_new[..., None]), 0.0)
            # m_prev is -inf until the first valid lane; exp(-inf - -inf)
            alpha = jnp.where(jnp.isfinite(m_prev),
                              jnp.exp(m_prev - m_new), 0.0)
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
            acc_scr[...] = acc_scr[...] * alpha[..., None] + \
                jax.lax.dot_general(
                    p, v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)  # [KVH, G, DH]
            m_scr[...] = m_new
            return c

        _for(hi - lo, one_page, _i32(0))
        turn[0] = jax.lax.rem(buf0 + hi - lo, _i32(2))
        turn[1] = jnp.where(nxt < rows, nxt, _i32(-1))

    l = l_scr[...][..., None]
    out = jnp.where(l > 0.0, acc_scr[...] / jnp.where(l > 0.0, l, 1.0), 0.0)
    o_ref[0] = out.reshape(kvh * group, dh).astype(o_ref.dtype)


def paged_attention_decode_kernel(q, k_pages, v_pages, lengths,
                                  block_tables, *, sm_scale=None,
                                  interpret=False, starts=None, ring=False):
    """The Pallas kernel proper (TPU; ``interpret=True`` on CPU)."""
    _check_shapes(q, k_pages, v_pages, lengths, block_tables, starts, ring)
    b, nh, dh = q.shape
    kvh, _npages, page, _ = k_pages.shape
    pps = block_tables.shape[1]
    group = nh // kvh
    scale = dh ** -0.5 if sm_scale is None else sm_scale
    windowed = starts is not None

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if windowed else 2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nh, dh), lambda bi, *_: (bi, Z, Z)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nh, dh), lambda bi, *_: (bi, Z, Z)),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, page, dh), k_pages.dtype),
            pltpu.VMEM((2, kvh, page, dh), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((kvh, group), jnp.float32),
            pltpu.VMEM((kvh, group), jnp.float32),
            pltpu.VMEM((kvh, group, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_body, kvh=kvh, group=group, page=page, pps=pps,
        scale=scale, windowed=windowed, ring=ring)
    bounds = (starts.astype(jnp.int32),) if windowed else ()
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, dh), q.dtype),
        # rows in order on one core: a row starts the next row's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode",
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), *bounds,
      q, k_pages, v_pages)


def resolve_backend(backend: str = "auto") -> str:
    """The path ``backend`` names: ``"auto"`` is the compiled kernel
    where Pallas kernels compile (``pallas_mode() == "compiled"``) and
    the jnp reference elsewhere; the explicit names pass through."""
    if backend == "auto":
        return "kernel" if pallas_mode() == "compiled" else "reference"
    if backend not in ("kernel", "reference", "interpret"):
        raise ValueError(
            f"paged_attention_decode: unknown backend {backend!r} "
            f"(use 'auto', 'kernel', 'reference' or 'interpret')")
    return backend


def paged_attention_decode(q, k_pages, v_pages, lengths, block_tables, *,
                           sm_scale=None, backend="auto", starts=None,
                           ring=False):
    """Paged-attention for ONE decode step.

    Args:
      q: ``[B, NH, DH]`` — one query token per sequence. With GQA, q
        heads are grouped by kv head (head ``h`` reads kv head
        ``h // (NH // KVH)``, the standard repeat layout).
      k_pages / v_pages: ``[KVH, total_pages, page_size, DH]`` pool.
      lengths: ``[B]`` int32 — valid context length per sequence
        (including the just-written token). Length 0 rows (inactive
        serving slots) return zeros instead of NaN.
      block_tables: ``[B, pages_per_seq]`` int32 physical page ids.
      backend: ``"auto"`` (see :func:`resolve_backend`), ``"kernel"``,
        ``"reference"``, or ``"interpret"`` (kernel under the Pallas
        interpreter — the CPU-CI equivalence path).
      starts: ``[B]`` int32 or None — the first position a row attends
        (a sliding window's lower bound).
      ring: ``block_tables`` is a ring of pages a row (module docstring);
        needs ``starts``.

    Returns ``[B, NH, DH]`` in q.dtype.
    """
    backend = resolve_backend(backend)
    window = {} if starts is None else dict(starts=starts, ring=ring)
    if backend == "reference":
        return paged_attention_decode_reference(
            q, k_pages, v_pages, lengths, block_tables, sm_scale=sm_scale,
            **window)
    return paged_attention_decode_kernel(
        q, k_pages, v_pages, lengths, block_tables, sm_scale=sm_scale,
        interpret=(backend == "interpret"), **window)
