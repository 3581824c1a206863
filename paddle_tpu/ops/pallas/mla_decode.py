"""Decode attention over a paged LATENT cache (multi-head latent
attention with the key-value expansion absorbed into the query).

A latent layer caches ONE row a token, shared by every head: the
normalised key-value latent (``dv`` lanes) and, after it, the rotated key
all heads share. With the expansion matrix folded into the query
(``ops/mla.py:absorb_q``) a head's score against a token is the plain
product of its query with the token's whole row, and its value is the
row's first ``dv`` lanes: attention of ``NH`` query heads over ONE
key-value head whose key is the row and whose value is a slice of it.
No existing kernel computes that (``paged_decode`` wants K and V pools of
one head size): this one reads each row once for both.

The walk is ``paged_decode``'s: one program a row of the batch (grid
``(B,)``), a loop with a dynamic trip count over the pages the row
holds, the pool left in HBM and its pages copied by hand into one of two
VMEM buffers, the first copies of the next row started in this row's last
turn, a row of length 0 (an idle slot) one empty program that writes
zeros. What differs follows from the arithmetic. A page of 128 rows is
``128 x lanes x 2`` bytes for ``NH x 128 x (lanes + dv) x 2`` FLOPs: at
128 heads that is 242 FLOPs a byte, the v5e's ridge, so the products go
to the MXU in the pool's own type (bfloat16 operands, float32
accumulation; the softmax state is float32) and a turn takes ``TURN``
pages at once, so that the loop's fixed cost a turn (the scalar work,
starting and awaiting the copies) is paid once for ``TURN x 128`` rows.

What a turn computes follows what it holds (PR 36). A turn that ends at
or under the row's length (every turn of a stream but its last) carries
no mask: no iota, compare or select on its scores. The last turn is
compiled once for each number of pages it can hold, 1 to ``TURN``, and
runs the body of its size, masked: its products run over the pages it
holds, not over ``TURN`` of them. Within a turn the softmax goes by
blocks of ``BLOCK`` pages, and a block's score product stands in the
program BEFORE the softmax of the block before it: the MXU returns its
results in program order, so what is written between two products waits
for the first and holds up the second, and written this way the vector
unit's work on one block runs under the MXU's on the next. The scale
(positive) is on no operand and costs no operation: the scores and their
running maximum stay unscaled in float32 and ``exp(scale * x)`` is
``2 ** (x * (scale * log2 e))``, the multiply an exponential makes
anyway, so ``q`` is rounded to bfloat16 once as before. The copies a turn
starts are chosen by selects, not by branches, and ``q`` is read inside
each body: a value read once above the bodies is loaded and spilled anew
for every one of them in every row (nine bodies: 720 loads and stores a
row).

The pool is ``[1, pages, page_size, lanes]`` with ``lanes`` a whole
number of 128-lane tiles (the engine pads a row with zeros: a row of 576
numbers lies in 640 lanes on the device whatever its logical width, and
Mosaic moves whole tiles), and ``q`` is ``[B, NH, lanes]``, zero where the
row is padding.

:func:`mla_decode_reference` is the same masked softmax as a jnp gather
program: the CPU engine's path and the oracle the kernel is tested
against under ``interpret=True``. Neither ever holds a per-head key or
value of a cached token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import Z
from .paged_attention import _for, resolve_backend

__all__ = ["mla_decode", "mla_decode_kernel", "mla_decode_reference",
           "pages_computed"]

_i32 = np.int32

#: pages a turn of the loop takes: the unit of the copies. A call's time
#: fits ``0.18 us a page + 0.57 us a turn + 0.68 us a stream`` at the
#: DeepSeek-V2 cell's shapes (``tools/mla_kernel_probe.py`` over 2, 4 and
#: 8 pages a turn: PERF.md §6, PR 36; ``0.33 us a page + 0.47 us a turn``
#: before it, PR 33), against 0.18 us a page at the MXU's peak and 0.20 at
#: the HBM's: what is left is a turn's and a stream's fixed cost, which a
#: longer turn spreads wider and a longer last turn's code pays for.
TURN = 8

#: pages of a block, the unit of the softmax within a turn: two blocks a
#: turn, the second's score product under way while the vector unit is on
#: the first. Blocks of two pages schedule 9% worse (the accumulator's
#: rescale, once a block, is bound by the one store a cycle), a turn in
#: one block 11% (nothing overlaps): my chip runs, PR 36.
BLOCK = 4


def _check_shapes(q, pool, lengths, block_tables, dv):
    if q.ndim != 3:
        raise ValueError(f"q must be [B, NH, lanes], got {q.shape}")
    if pool.ndim != 4 or pool.shape[0] != 1:
        raise ValueError(
            f"the latent pool must be [1, pages, page_size, lanes], got "
            f"{pool.shape}")
    b, _, lanes = q.shape
    if pool.shape[-1] != lanes:
        raise ValueError(
            f"q has {lanes} lanes, the pool's rows {pool.shape[-1]}")
    if not 0 < dv <= lanes:
        raise ValueError(f"dv ({dv}) must lie in (0, {lanes}]")
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be [B]={b}, got {lengths.shape}")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(
            f"block_tables must be [B, pages_per_seq], got "
            f"{block_tables.shape}")


def mla_decode_reference(q, pool, lengths, block_tables, *, dv,
                         sm_scale):
    """jnp gather reference: rows through the table, one masked float32
    softmax a head, values the rows' first ``dv`` lanes. Output
    ``[B, NH, dv]`` in q.dtype; a row of length 0 gives zeros."""
    _check_shapes(q, pool, lengths, block_tables, dv)
    b, _, lanes = q.shape
    page = pool.shape[2]
    s_pad = block_tables.shape[1] * page
    rows = pool[0][block_tables].reshape(b, s_pad, lanes)
    scores = jnp.einsum("bhl,bsl->bhs", q.astype(jnp.float32),
                        rows.astype(jnp.float32)) * sm_scale
    valid = (jnp.arange(s_pad)[None, :] < lengths[:, None])[:, None, :]
    probs = jax.nn.softmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
    probs = jnp.where(valid, probs, 0.0)
    return jnp.einsum("bhs,bsv->bhv", probs,
                      rows[..., :dv].astype(jnp.float32)).astype(q.dtype)


def _scores(q, k):
    """``[NH, rows]`` float32: the absorbed queries against ``k``'s rows."""
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _values(p, k, dv):
    """``[NH, dv]`` float32: the weights ``p`` over the value lanes of
    ``k``'s rows."""
    return jax.lax.dot_general(p.astype(k.dtype), k[:, :dv],
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def pages_computed(lengths, page_size, pages_per_seq):
    """Pages the kernel's two products run over for streams of these
    lengths (numpy, ``[B]``): the pages a stream holds and no other,
    since its last turn runs the body of its own size. What
    ``serve.mla_pages_computed`` counts."""
    held = -(-np.asarray(lengths, np.int64) // page_size)
    return np.minimum(held, pages_per_seq)


def _kernel_body(len_ref, tbl_ref, q_ref, pool_hbm, o_ref, buf, sems, turn,
                 m_scr, l_scr, acc_scr, *, page, pps, per_turn, dv, scale):
    """One row of the batch a program. ``buf`` ``[2, per_turn * page,
    lanes]`` holds the pages being computed on and those in flight;
    ``turn`` (SMEM) carries from program to program the buffer the next
    pages land in and the row whose first pages are already on their
    way."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    span = per_turn * page
    rate = np.float32(scale * np.log2(np.e))

    def pages_of(r):
        return jnp.minimum(
            jax.lax.div(len_ref[r] + _i32(page - 1), _i32(page)), _i32(pps))

    def turns_of(r):
        return jax.lax.div(pages_of(r) + _i32(per_turn - 1), _i32(per_turn))

    def each_copy(r, t, slot, fn, held=None):
        """``fn`` of the copy of each page that turn ``t`` of row ``r``
        holds (of the row's first ``held`` pages, where given), into its
        place in buffer ``slot``. A page's condition becomes a predicate
        on its copy, no branch: the eight lie in the caller's block."""
        held = pages_of(r) if held is None else held
        for j in range(per_turn):
            pg = t * _i32(per_turn) + _i32(j)
            pid = tbl_ref[r, jnp.minimum(pg, _i32(pps - 1))]
            copy = pltpu.make_async_copy(
                pool_hbm.at[Z, pid], buf.at[slot, pl.ds(j * page, page)],
                sems.at[slot, _i32(j)])
            pl.when(pg < held)(functools.partial(fn, copy))

    @pl.when(b == 0)
    def _first():
        turn[0] = _i32(0)
        turn[1] = _i32(-1)

    m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    n_turns = turns_of(b)

    @pl.when(n_turns > 0)
    def _row():
        buf0 = turn[0]

        @pl.when(turn[1] != b)
        def _():                  # the call's first live row
            each_copy(b, _i32(0), buf0, lambda copy: copy.start())

        def holds_none(r):
            return (r < rows) & (turns_of(jnp.minimum(r, rows - 1)) == 0)

        # the next row that holds a page: its first pages follow this
        # row's last into the free buffer
        nxt = jax.lax.while_loop(holds_none, lambda r: r + _i32(1),
                                 b + _i32(1))
        length = jnp.minimum(len_ref[b], _i32(pps * page))
        n_full = jax.lax.div(length, _i32(span))   # turns under length

        def update(s, k, base, masked):
            """The softmax state taken over the rows ``k`` with scores
            ``s``, the first of them at position ``base``. Every block
            handed in holds a row under ``length``."""
            m_prev = m_scr[:, :1]
            if masked:
                seen = base + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1) < length
                s = jnp.where(seen, s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # exp(scale * x) as 2 ** (x * (scale * log2 e)): the scale
            # rides the multiply an exponential makes anyway
            p = jnp.exp2((s - m_new) * rate)
            if masked:
                p = jnp.where(seen, p, 0.0)
            alpha = jnp.exp2((m_prev - m_new) * rate)   # 0 from -inf
            l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + _values(p, k, dv)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        def compute(slot, n, base, masked):
            """The first ``n`` (static) pages of buffer ``slot``, in
            blocks of ``BLOCK`` pages. The MXU's results come back in
            program order, so a block's score product stands BEFORE the
            softmax of the block before it: the vector unit works on one
            block while the MXU is on the next."""
            q = q_ref[0]          # [NH, lanes]; read in each body
            blocks = [buf[slot, pl.ds(j * page, min(BLOCK, n - j) * page)]
                      for j in range(0, n, BLOCK)]
            s = _scores(q, blocks[0])
            for i, k in enumerate(blocks):
                s_next = (_scores(q, blocks[i + 1])
                          if i + 1 < len(blocks) else None)
                update(s, k, base + _i32(i * BLOCK * page), masked)
                s = s_next

        def one_turn(t):
            """Turn ``t``: the next pages started, this turn's awaited."""
            slot = jax.lax.rem(buf0 + t, _i32(2))
            # what follows this turn: the row's next turn, or the first
            # of the next row that holds a page, or nothing. Chosen by
            # selects, not by branches: one block a turn
            more = t + 1 < n_turns
            r = jnp.where(more, b, jnp.minimum(nxt, rows - 1))
            each_copy(r, jnp.where(more, t + 1, _i32(0)), _i32(1) - slot,
                      lambda copy: copy.start(),
                      jnp.where(more | (nxt < rows), pages_of(r), _i32(0)))
            each_copy(b, t, slot, lambda copy: copy.wait())
            return slot

        def full_turn(t, c):      # no row past ``length``: no mask
            compute(one_turn(t), per_turn, t * _i32(span), False)
            return c

        _for(n_full, full_turn, _i32(0))

        @pl.when(n_full < n_turns)
        def _last():              # computed at the size of what it holds
            slot = one_turn(n_full)
            held = pages_of(b) - n_full * _i32(per_turn)
            for n in range(1, per_turn + 1):
                pl.when(held == n)(functools.partial(
                    compute, slot, n, n_full * _i32(span), True))

        turn[0] = jax.lax.rem(buf0 + n_turns, _i32(2))
        turn[1] = jnp.where(nxt < rows, nxt, _i32(-1))

    l = l_scr[:, :1]
    o_ref[0] = jnp.where(l > 0.0, acc_scr[...] / jnp.where(l > 0.0, l, 1.0),
                         0.0).astype(o_ref.dtype)


def mla_decode_kernel(q, pool, lengths, block_tables, *, dv, sm_scale,
                      interpret=False):
    """The Pallas kernel proper (TPU; ``interpret=True`` on the CPU)."""
    _check_shapes(q, pool, lengths, block_tables, dv)
    b, nh, lanes = q.shape
    page = pool.shape[2]
    pps = block_tables.shape[1]
    per_turn = min(TURN, pps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nh, lanes), lambda bi, *_: (bi, Z, Z)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nh, dv), lambda bi, *_: (bi, Z, Z)),
        scratch_shapes=[
            pltpu.VMEM((2, per_turn * page, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2, per_turn)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel_body, page=page, pps=pps, per_turn=per_turn, dv=dv,
        scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, dv), q.dtype),
        # rows in order on one core: a row starts the next row's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_decode",
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      q.astype(pool.dtype), pool)


def mla_decode(q, pool, lengths, block_tables, *, dv, sm_scale,
               backend="auto"):
    """Latent attention for ONE decode step.

    Args:
      q: ``[B, NH, lanes]``: the absorbed query of each head, a row's
        layout (latent lanes, the rotated key's, zeros in the padding).
      pool: ``[1, total_pages, page_size, lanes]``, the layer's rows.
      lengths: ``[B]`` int32, a sequence's valid rows (the one just
        written included); a row of length 0 returns zeros.
      block_tables: ``[B, pages_per_seq]`` int32 physical page ids.
      dv: the leading lanes of a row that are its value.
      sm_scale: what the scores are multiplied by.
      backend: as ``paged_attention_decode``'s.

    Returns ``[B, NH, dv]`` in q.dtype: a head's softmax-weighted sum of
    the rows' value lanes, still to go through the expansion's value half
    (``ops/mla.py:absorb_o``).
    """
    backend = resolve_backend(backend)
    if backend == "reference":
        return mla_decode_reference(q, pool, lengths, block_tables, dv=dv,
                                    sm_scale=sm_scale)
    return mla_decode_kernel(q, pool, lengths, block_tables, dv=dv,
                             sm_scale=sm_scale,
                             interpret=(backend == "interpret"))
