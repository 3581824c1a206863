"""The lightning indexer of a decode step over a paged INDEX-KEY cache
(DeepSeek sparse attention, ``ops/dsa.py`` has the equations).

A DSA layer caches, beside its latent row, ONE index key a token
(``dim`` numbers, 128 at the published sizes: one lane tile). A decode
row's score of every position it holds is

    I[s] = scale x sum_j w[j] x ReLU(q[j] . k[s])

over its ``heads`` index queries, in float32: the queries against a page of
keys are one MXU product (bfloat16 operands, float32 accumulation), the
ReLU, the weighted sum over heads and the scale are the vector unit's.

The walk is ``mla_decode``'s (one program a row of the batch, a loop with
a dynamic trip count over the pages the row holds, the pool left in HBM
and copied by hand into one of two VMEM buffers, ``TURN`` pages a turn, the
next row's first copies started in this row's last turn). A row's scores
leave as ``[pages_per_seq, page]`` (a page's keys along the lanes): every
position past the row's length reads ``-inf``, so that the selection
(``ops/dsa.py:top_mask``, outside the kernel) reads the kernel's
output as it lies. A row of length 0 (an idle slot) is all ``-inf``.

:func:`dsa_index_reference` is the same as a jnp gather program: the CPU
engine's path and the oracle the kernel is tested against under
``interpret=True``.
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

__all__ = ["dsa_index", "dsa_index_kernel", "dsa_index_reference"]

_i32 = np.int32

#: pages a turn of the loop takes: 16 pages of 128 keys of 128 numbers is
#: 512 KB a copy buffer, so that a turn's fixed cost is paid for as many
#: bytes as ``mla_decode``'s turn of eight 640-lane pages
TURN = 16


def _check_shapes(q, w, pool, lengths, block_tables):
    if q.ndim != 3:
        raise ValueError(f"q must be [B, heads, dim], got {q.shape}")
    b, heads, dim = q.shape
    if w.shape != (b, heads):
        raise ValueError(f"w must be [B, heads]={(b, heads)}, got {w.shape}")
    if pool.ndim != 4 or pool.shape[0] != 1 or pool.shape[-1] != dim:
        raise ValueError(
            f"the index-key pool must be [1, pages, page_size, {dim}], got "
            f"{pool.shape}")
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be [B]={b}, got {lengths.shape}")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(
            f"block_tables must be [B, pages_per_seq], got "
            f"{block_tables.shape}")


def dsa_index_reference(q, w, pool, lengths, block_tables, *, scale):
    """jnp gather reference: ``[B, pages_per_seq * page]`` float32 scores,
    ``-inf`` past each row's length."""
    _check_shapes(q, w, pool, lengths, block_tables)
    b, _, dim = q.shape
    s_pad = block_tables.shape[1] * pool.shape[2]
    keys = pool[0][block_tables].reshape(b, s_pad, dim)
    s = jnp.einsum("bhd,bsd->bhs", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    out = jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[:, :, None],
                  axis=1) * scale
    seen = jnp.arange(s_pad)[None, :] < lengths[:, None]
    return jnp.where(seen, out, -jnp.inf)


def _kernel_body(len_ref, tbl_ref, q_ref, w_ref, pool_hbm, o_ref, buf, sems,
                 turn, *, page, pps, per_turn, scale):
    """One row of the batch a program. ``buf`` ``[2, per_turn * page,
    dim]`` holds the pages being scored and those in flight; ``turn``
    (SMEM) carries from program to program the buffer the next pages land
    in and the row whose first pages are already on their way."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)

    def pages_of(r):
        return jnp.minimum(
            jax.lax.div(len_ref[r] + _i32(page - 1), _i32(page)), _i32(pps))

    def turns_of(r):
        return jax.lax.div(pages_of(r) + _i32(per_turn - 1), _i32(per_turn))

    def each_copy(r, t, slot, fn, held=None):
        """``fn`` of the copy of each page that turn ``t`` of row ``r``
        holds (of the row's first ``held`` pages, where given)."""
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

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)
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
        length = len_ref[b]
        held = pages_of(b)

        def one_turn(t, c):
            slot = jax.lax.rem(buf0 + t, _i32(2))
            more = t + 1 < n_turns
            r = jnp.where(more, b, jnp.minimum(nxt, rows - 1))
            each_copy(r, jnp.where(more, t + 1, _i32(0)), _i32(1) - slot,
                      lambda copy: copy.start(),
                      jnp.where(more | (nxt < rows), pages_of(r), _i32(0)))
            each_copy(b, t, slot, lambda copy: copy.wait())
            s = jax.lax.dot_general(q_ref[0], buf[slot],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            w = w_ref[0]          # [heads, page], lane-replicated
            for j in range(per_turn):
                pg = t * _i32(per_turn) + _i32(j)
                sj = jax.nn.relu(s[:, j * page:(j + 1) * page]) * w
                row = jnp.sum(sj, axis=0, keepdims=True) * np.float32(scale)
                pos = pg * _i32(page) + jax.lax.broadcasted_iota(
                    jnp.int32, row.shape, 1)
                row = jnp.where(pos < length, row, -jnp.inf)

                @pl.when(pg < held)
                def _store(row=row, pg=pg):
                    o_ref[0, pl.ds(pg, 1), :] = row
            return c

        _for(n_turns, one_turn, _i32(0))
        turn[0] = jax.lax.rem(buf0 + n_turns, _i32(2))
        turn[1] = jnp.where(nxt < rows, nxt, _i32(-1))


def dsa_index_kernel(q, w, pool, lengths, block_tables, *, scale,
                     interpret=False):
    """The Pallas kernel proper (TPU; ``interpret=True`` on the CPU):
    ``[B, pages_per_seq * page]`` float32."""
    _check_shapes(q, w, pool, lengths, block_tables)
    b, heads, dim = q.shape
    page = pool.shape[2]
    pps = block_tables.shape[1]
    per_turn = min(TURN, pps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, dim), lambda bi, *_: (bi, Z, Z)),
            pl.BlockSpec((1, heads, page), lambda bi, *_: (bi, Z, Z)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, pps, page), lambda bi, *_: (bi, Z, Z)),
        scratch_shapes=[
            pltpu.VMEM((2, per_turn * page, dim), pool.dtype),
            pltpu.SemaphoreType.DMA((2, per_turn)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    kernel = functools.partial(_kernel_body, page=page, pps=pps,
                               per_turn=per_turn, scale=scale)
    w_lanes = jnp.broadcast_to(w.astype(jnp.float32)[:, :, None],
                               (b, heads, page))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, pps, page), jnp.float32),
        # rows in order on one core: a row starts the next row's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="dsa_index",
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      q.astype(pool.dtype), w_lanes, pool)
    return out.reshape(b, pps * page)


def dsa_index(q, w, pool, lengths, block_tables, *, scale, backend="auto"):
    """Index scores of ONE decode step.

    Args:
      q: ``[B, heads, dim]`` index queries (rotated as the keys are).
      w: ``[B, heads]`` the heads' weights (``h W_w x heads^-0.5``).
      pool: ``[1, total_pages, page_size, dim]``, the layer's index keys.
      lengths: ``[B]`` int32, a row's valid positions (the one just
        written included).
      block_tables: ``[B, pages_per_seq]`` int32 physical page ids.
      scale: ``dim^-0.5``.
      backend: as ``paged_attention_decode``'s.

    Returns ``[B, pages_per_seq * page_size]`` float32, ``-inf`` past each
    row's length.
    """
    backend = resolve_backend(backend)
    if backend == "reference":
        return dsa_index_reference(q, w, pool, lengths, block_tables,
                                   scale=scale)
    return dsa_index_kernel(q, w, pool, lengths, block_tables, scale=scale,
                            interpret=(backend == "interpret"))
