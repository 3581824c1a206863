"""Decode attention of a DSA layer: absorbed latent attention over the
rows a stream's indexer SELECTED, and over no other row of its context.

The arithmetic is ``mla_decode``'s (``ops/mla.py`` folds the expansion
into the query): ``NH`` absorbed queries against ONE key-value head whose
key is a cached row ``[c | k_pe]`` and whose value is its first ``dv``
lanes, bfloat16 operands on the MXU, the softmax state in float32. What
differs is which rows: a stream's ``min(length, topk)`` selected positions
(``ops/pallas/dsa_select``'s ``keep`` rows), spread over its whole context.

So is the walk: ``mla_decode``'s over every page the stream holds, each
selected position's score taken as it is and every other one's pushed
down by ``mla_decode.DROP``, so that its weight is 0. The walk reads every
row where the selection needs a fifth of them at the GLM-5 cell's
contexts, and it is still the fastest form on the chip: at the cell's
shape (32 streams of ~10k rows, 2,048 selected each) the walk takes 0.65
ms a layer (0.60 without the selection), where an XLA gather of the
selected rows into one contiguous buffer took 1.45 ms before a kernel over
that buffer took 0.16 more (``tools/dsa_kernel_probe.py`` on a v5e), and a
kernel cannot fetch
the rows itself: Mosaic refuses a DMA of one row, since the pool's HBM
layout tiles its rows by 8 and a slice along them must be aligned to the
tile.

:func:`mla_sparse_decode_reference` is the same masked softmax as a jnp
program: the CPU engine's path and the oracle the kernel is tested
against under ``interpret=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mla_decode import DROP, _check_shapes, mla_decode_kernel
from .paged_attention import resolve_backend

__all__ = ["mla_sparse_decode", "mla_sparse_decode_kernel",
           "mla_sparse_decode_reference", "keep_of"]


def keep_of(chosen):
    """``[B, 1, S]`` float32 from ``chosen`` ``[B, S]`` bool: 0 where a
    position is selected, ``DROP`` elsewhere."""
    return jnp.where(chosen, 0.0, DROP).astype(jnp.float32)[:, None, :]


def mla_sparse_decode_reference(q, pool, keep, lengths, block_tables, *,
                                dv, sm_scale):
    """jnp reference: ``mla_decode_reference`` over the positions each
    stream's ``keep`` row selects (0) alone. ``[B, NH, dv]`` in q.dtype; a
    stream of length 0 gives zeros."""
    _check_shapes(q, pool, lengths, block_tables, dv)
    b, _, lanes = q.shape
    s_pad = block_tables.shape[1] * pool.shape[2]
    if keep.shape != (b, 1, s_pad):
        raise ValueError(f"keep must be [B, 1, {s_pad}], got {keep.shape}")
    rows = pool[0][block_tables].reshape(b, s_pad, lanes)
    scores = jnp.einsum("bhl,bsl->bhs", q.astype(jnp.float32),
                        rows.astype(jnp.float32)) * sm_scale
    valid = ((keep[:, 0, :] == 0.0)
             & (jnp.arange(s_pad)[None, :] < lengths[:, None]))
    probs = jax.nn.softmax(jnp.where(valid[:, None], scores, -jnp.inf), -1)
    probs = jnp.where(valid[:, None], probs, 0.0)
    return jnp.einsum("bhs,bsv->bhv", probs,
                      rows[..., :dv].astype(jnp.float32)).astype(q.dtype)


def mla_sparse_decode_kernel(q, pool, keep, lengths, block_tables, *, dv,
                             sm_scale, interpret=False):
    """``mla_decode``'s page walk under the selection, as its own Pallas
    call (TPU; ``interpret=True`` on the CPU)."""
    return mla_decode_kernel(q, pool, lengths, block_tables, dv=dv,
                             sm_scale=sm_scale, interpret=interpret,
                             keep=keep, name="mla_sparse_decode")


def mla_sparse_decode(q, pool, keep, lengths, block_tables, *, dv,
                      sm_scale, backend="auto"):
    """Latent attention of ONE decode step over the selected rows.

    Args:
      q: ``[B, NH, lanes]``: the absorbed query of each head, a row's
        layout (``ops/mla.py:absorb_q``).
      pool: ``[1, total_pages, page_size, lanes]``, the layer's rows.
      keep: ``[B, 1, pages_per_seq * page_size]`` float32, 0 at a
        stream's selected positions (none at or past its length) and
        ``DROP`` at every other (``ops/pallas/dsa_select``'s rows).
      lengths: ``[B]`` int32; a stream of length 0 returns zeros.
      block_tables: ``[B, pages_per_seq]`` int32 physical page ids.
      dv: the leading lanes of a row that are its value.
      sm_scale: what the scores are multiplied by.
      backend: as ``paged_attention_decode``'s.

    Returns ``[B, NH, dv]`` in q.dtype, still to go through the
    expansion's value half (``ops/mla.py:absorb_o``).
    """
    backend = resolve_backend(backend)
    if backend == "reference":
        return mla_sparse_decode_reference(q, pool, keep, lengths,
                                           block_tables, dv=dv,
                                           sm_scale=sm_scale)
    return mla_sparse_decode_kernel(q, pool, keep, lengths, block_tables,
                                    dv=dv, sm_scale=sm_scale,
                                    interpret=(backend == "interpret"))
