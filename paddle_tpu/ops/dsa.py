"""DeepSeek sparse attention (DSA; DeepSeek-V3.2-Exp's lightning indexer
and top-k selection, as GLM-5's ``glm_moe_dsa`` has it): what the callers
of a ``mla`` mixer with an indexer share of the selection.

A query ``t`` scores every position ``s <= t`` with

    I[t, s] = dim^-0.5 x sum_j w[t, j] x ReLU(q_I[t, j] . k_I[s])

in float32 (``heads`` index queries ``q_I`` of ``dim`` a token, ONE index
key ``k_I`` a token, per-head weights ``w``), and attention's softmax runs
over the ``topk`` positions with the largest ``I[t, s]`` only, ties to the
earlier position (``min(topk, t + 1)`` of them: a query with no more than
``topk`` positions before it attends every one). Every other score is
``-inf``.

- :func:`index_scores` is the score itself (the whole-sequence forward's,
  and the oracle of the decode kernel ``ops/pallas/dsa_index``);
- :func:`top_mask` the selection as a mask over scored rows;
- :func:`prefill_select` a prompt's selection as an int8 ``[T, T]`` mask
  for the flash forward, computed a block of queries at a time (never a
  ``[T, T]`` float32 array whole).

The selection goes by the k-th largest score (:func:`kth_largest`, without
a sort) and takes the ties at it from the earliest position on, so it does
not depend on the order in which a backend's ``top_k`` returns equal
values (the CPU's does not keep the earlier of equal values first at a k
of 2,048).

On the chip the serving engine selects in VMEM instead
(``ops/pallas/dsa_select.py``: ``dsa_select`` over a decode step's scores,
``dsa_prefill_select`` scoring and selecting a prompt's query blocks),
with the same halvings and the same ties; the functions here are the CPU
engine's path, ``models/glm_moe_dsa.py``'s whole-sequence forward and
those kernels' oracles.
"""
from __future__ import annotations

__all__ = ["index_scores", "kth_largest", "top_mask", "prefill_select",
           "selected_pairs", "QUERY_BLOCK"]

#: queries whose scores a prompt's selection holds at once: ``[256, T]``
#: float32, 21 MB at a prompt of 20,480
QUERY_BLOCK = 256


def index_scores(q, w, k, scale):
    """``[Tq, Tk]`` float32 ``I`` of queries ``q`` ``[Tq, heads, dim]``
    with weights ``w`` ``[Tq, heads]`` against keys ``k`` ``[Tk, dim]``:
    the products in the operands' type with float32 accumulation, the
    ReLU, the weighted sum over heads and the scale in float32."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("qhd,kd->qhk", q, k, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[:, :, None],
                   axis=1) * scale


def kth_largest(s, k):
    """``[rows, 1]`` float32: the ``k``-th largest of each row of ``s``
    (float32, no NaN; ``-inf`` counts as a value), by 32 halvings of an
    order-keeping uint32 key: the largest key with ``k`` keys at or above
    it is found a bit at a time from the top, each bit one compare and one
    count over the row. No sort: a ``lax.top_k`` of 2,048 of 20,480 sorts
    the row."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(s + 0.0, jnp.int32)   # -0 -> +0
    # the order of the floats is that of the signed ints with a negative
    # float's magnitude bits flipped, and of those as uint32 with the sign
    # bit flipped
    key = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits),
        u32) ^ u32(0x80000000)

    def bit(i, ans):
        cand = ans | (u32(1) << (u32(31) - i.astype(u32)))
        ok = jnp.sum(key >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(ok, cand, ans)

    ans = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(s.shape[:-1] + (1,), u32))
    back = jax.lax.bitcast_convert_type(ans ^ u32(0x80000000), jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.where(back < 0, back ^ jnp.int32(0x7FFFFFFF), back), jnp.float32)


def top_mask(scores, seen, topk):
    """``[rows, T]`` bool: of the positions ``seen`` (bool, same shape) of
    each row, the ``topk`` whose ``scores`` are largest, ties to the earlier
    position; every seen position where a row sees ``topk`` or fewer."""
    import jax.numpy as jnp

    s = jnp.where(seen, scores, -jnp.inf) + 0.0
    k = min(topk, s.shape[-1])
    theta = kth_largest(s, k)
    above = seen & (s > theta)
    tie = seen & (s == theta)
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= need))


def _block_scores(qb, wb, k, scale):
    """``[block, T]`` float32 scores a head at a time: the ``[block,
    heads, T]`` products are never held whole."""
    import jax
    import jax.numpy as jnp

    def head(j, acc):
        qj = jax.lax.dynamic_index_in_dim(qb, j, axis=1, keepdims=False)
        wj = jax.lax.dynamic_index_in_dim(wb, j, axis=1)
        s = jax.lax.dot_general(qj, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return acc + jax.nn.relu(s) * wj.astype(jnp.float32)

    acc = jnp.zeros((qb.shape[0], k.shape[0]), jnp.float32)
    return jax.lax.fori_loop(0, qb.shape[1], head, acc) * scale


def prefill_select(q, w, k, topk, scale, block=QUERY_BLOCK):
    """int8 ``[T, T]``: 1 where causal query ``t`` of a prompt selects key
    ``s`` (:func:`top_mask` of its causal positions), from ``q`` ``[T,
    heads, dim]``, ``w`` ``[T, heads]`` and ``k`` ``[T, dim]``, a block of
    ``block`` queries at a time."""
    import jax
    import jax.numpy as jnp

    t, heads, dim = q.shape
    block = min(block, t)
    cols = jnp.arange(t, dtype=jnp.int32)[None, :]

    def one(args):
        qb, wb, first = args
        seen = cols <= first + jnp.arange(block, dtype=jnp.int32)[:, None]
        return top_mask(_block_scores(qb, wb, k, scale), seen,
                        topk).astype(jnp.int8)

    out = jax.lax.map(one, (q.reshape(t // block, block, heads, dim),
                            w.reshape(t // block, block, heads),
                            jnp.arange(0, t, block, dtype=jnp.int32)))
    return out.reshape(t, t)


def selected_pairs(n, topk, start=0):
    """(pairs the ``n`` queries at positions ``start`` on attend, their
    causal pairs): the query at ``t`` attends ``min(t + 1, topk)`` keys of
    ``t + 1``."""
    def upto(m):              # over the queries at positions below m
        full = min(m, topk)
        return full * (full + 1) // 2 + (m - full) * topk

    return (upto(start + n) - upto(start),
            (start + n) * (start + n + 1) // 2 - start * (start + 1) // 2)
