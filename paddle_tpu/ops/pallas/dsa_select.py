"""DeepSeek sparse attention's exact top-``k`` selection on rows of scores
held in VMEM (``ops/dsa.py`` has the rule: of the positions a row sees,
the ``k`` whose float32 scores are largest, ties to the earlier position).

ONE body, :func:`_select_rows`, runs over a buffer of int32 keys in VMEM
(``rows`` rows, a key a position, the signed order of the keys that of
the scores; a position the row does not see holds ``UNSEEN``, below every
score's key) up to a dynamic number of chunks of the row, and hands each
chunk's mask to its caller:

- the k-th largest key by ``ops/dsa.py:kth_largest``'s 32 halvings of
  the order-keeping key, from the top bit: a compare and a count a pass,
  over the live chunks only;
- every key above it;
- the ties at it from the earliest position on: where a row holds more
  ties than it needs, the last tie it takes is found by a second halving,
  over positions (the largest ``p`` with fewer than ``need`` ties before
  it; at most 15 passes at 20,480 positions, run only where a row needs
  it). No running sum.

Two kernels feed it:

- :func:`dsa_select` (``pallas_call`` name ``dsa_select``), a decode
  step's: ``dsa_index``'s ``[B, S]`` scores read once, up to 32 streams
  a program walked to the longest one's length (scalar-prefetched), and
  the selection written as the ``[B, 1, S]`` float32 ``keep`` rows
  ``mla_sparse_decode`` adds to its scores (0 where selected,
  ``mla_decode.DROP`` elsewhere);
- :func:`dsa_prefill_select` (``dsa_prefill_select``), a prompt's: a
  program a block of queries (the blocks in order, so that a block's keys
  join those before it in VMEM and none is fetched twice), the block's
  index scores made in VMEM over the key tiles up to its last query only
  (all heads' queries against a tile in one MXU product, bfloat16
  operands where the indexer's are, float32 accumulation; the ReLU, the
  head weights, the head sum in head order and the scale in float32, as
  ``ops/dsa.py:prefill_select`` has them), the shared body over the
  block's rows, and the block's int8 mask rows written once, 0 past the
  causal end.

The float32 order of the scores, ``k``, and the ties to the earlier
position are ``ops/dsa.py:top_mask``'s: :func:`dsa_select` equals
``top_mask`` bit for bit on the same scores; :func:`dsa_prefill_select`
differs from ``prefill_select`` only where the MXU's order of a query's
dot product moves a score across the k-th. Both run under
``interpret=True`` on the CPU; the engine's ``"reference"`` backend keeps
``ops/dsa.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import dsa as _dsa
from .flash_attention import Z
from .mla_decode import DROP
from .mla_sparse_decode import keep_of
from .paged_attention import _for, resolve_backend

__all__ = ["dsa_select", "dsa_select_kernel", "dsa_select_reference",
           "dsa_prefill_select", "dsa_prefill_select_kernel",
           "prefill_block", "prefill_vmem_bytes"]

_i32 = np.int32

#: the key of a position a row does not see: below every float's key
#: (it is the key of a NaN with every bit set, which no score is)
UNSEEN = _i32(-2 ** 31)

#: the key of -inf: the least the k-th largest of a row can be (a row
#: that sees fewer than k positions takes every one it sees)
NEG_INF_KEY = _i32(np.int32(np.float32(-np.inf).view(np.int32))
                   ^ np.int32(0x7FFFFFFF))

#: streams a program of :func:`dsa_select_kernel` takes, and the lanes of
#: a chunk of their rows (sixteen vector registers a chunk). One program
#: of 32 streams walks every stream to the longest one's end and still
#: beat four of eight walking each group to its own: 45.8 against 55.9 us
#: a call at the GLM-5 cell's lengths (``tools/dsa_kernel_probe.py`` on a
#: v5e; 16 streams 53.2, 8 streams x 2,048 lanes 55.6)
DECODE_ROWS = 32
DECODE_CHUNK = 512

#: rows of a prompt block the shared body takes at once (whole int8
#: tiles), and the lanes of its chunk (sixteen registers a chunk): 15.0
#: ms a layer at the 16,384 bucket against 17.2 for 32 rows and 15.7 and
#: 17.5 for 128 and 256 rows of 128 lanes (``tools/dsa_kernel_probe.py``
#: on a v5e)
PREFILL_ROWS = 64
PREFILL_CHUNK = 256

#: keys a tile of a prompt's scoring: one MXU product of every head's
#: queries of the block against them
KEY_TILE = 128

#: rows of the block whose head sum is held in registers at once
SUM_ROWS = 64

#: bytes a prompt block's int32 keys may take: blocks of 256 queries up
#: to 16,384 keys (16 MiB), of 128 past it
_BLOCK_BYTES = 16 << 20


def _keys(s):
    """int32 keys whose signed order is the float order of ``s`` (float32,
    no NaN), ``-0.0`` taken as ``+0.0`` (``top_mask`` adds 0.0)."""
    b = jax.lax.bitcast_convert_type(s, jnp.int32)
    b = jnp.where(b == UNSEEN, _i32(0), b)
    return jnp.where(b < 0, b ^ _i32(0x7FFFFFFF), b)


def _bit(i, top):
    """The int32 with only bit ``top - i`` set (``i`` traced)."""
    return jnp.left_shift(_i32(1), _i32(top) - i)


def _select_rows(key_ref, r0, rows, n_chunks, width, k, bits, emit):
    """The shared body: the top-``k`` of each of ``rows`` rows of keys at
    ``key_ref[r0:r0 + rows]``, over their first ``n_chunks`` chunks of
    ``width`` lanes (a position past them is one the row does not see).
    ``emit(c, chosen)`` is called for each live chunk ``c`` with its
    ``[rows, width]`` bool mask. ``bits``: positions are below
    ``2 ** bits``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

    def chunk_of(c):
        return key_ref[pl.ds(r0, rows),
                       pl.ds(pl.multiple_of(c * width, width), width)]

    def count(*preds):
        """[rows, 1] float32 a predicate: the live keys it holds for (a
        whole number below 2 ** 24, so exact; one lane sum a pass)."""
        one_, zero_ = np.float32(1.0), np.float32(0.0)

        def one(c, accs):
            x = chunk_of(c)
            return tuple(a + jnp.where(p(x, c), one_, zero_)
                         for a, p in zip(accs, preds))

        zero = jnp.zeros((rows, width), jnp.float32)
        accs = _for(n_chunks, one, (zero,) * len(preds))
        return tuple(jnp.sum(a, axis=1, keepdims=True) for a in accs)

    # the k-th largest key, a bit at a time from the top of the unsigned
    # key (the signed key with its sign bit flipped)
    def halve(i, ans):
        cand = ans | _bit(i, 31)
        at = jnp.broadcast_to(cand ^ UNSEEN, (rows, width))
        ok = count(lambda x, c: x >= at)[0] >= np.float32(k)
        return jnp.where(ok, cand, ans)

    ans = _for(32, halve, jnp.zeros((rows, 1), jnp.int32))
    theta = jnp.maximum(ans ^ UNSEEN, NEG_INF_KEY)
    at = jnp.broadcast_to(theta, (rows, width))
    above, ties = count(lambda x, c: x > at, lambda x, c: x == at)
    need = np.float32(k) - above
    many = ties > need

    # the last tie taken: the largest position with fewer than ``need``
    # ties before it
    def cut_bit(i, cut):
        cand = cut | _bit(i, bits - 1)
        before = jnp.broadcast_to(cand, (rows, width))
        n = count(lambda x, c: (x == at)
                  & (c * width + lane < before))[0]
        return jnp.where(n < need, cand, cut)

    passes = jnp.where(jnp.max(many.astype(jnp.int32)) > 0, _i32(bits),
                       _i32(0))
    cut = _for(passes, cut_bit, jnp.zeros((rows, 1), jnp.int32))
    last = jnp.broadcast_to(jnp.where(many, cut, _i32(2 ** bits)),
                            (rows, width))

    def out(c, carry):
        x = chunk_of(c)
        emit(c, (x > at) | ((x == at) & (c * width + lane <= last)))
        return carry

    _for(n_chunks, out, _i32(0))


# --- a decode step ---------------------------------------------------------------
def _decode_body(len_ref, s_ref, o_ref, key_scr, *, k, width, bits):
    """A program's streams: their scores to keys up to their lengths, the
    body, the ``keep`` rows."""
    b = pl.program_id(0)
    rows, total = s_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    lens = jnp.zeros((rows, 1), jnp.int32)
    longest = _i32(0)
    for r in range(rows):
        n = len_ref[b * rows + r]
        lens = jnp.where(row == r, n, lens)
        longest = jnp.maximum(longest, n)
    n_chunks = jnp.minimum(
        jax.lax.div(longest + _i32(width - 1), _i32(width)),
        _i32(total // width))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

    def prep(c, carry):
        cols = pl.ds(pl.multiple_of(c * width, width), width)
        key_scr[:, cols] = jnp.where(c * width + lane < lens,
                                     _keys(s_ref[:, cols]), UNSEEN)
        return carry

    _for(n_chunks, prep, _i32(0))
    o_ref[...] = jnp.full(o_ref.shape, DROP, jnp.float32)

    def emit(c, chosen):
        cols = pl.ds(pl.multiple_of(c * width, width), width)
        o_ref[:, 0, cols] = jnp.where(chosen, np.float32(0.0),
                                      np.float32(DROP))

    _select_rows(key_scr, 0, rows, n_chunks, width, k, bits, emit)


def _round_up(n, m):
    return -(-n // m) * m


def dsa_select_reference(scores, lengths, *, topk):
    """``ops/dsa.py:top_mask`` of the positions below each length, as
    ``keep`` rows: the CPU engine's path and the kernel's oracle."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
    return keep_of(_dsa.top_mask(scores, pos < lengths[:, None], topk))


def dsa_select_kernel(scores, lengths, *, topk, interpret=False):
    """A decode step's selection, in VMEM (TPU; ``interpret=True`` on the
    CPU).

    Args:
      scores: ``[B, S]`` float32 (``dsa_index``'s; no NaN).
      lengths: ``[B]`` int32: a row sees its positions below its length.
      topk: positions a row keeps (every one it sees where it sees no
        more).

    Returns ``[B, 1, S]`` float32: 0 where ``ops/dsa.py:top_mask`` of the
    same scores selects, ``mla_decode.DROP`` elsewhere (the form
    ``mla_sparse_decode`` adds to its scores).
    """
    b, s = scores.shape
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be [B]={b}, got {lengths.shape}")
    k = min(topk, s)
    rows = min(DECODE_ROWS, _round_up(b, 8))
    width = DECODE_CHUNK if s >= DECODE_CHUNK else _round_up(s, 128)
    wide, tall = _round_up(s, width), _round_up(b, rows)
    scores = scores.astype(jnp.float32)
    lengths = lengths.astype(jnp.int32)
    if (wide, tall) != (s, b):
        scores = jnp.pad(scores, ((0, tall - b), (0, wide - s)),
                         constant_values=-jnp.inf)
        lengths = jnp.pad(lengths, (0, tall - b))
    kernel = functools.partial(_decode_body, k=k, width=width,
                               bits=(wide - 1).bit_length())
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tall // rows,),
            in_specs=[pl.BlockSpec((rows, wide), lambda i, *_: (i, Z))],
            out_specs=pl.BlockSpec((rows, 1, wide),
                                   lambda i, *_: (i, Z, Z)),
            scratch_shapes=[pltpu.VMEM((rows, wide), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((tall, 1, wide), jnp.float32),
        # the scores and the keep rows twice each, the keys once
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=5 * rows * wide * 4 + (8 << 20)),
        name="dsa_select",
        interpret=interpret,
    )(lengths, scores)
    return out if (wide, tall) == (s, b) else out[:b, :, :s]


# --- a prompt --------------------------------------------------------------------
def prefill_block(t):
    """Queries a program of :func:`dsa_prefill_select` takes at a prompt
    of ``t``: 256 while their keys fit ``_BLOCK_BYTES``, else 128; the
    whole prompt where it is shorter than a lane tile."""
    if t % 128:
        return t
    for block in (256, 128):
        if t % block == 0 and block * t * 4 <= _BLOCK_BYTES:
            return block
    return 128


def prefill_vmem_bytes(t, heads, dim, dtype, block):
    """The VMEM limit :func:`dsa_prefill_select_kernel` asks for: its
    buffers (the block's int32 keys, its int8 mask rows twice, the
    prompt's keys, the block's queries twice, the products of a key tile
    and the weights along the lanes) and a quarter and 8 MiB more."""
    item = jnp.dtype(dtype).itemsize
    tile = KEY_TILE if block % KEY_TILE == 0 else block
    vmem = (block * t * (4 + 2) + t * dim * item
            + heads * block * (dim * item * 2 + tile * 4 * 2)
            + 2 * block * 128 * 4)
    return int(vmem * 1.25) + (8 << 20)


def _prefill_body(q_ref, w_ref, k_ref, o_ref, k_all, wb, key_scr, *,
                  heads, dim, tile, rows, width, sum_rows, k, bits, scale):
    """One block of queries: its keys join those before it, its scores
    against the key tiles up to its last query go to keys in VMEM, the
    body runs over each group of ``rows`` rows, and its mask rows leave
    once."""
    i = pl.program_id(0)
    block, t = o_ref.shape
    first = i * block
    k_all[pl.ds(pl.multiple_of(first, block), block), :] = k_ref[...]
    for j in range(heads):
        wb[j] = jnp.broadcast_to(w_ref[:, j:j + 1], (block, tile))
    qry = first + jax.lax.broadcasted_iota(jnp.int32, (sum_rows, tile), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sum_rows, tile), 1)

    def score(c, carry):
        keys = k_all[pl.ds(pl.multiple_of(c * tile, tile), tile), :]
        s = jax.lax.dot_general(q_ref[...].reshape(heads * block, dim),
                                keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        for g in range(0, block, sum_rows):
            acc = jnp.zeros((sum_rows, tile), jnp.float32)
            for j in range(heads):
                acc = acc + (jax.nn.relu(s[j * block + g:
                                           j * block + g + sum_rows])
                             * wb[j, g:g + sum_rows])
            key_scr[g:g + sum_rows,
                    pl.ds(pl.multiple_of(c * tile, tile), tile)] = jnp.where(
                c * tile + col <= qry + g, _keys(acc * np.float32(scale)),
                UNSEEN)
        return carry

    _for(jax.lax.div(first + _i32(block), _i32(tile)), score, _i32(0))

    def group(g, carry):
        r0 = pl.multiple_of(g * rows, rows)
        n_chunks = jax.lax.div(first + r0 + _i32(rows + width - 1),
                               _i32(width))

        def blank(c, carry):
            o_ref[pl.ds(r0, rows),
                  pl.ds(pl.multiple_of(c * width, width), width)] = (
                jnp.zeros((rows, width), jnp.int8))
            return carry

        jax.lax.fori_loop(n_chunks, _i32(t // width), blank, _i32(0))

        def emit(c, chosen):
            o_ref[pl.ds(r0, rows),
                  pl.ds(pl.multiple_of(c * width, width), width)] = (
                jnp.where(chosen, _i32(1), _i32(0)).astype(jnp.int8))

        _select_rows(key_scr, r0, rows, n_chunks, width, k, bits, emit)
        return carry

    _for(block // rows, group, _i32(0))


def dsa_prefill_select_kernel(q, w, k, topk, scale, *, block=None,
                              interpret=False):
    """A prompt's selection, scored and selected in VMEM: int8 ``[T,
    T]``, 1 where causal query ``t`` selects key ``s`` (what
    ``ops/dsa.py:prefill_select`` computes from the same arguments).

    Args:
      q: ``[T, heads, dim]`` index queries.
      w: ``[T, heads]`` the heads' weights.
      k: ``[T, dim]`` index keys (``q``'s dtype on the MXU).
      topk: keys a query keeps (every causal one where it has no more).
      scale: ``dim^-0.5``.
      block: queries a program (:func:`prefill_block` by default).
    """
    t, heads, dim = q.shape
    if t % 128 and t > 128:
        raise ValueError(f"a prompt of {t} is not whole 128-lane tiles")
    block = prefill_block(t) if block is None else block
    if t % block or w.shape != (t, heads) or k.shape != (t, dim):
        raise ValueError(
            f"q [T, heads, dim]={q.shape}, w {w.shape} and k {k.shape} "
            f"with T a multiple of the block {block}")
    tile, rows, width, sum_rows = (
        n if block % n == 0 else block
        for n in (KEY_TILE, PREFILL_ROWS, PREFILL_CHUNK, SUM_ROWS))
    kernel = functools.partial(
        _prefill_body, heads=heads, dim=dim, tile=tile, rows=rows,
        width=width, sum_rows=sum_rows, k=min(topk, t),
        bits=(t - 1).bit_length(), scale=scale)
    k = k.astype(q.dtype)
    out = pl.pallas_call(
        kernel,
        grid=(t // block,),
        in_specs=[
            pl.BlockSpec((heads, block, dim), lambda i: (Z, i, Z)),
            pl.BlockSpec((block, heads), lambda i: (i, Z)),
            pl.BlockSpec((block, dim), lambda i: (i, Z)),
        ],
        out_specs=pl.BlockSpec((block, t), lambda i: (i, Z)),
        out_shape=jax.ShapeDtypeStruct((t, t), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((t, dim), q.dtype),
            pltpu.VMEM((heads, block, tile), jnp.float32),
            pltpu.VMEM((block, t), jnp.int32),
        ],
        # the blocks in order: a block's keys join those before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=prefill_vmem_bytes(t, heads, dim, q.dtype,
                                                block)),
        name="dsa_prefill_select",
        interpret=interpret,
    )(q.transpose(1, 0, 2), w.astype(jnp.float32), k)
    return out


def dsa_select(scores, lengths, *, topk, backend="auto"):
    """A decode step's ``keep`` rows (:func:`dsa_select_kernel`'s
    arguments and result); ``backend`` as ``paged_attention_decode``'s."""
    backend = resolve_backend(backend)
    if backend == "reference":
        return dsa_select_reference(scores, lengths, topk=topk)
    return dsa_select_kernel(scores, lengths, topk=topk,
                             interpret=(backend == "interpret"))


def dsa_prefill_select(q, w, k, topk, scale, *, backend="auto"):
    """A prompt's int8 ``[T, T]`` selection
    (:func:`dsa_prefill_select_kernel`'s arguments and result; the
    ``"reference"`` backend runs ``ops/dsa.py:prefill_select``)."""
    backend = resolve_backend(backend)
    if backend == "reference":
        return _dsa.prefill_select(q, w, k, topk, scale)
    return dsa_prefill_select_kernel(q, w, k, topk, scale,
                                     interpret=(backend == "interpret"))
