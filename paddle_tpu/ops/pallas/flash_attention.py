"""Pallas flash attention (forward + backward) for TPU.

TPU-native replacement for the reference's CUDA flashattn integration
(reference: phi/kernels/gpu/flash_attn_kernel.cu:35, Python surface
python/paddle/nn/functional/flash_attention.py:198,991). Design: classic
flash-attention online softmax; the backward recomputes P from the saved
logsumexp (no O(S^2) residuals). GQA is expressed in the BlockSpec index
maps (kv head = q head // group), so grouped KV blocks are fetched once
per q head without materialising the repeat.

Tiles. A call's grid is ``(batch, q head, tile)``: the third axis walks a
list of the (q block, k block) pairs the mask leaves anything of
(``_tile_list``), handed to the index maps by scalar prefetch, so a pair
the causal diagonal or a window's band empties costs neither a DMA nor a
grid step. Each pair carries flags: the first and last of its run (the
run's scratch is reset and written out there). Every tile of a causal
call is masked: on the chip the iota / compare / select hides behind the
products (``tools/flash_kernel_probe.py``: masking only the tiles the
edge crosses read 556 us against 560, 1,067 against 1,068).

Forward (``flash_fwd``): q-block-major; the running maximum and sum stay
lane-replicated ``[block_q, 128]`` in VMEM, q is scaled once a q block,
operands reach the MXU in the dtype they arrive in, and the logsumexp
leaves as ``[B, H, 1, Sq]`` rows.

Backward. Where a float32 ``[Sq, D]`` dq accumulator fits the VMEM
budget (``_single_pass_fits``: a rule on the shapes, no flag), one
k-block-major kernel (``flash_bwd_dkv``) computes each transposed score
tile ``k q^T`` once and from it dv, dk and the tile's share of dq; the
row statistics arrive as ``[1, block_q]`` rows, which the transposed
tile takes as a sublane broadcast. Longer sequences keep the two-kernel
form (``flash_bwd_dq`` + ``flash_bwd_dkv``, lane-padded statistics).

Layouts: the public API uses paddle's [B, S, H, D]. With heads a
multiple of 128 wide the kernels read that layout directly (a
``[B, S, H*D]`` view blocked ``(block, D)`` at lane block ``h``); other
head sizes, the split backward and the ``[B, H, S, D]`` entries
(``_flash_fwd_bhsd``, ``_flash_bwd_bhsd``) run head-major.
Maxima, sums, lse, delta and accumulators are float32; outputs cast back.

On non-TPU backends the same kernels run under `interpret=True`, which is
how the OpTest suite checks their arithmetic against the XLA composition
oracle. The interpreter does not check that Mosaic accepts a kernel:
tests/test_tpu_aot_compile.py compiles each one for the chip, and
chip_smoke.py runs them there against the same oracle.

In a sharded program each array-level function runs under shard_map over
batch and head (`ops/kernel_partition.py`); sequence and head_dim stay
whole per shard.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import observability as _obs
from ...core import dispatch
from ...core.flags import pallas_mode
from ..kernel_partition import shard_kernel

NEG_INF = float("-inf")
Z = np.int32(0)  # index-map literal: stays i32 under jax_enable_x64
LANES = 128  # the forward's statistics; the split backward's lse/delta padding

# a tile's flags in the prefetched list (see _tile_list)
_FIRST, _LAST = 1, 2

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a^T @ b

_M_TRACED = _obs.counter(
    "kernels.flash_traced", "flash attention calls traced into a program, "
    "by pass (fwd / bwd), the backward's form (single: one k-major kernel "
    "for dq, dk and dv; split: two kernels; '-' for the forward) and the "
    "layout the kernels read (bshd: the model's [B, S, H*D]; bhsd)")


def _c32(u):
    """uint32 literal as a wrapping int32 constant."""
    return jnp.int32(np.uint32(u).astype(np.int32))


def _dropout_keep(seed, bh, i, j, block_q, block_k, rate, transposed=False):
    """Counter-based attention-dropout mask for the (i, j) tile of head bh.

    P(keep) = 1 - rate. murmur3-style int32 mixing over
    (seed, batch*head, global row, global col) — pure vector int ops, so
    the SAME bits regenerate in the forward and the backward kernels,
    whatever tiles each walks, and under ``interpret=True``
    (``pltpu.prng_*`` has no interpret lowering). ``transposed``: the
    ``[block_k, block_q]`` tile of the single backward pass.
    Reference semantics: dropout on the softmax WEIGHTS
    (flash_attention.py:991 attn_dropout), denominator excluded.
    """
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, int(transposed))
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - int(transposed))
    x = (rows * _c32(0x9E3779B1)) ^ (cols * _c32(0x85EBCA77))
    x = x ^ (bh * _c32(0xC2B2AE3D)) ^ seed
    shr = lambda a, n: jax.lax.shift_right_logical(a, jnp.int32(n))
    x = x ^ shr(x, 16)
    x = x * _c32(0x85EBCA6B)
    x = x ^ shr(x, 13)
    x = x * _c32(0xC2B2AE35)
    x = x ^ shr(x, 16)
    thresh = jnp.int32(int(min(float(rate), 1.0) * 2147483647.0))
    keep = (x & _c32(0x7FFFFFFF)) >= thresh
    return keep.astype(jnp.float32)


def _interpret() -> bool:
    return pallas_mode() != "compiled"


def _pick_block(n: int, target: int = 512) -> int:
    """Largest power-of-two divisor of n, capped at target (>=128 when
    possible so blocks tile the lane dimension)."""
    b = min(n, target)
    while b > 1 and n % b:
        b //= 2
    return max(b, 1)


def _seq_block(n: int, target: int) -> int:
    """A sequence block of the flash kernels: ``_pick_block``, but a
    sequence no multiple of 128 is one block (its statistics are rows,
    whose blocks are whole lane tiles or the whole row)."""
    return _pick_block(n, target) if n % LANES == 0 else n


def _kv_head_map(g: int):
    """Index-map component mapping q head -> kv head (GQA). `h // g` via
    jnp inside an index map trips an int-promotion convert_element_type
    cycle in Mosaic lowering; use an identity map for g==1 and a
    same-dtype lax.div otherwise."""
    if g == 1:
        return lambda h: h
    return lambda h: jax.lax.div(h, np.int32(g))


# ---------------------------------------------------------------------------
# the tiles a call visits
# ---------------------------------------------------------------------------
def _tile_list(nq, nk, block_q, block_k, offset, causal, window, k_major):
    """(q block, k block, flags) int32 arrays of the pairs the mask leaves
    anything of, in visiting order: by q block, k ascending (the forward)
    or, ``k_major``, by k block, q ascending (the single backward pass).
    Query ``r`` sees keys ``<= r + offset`` (``causal``; offset = Sk - Sq,
    the bottom-right-aligned mask) and ``> r + offset - window``. Flags:
    ``_FIRST`` / ``_LAST`` of the run over one major block. A major block
    the mask empties (queries before the first key) keeps one pair, so
    that its outputs are written."""
    r_lo = np.arange(nq)[:, None] * block_q
    c_lo = np.arange(nk)[None, :] * block_k
    need = np.ones((nq, nk), bool)
    if causal:
        need = c_lo <= r_lo + block_q - 1 + offset
        if window is not None:
            need &= c_lo + block_k - 1 > r_lo + offset - window
    if k_major:
        need = need.T
    majors, minors, flags = [], [], []
    for a, row in enumerate(need):
        run = np.flatnonzero(row) if row.any() else np.zeros(1, int)
        f = np.zeros(run.size, int)
        f[0] |= _FIRST
        f[-1] |= _LAST
        majors += [a] * run.size
        minors += run.tolist()
        flags += f.tolist()
    qi, kj = (minors, majors) if k_major else (majors, minors)
    return tuple(jnp.asarray(np.asarray(x, np.int32)) for x in (qi, kj, flags))


def _head_spec(layout, rows, width, seq, head=lambda h: h):
    """BlockSpec of one head's ``[rows, width]`` tile of a q- or kv-shaped
    array: ``[B, H, S, D]`` (``bhsd``) or its ``[B, S, H*D]`` view
    (``bshd``: lane block ``head``). ``seq(t, qi, kj)`` is the block along
    the sequence for grid step ``t`` of the prefetched tile list."""
    if layout == "bhsd":
        return pl.BlockSpec(
            (None, None, rows, width),
            lambda b, h, t, qi, kj, fl: (b, head(h), seq(t, qi, kj), Z))
    return pl.BlockSpec(
        (None, rows, width),
        lambda b, h, t, qi, kj, fl: (b, seq(t, qi, kj), head(h)))


def _q_of(t, qi, kj):
    return qi[t]


def _k_of(t, qi, kj):
    return kj[t]


def _row_spec(block_q):
    """A ``[1, block_q]`` block of the ``[B, H, 1, Sq]`` row statistics."""
    return pl.BlockSpec((None, None, 1, block_q),
                        lambda b, h, t, qi, kj, fl: (b, h, Z, qi[t]))


def _flat_shape(shape, layout):
    """The shape a kernel blocks: ``[B, S, H, D]`` as ``[B, S, H*D]``."""
    shape = tuple(shape)
    return shape if layout == "bhsd" else shape[:2] + (shape[2] * shape[3],)


def _flat(x, layout):
    return x.reshape(_flat_shape(x.shape, layout))


def _dims(q, k, v, layout):
    """(B, H, Hkv, Sq, Sk, D, Dv) of a call's operands."""
    s, h = (2, 1) if layout == "bhsd" else (1, 2)
    return (q.shape[0], q.shape[h], k.shape[h], q.shape[s], k.shape[s],
            q.shape[3], v.shape[3])


def _lanes(x, n):
    """A lane-replicated ``[rows, 128]`` statistic at width ``n``."""
    if n == LANES:
        return x
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _visible(i, j, shape, block_q, block_k, offset, window, q_dim):
    """The mask of the (i, j) tile: query rows along ``q_dim`` of
    ``shape``, keys along the other."""
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
             - jax.lax.broadcasted_iota(jnp.int32, shape, q_dim))
    # col - row <= offset, in tile coordinates
    lim = i * block_q - j * block_k + offset
    seen = ahead <= lim
    if window is not None:
        seen &= ahead > lim - window
    return seen


def _optional_refs(refs, n, has_bias, rate):
    """(bias_ref, seed_ref, rest) of a kernel's refs after its first n."""
    bias_ref = refs[n] if has_bias else None
    n += int(has_bias)
    seed_ref = refs[n] if rate > 0.0 else None
    n += int(rate > 0.0)
    return bias_ref, seed_ref, refs[n:]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(qi_ref, kj_ref, fl_ref, *refs, scale, causal, block_q,
                block_k, offset, rate, n_heads, has_bias, window, guard):
    # offset = Sk - Sq: bottom-right-aligned causal mask (query i attends
    # keys <= i + offset), matching paddle/XLA semantics for Sq != Sk.
    # window (causal only): and keys > i + offset - window, a band.
    # guard: a row may have seen no key yet when a tile is computed (a
    # key bias of -inf, the band's lower edge, queries before the first
    # key); keep the exp args finite so it stays exactly zero, not NaN
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref, seed_ref, rest = _optional_refs(refs, 3, has_bias, rate)
    o_ref, lse_ref, qs_scr, m_scr, l_scr, acc_scr = rest
    t = pl.program_id(2)
    i, j, fl = qi_ref[t], kj_ref[t], fl_ref[t]
    bh = pl.program_id(0) * n_heads + pl.program_id(1)
    dv = acc_scr.shape[-1]

    @pl.when((fl & _FIRST) != 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        qs_scr[...] = (q_ref[...].astype(jnp.float32) * scale
                       ).astype(qs_scr.dtype)

    s = jax.lax.dot_general(qs_scr[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32)
    if has_bias:
        # additive per-key bias (broadcast over query rows): the
        # [B, 1, 1, Sk] padding-mask pattern of sdpa_mask_p
        s = s + bias_ref[...].astype(jnp.float32)
    if causal:
        s = jnp.where(_visible(i, j, s.shape, block_q, block_k, offset,
                               window, 0), s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_eff = jnp.where(m_new == NEG_INF, 0.0, m_new) if guard else m_new
    alpha = jnp.exp(m_prev - m_eff)  # exp(-inf)=0 for first visit
    p = jnp.exp(s - _lanes(m_eff, block_k))
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    if rate > 0.0:
        # softmax denominator (l) stays over the UNDROPPED weights;
        # only the value accumulation sees the mask (post-softmax
        # dropout semantics, matching the XLA oracle path)
        p = p * (_dropout_keep(seed_ref[0], bh, i, j, block_q, block_k,
                               rate) * (1.0 / (1.0 - rate)))
    acc_scr[...] = acc_scr[...] * _lanes(alpha, dv) + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], _NN,
        preferred_element_type=jnp.float32)

    @pl.when((fl & _LAST) != 0)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / _lanes(l_safe, dv)).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[...] + jnp.log(l_safe))
        # a row of the [B, H, 1, Sq] logsumexp: the columns' transpose
        lse_ref[...] = lse.T[:1]


# ---------------------------------------------------------------------------
# sharded programs: batch and head are independent, sequence and head_dim
# are not (see ops/kernel_partition.py)
# ---------------------------------------------------------------------------
def _run_flash(local, operands, results, arrays, *, partition, layout,
               **statics):
    """Call one shard-level flash function: directly, or under shard_map
    when the program is sharded. ``operands``/``results`` name each
    array's kind (x = q-shaped, k = kv-shaped, in ``layout``; l =
    lse-shaped [B, H, Sq]); the optional [B|1, Sk] bias and [1] seed
    follow the operands."""
    local = functools.partial(local, layout=layout, **statics)
    if partition is None:
        return local(*arrays)
    q, k = arrays[0], arrays[1]
    b_ax = partition.axis_if_divides(partition.batch, q.shape[0])
    # heads split on kv-head boundaries: a shard holds whole GQA groups
    h_ax = partition.axis_if_divides(
        partition.heads, k.shape[1 if layout == "bhsd" else 2])
    specs = {"l": (b_ax, h_ax, None)}
    specs["x"] = specs["k"] = ((b_ax, h_ax, None, None) if layout == "bhsd"
                               else (b_ax, None, h_ax, None))
    in_specs = [specs[c] for c in operands]
    if statics["has_bias"]:
        bias = arrays[len(operands)]
        in_specs.append((None if bias.shape[0] == 1 else b_ax, None))
    if statics["rate"] > 0.0:
        in_specs.append((None,))
    local = functools.partial(
        local, shard_axes=tuple(a for a in (b_ax, h_ax) if a is not None))
    return shard_kernel(local, partition, in_specs,
                        [specs[c] for c in results])(*arrays)


def _split_extras(extras, has_bias, rate, shard_axes):
    """(key_bias, seed) from the optional trailing operands. Inside a
    partitioned program the dropout seed is offset by the shard's index,
    so shards draw different masks (the kernels key the bits on LOCAL
    batch/head indices); forward and backward apply the same offset."""
    extras = list(extras)
    key_bias = extras.pop(0) if has_bias else None
    seed = extras.pop(0) if rate > 0.0 else None
    if seed is not None and shard_axes:
        seed = seed + (jax.lax.axis_index(shard_axes).astype(jnp.int32)
                       * _c32(0x9E3779B1))
    return key_bias, seed


def _bias_operand(key_bias, block_k, column):
    """(BlockSpec, array) of the [B|1, Sk] key bias: ``[1, block_k]`` rows
    for a ``[q, k]`` score tile, ``[block_k, 1]`` columns for a
    transposed one. A batch-1 bias (a mask shared across the batch) pins
    the index map to row 0 instead of materializing B copies."""
    nb, sk = key_bias.shape
    row = (lambda b: Z) if nb == 1 else (lambda b: b)
    if column:
        return (pl.BlockSpec((None, block_k, 1),
                             lambda b, h, t, qi, kj, fl: (row(b), kj[t], Z)),
                key_bias.reshape(nb, sk, 1))
    return (pl.BlockSpec((None, 1, block_k),
                         lambda b, h, t, qi, kj, fl: (row(b), Z, kj[t])),
            key_bias.reshape(nb, 1, sk))


_SEED_SPEC = pl.BlockSpec((1,), lambda b, h, t, qi, kj, fl: (Z,),
                          memory_space=pltpu.SMEM)


def _blocks(sq, sk):
    """(block_q, block_k) of the forward and of the single backward pass,
    from the shapes: 512 x 512 was the fastest of the tiles
    ``tools/flash_kernel_probe.py`` swept at every cell's shape (heads of
    64, 128 and 192/128, banded and plain): smaller tiles pay the
    statistics and the grid step more often than they save on the
    diagonal, larger ones waste more of it."""
    return _seq_block(sq, 512), _seq_block(sk, 512)


def _fwd_local(q, k, v, *extras, causal, scale, rate, has_bias, interpret,
               layout="bhsd", shard_axes=(), window=None, blocks=None):
    """One shard's forward: q [B,H,Sq,D]; k [B,Hkv,Sk,D]; v
    [B,Hkv,Sk,Dv] (``layout`` bhsd; bshd: [B,S,H,D] each) -> (out like
    q at width Dv, lse [B,H,Sq]). The value head may be another size than
    the query-key head (a latent-attention prefill: 192 and 128).
    ``window`` (with ``causal``): a query sees its last ``window`` keys
    only; key blocks outside that band are neither computed nor fetched
    (forward only). ``blocks``: (block_q, block_k) in place of the
    shapes' own (``tools/flash_kernel_probe.py`` sweeps them)."""
    key_bias, seed = _split_extras(extras, has_bias, rate, shard_axes)
    if window is not None and (not causal or has_bias or rate > 0.0):
        raise ValueError("flash forward: a window needs causal=True "
                         "and takes neither key bias nor dropout")
    B, H, Hkv, Sq, Sk, D, Dv = _dims(q, k, v, layout)
    block_q, block_k = blocks or _blocks(Sq, Sk)
    offset = Sk - Sq
    tiles = _tile_list(Sq // block_q, Sk // block_k, block_q, block_k,
                       offset, causal, window, k_major=False)
    kv_head = _kv_head_map(H // Hkv)
    _M_TRACED.inc(**{"pass": "fwd", "form": "-", "layout": layout})
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, offset=offset, rate=rate, n_heads=H,
        has_bias=has_bias, window=None if window is None else int(window),
        guard=has_bias or window is not None or (causal and offset < 0))
    in_specs = [
        _head_spec(layout, block_q, D, _q_of),
        _head_spec(layout, block_k, D, _k_of, kv_head),
        _head_spec(layout, block_k, Dv, _k_of, kv_head),
    ]
    inputs = [_flat(x, layout) for x in (q, k, v)]
    if key_bias is not None:
        spec, bias = _bias_operand(key_bias, block_k, column=False)
        in_specs.append(spec)
        inputs.append(bias)
    if rate > 0.0:
        in_specs.append(_SEED_SPEC)
        inputs.append(seed)
    out_shape = q.shape[:3] + (Dv,)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, tiles[0].shape[0]),
            in_specs=in_specs,
            out_specs=[_head_spec(layout, block_q, Dv, _q_of),
                       _row_spec(block_q)],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), q.dtype),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(_flat_shape(out_shape, layout), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * Sq * Sk * (D + Dv),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=B * H * Sq * Sk,
        ),
        name="flash_fwd",
        interpret=interpret,
    )(*tiles, *inputs)
    return out.reshape(out_shape), lse.reshape(B, H, Sq)


_JIT_STATICS = ("causal", "scale", "dropout_rate", "partition", "interpret",
                "layout")


@functools.partial(jax.jit, static_argnames=_JIT_STATICS + ("window",))
def _flash_fwd_jit(q, k, v, seed, key_bias, *, causal, scale, dropout_rate,
                   partition, interpret, layout="bhsd", window=None):
    extras = [x for x in (key_bias, seed) if x is not None]
    band = {} if window is None else {"window": window}
    return _run_flash(
        _fwd_local, "xkk", "xl", (q, k, v, *extras), partition=partition,
        layout=layout, causal=causal, scale=scale, rate=dropout_rate,
        has_bias=key_bias is not None, interpret=interpret, **band)


def _flash_fwd_bhsd(q, k, v, seed=None, key_bias=None, *, causal, scale,
                    dropout_rate=0.0, partition=None, window=None,
                    interpret=None):
    """q: [B,H,Sq,D]; k: [B,Hkv,Sk,D]; v: [B,Hkv,Sk,Dv] -> (out
    [B,H,Sq,Dv], lse [B,H,Sq]); Dv may differ from D (forward only).
    seed: int32 [1] dropout seed, required when dropout_rate > 0.
    key_bias: [B, Sk] additive logit bias broadcast over heads/rows (the
    padding-mask pattern), added BEFORE the causal mask/softmax.
    partition: the :class:`KernelPartition` of a sharded program.
    window: with ``causal``, a query sees its last ``window`` keys (a
    sliding-window layer's band; forward only, as serving needs it).
    interpret: None follows ``pallas_mode()``."""
    return _flash_fwd_jit(q, k, v, seed, key_bias, causal=causal,
                          scale=scale, dropout_rate=dropout_rate,
                          partition=partition, window=window,
                          interpret=(_interpret() if interpret is None
                                     else interpret))


# ---------------------------------------------------------------------------
# backward: one k-block-major pass for dq, dk and dv
# ---------------------------------------------------------------------------
def _bwd_kernel(qi_ref, kj_ref, fl_ref, *refs, scale, causal, block_q,
                block_k, offset, rate, n_heads, has_bias):
    """Transposed tiles ``[block_k, block_q]``: the row statistics are
    ``[1, block_q]`` rows (a sublane broadcast), dv = p^T do and dk =
    ds^T q are plain products, and only dq's contracts the tile's rows."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    bias_ref, seed_ref, rest = _optional_refs(refs, 6, has_bias, rate)
    dq_ref, dk_ref, dv_ref, ks_scr, dq_scr, dk_scr, dv_scr = rest
    t = pl.program_id(2)
    i, j, fl = qi_ref[t], kj_ref[t], fl_ref[t]
    bh = pl.program_id(0) * n_heads + pl.program_id(1)
    q_rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    @pl.when(t == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when((fl & _FIRST) != 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)
        # k is scaled once a k block: s and dq take it so, dk is scaled
        # where it is written out
        ks_scr[...] = (k_ref[...].astype(jnp.float32) * scale
                       ).astype(ks_scr.dtype)

    q, do = q_ref[...], do_ref[...]
    s = jax.lax.dot_general(ks_scr[...], q, _NT,
                            preferred_element_type=jnp.float32)
    if has_bias:
        s = s + bias_ref[...].astype(jnp.float32)   # [block_k, 1]
    if causal:
        s = jnp.where(_visible(i, j, s.shape, block_q, block_k, offset,
                               None, 1), s, NEG_INF)
    lse = lse_ref[...]                              # [1, block_q]
    p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
    dp = jax.lax.dot_general(v_ref[...], do, _NT,
                             preferred_element_type=jnp.float32)
    if rate > 0.0:
        # d/ds of out = (keep∘c∘softmax(s)) @ v with the softmax
        # denominator undropped: ds_j = p_j (keep_j c dp_j - delta),
        # delta = rowsum(do∘o) (absorbs the Σ p·dp term exactly);
        # the forward's bits of these rows and columns
        keep = _dropout_keep(seed_ref[0], bh, i, j, block_q, block_k,
                             rate, transposed=True) * (1.0 / (1.0 - rate))
        p_drop, dp = p * keep, dp * keep
    else:
        p_drop = p
    # dV += (keep∘c∘P)^T dO
    dv_scr[...] += jax.lax.dot_general(
        p_drop.astype(do.dtype), do, _NN,
        preferred_element_type=jnp.float32)
    ds = (p * (dp - delta_ref[...])).astype(q.dtype)
    # dK += dS^T Q (scaled at the end); dQ[i] += dS (K scale)
    dk_scr[...] += jax.lax.dot_general(
        ds, q, _NN, preferred_element_type=jnp.float32)
    dq_scr[q_rows, :] += jax.lax.dot_general(
        ds, ks_scr[...], _TN, preferred_element_type=jnp.float32)

    @pl.when((fl & _LAST) != 0)
    def _finalize():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize_dq():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


# the single pass keeps dq of one (batch, head) in VMEM: a float32
# [Sq, D] accumulator and the output's two buffers
_DQ_ACC_BYTES = 4 << 20
_VMEM_LIMIT = 64 << 20


def _single_pass_fits(sq, d):
    return sq * d * 4 <= _DQ_ACC_BYTES


def _bwd_single(q, k, v, do, lse, delta, key_bias, seed, *, causal, scale,
                rate, interpret, layout, blocks=None):
    """dq, and dk, dv a q head (the caller reduces GQA groups)."""
    B, H, Hkv, Sq, Sk, D, _ = _dims(q, k, v, layout)
    block_q, block_k = blocks or _blocks(Sq, Sk)
    offset = Sk - Sq
    tiles = _tile_list(Sq // block_q, Sk // block_k, block_q, block_k,
                       offset, causal, None, k_major=True)
    kv_head = _kv_head_map(H // Hkv)
    has_bias = key_bias is not None
    kernel = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, offset=offset, rate=rate, n_heads=H,
        has_bias=has_bias)
    q_spec = _head_spec(layout, block_q, D, _q_of)
    k_spec = _head_spec(layout, block_k, D, _k_of, kv_head)
    in_specs = [q_spec, k_spec, k_spec, q_spec,
                _row_spec(block_q), _row_spec(block_q)]
    inputs = [_flat(x, layout) for x in (q, k, v, do)]
    inputs += [lse.reshape(B, H, 1, Sq), delta.reshape(B, H, 1, Sq)]
    if has_bias:
        spec, bias = _bias_operand(key_bias, block_k, column=True)
        in_specs.append(spec)
        inputs.append(bias)
    if rate > 0.0:
        in_specs.append(_SEED_SPEC)
        inputs.append(seed)
    # dk, dv a q head: q's shape with Sk for Sq
    seq = 2 if layout == "bhsd" else 1
    dkv = jax.ShapeDtypeStruct(
        _flat_shape(q.shape[:seq] + (Sk,) + q.shape[seq + 1:], layout),
        k.dtype)
    dkv_spec = _head_spec(layout, block_k, D, _k_of)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, tiles[0].shape[0]),
            in_specs=in_specs,
            out_specs=[_head_spec(layout, Sq, D, lambda t, qi, kj: Z),
                       dkv_spec, dkv_spec],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), k.dtype),
                pltpu.VMEM((Sq, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct(_flat_shape(q.shape, layout),
                                        q.dtype), dkv, dkv],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=10 * B * H * Sq * Sk * D,
            bytes_accessed=(3 * q.size + 2 * k.size + 2 * B * H * Sk * D)
            * q.dtype.itemsize,
            transcendentals=B * H * Sq * Sk,
        ),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(*tiles, *inputs)


# ---------------------------------------------------------------------------
# backward: the two-kernel form, for a dq accumulator over the budget
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, nk, offset,
                   rate, n_heads, has_bias=False):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    n = 6
    bias_ref = refs[n] if has_bias else None
    n += int(has_bias)
    seed_ref = refs[n] if rate > 0.0 else None
    n += int(rate > 0.0)
    dq_ref, dq_scr = refs[n:]
    i = pl.program_id(2)
    j = pl.program_id(3)
    # hoisted: pl.program_id is not available inside a pl.when body under
    # interpret mode
    bh = pl.program_id(0) * n_heads + pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]  # lane-padded [block_q, LANES]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if rate > 0.0:
            # d/ds of out = (keep∘c∘softmax(s)) @ v with the softmax
            # denominator undropped: ds_j = p_j (keep_j c dp_j - delta),
            # delta = rowsum(do∘o) (absorbs the Σ p·dp term exactly)
            keep = _dropout_keep(seed_ref[0], bh, i, j, block_q, block_k,
                                 rate)
            dp = dp * keep * (1.0 / (1.0 - rate))
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        @pl.when(j * block_k <= i * block_q + (block_q - 1) + offset)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, nq, offset,
                    rate, n_heads, has_bias=False):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    n = 6
    bias_ref = refs[n] if has_bias else None
    n += int(has_bias)
    seed_ref = refs[n] if rate > 0.0 else None
    n += int(rate > 0.0)
    dk_ref, dv_ref, dk_scr, dv_scr = refs[n:]
    j = pl.program_id(2)  # k block
    i = pl.program_id(3)  # q block (innermost: accumulate over q)
    bh = pl.program_id(0) * n_heads + pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]  # lane-padded [block_q, LANES]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        if rate > 0.0:
            # same (b, h, i, j) tile bits as fwd/dq — note i is pid 3 here
            keep = _dropout_keep(seed_ref[0], bh, i, j, block_q, block_k,
                                 rate)
            p_drop = p * keep * (1.0 / (1.0 - rate))
        else:
            p_drop = p
        # dV += (keep∘c∘P)^T dO
        dv_scr[:] += jax.lax.dot_general(
            p_drop, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if rate > 0.0:
            dp = dp * keep * (1.0 / (1.0 - rate))
        ds = p * (dp - delta) * scale
        # dK += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        @pl.when(i * block_q + (block_q - 1) + offset >= j * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_split(q, k, v, do, lse, delta, key_bias, seed, *, causal, scale,
               rate, interpret):
    """dq, and dk, dv a q head, head-major ([B, H, S, D]) by two kernels:
    dq over q blocks, dk and dv over k blocks, each recomputing the
    scores; the row statistics lane-padded."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    has_bias = key_bias is not None
    block_q = _pick_block(Sq)
    block_k = _pick_block(Sk)
    nq, nk = Sq // block_q, Sk // block_k
    kv_head = _kv_head_map(H // Hkv)
    lse = jnp.broadcast_to(lse[..., None], (B, H, Sq, LANES))
    delta = jnp.broadcast_to(delta[..., None], (B, H, Sq, LANES))

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk, offset=Sk - Sq,
        rate=rate, n_heads=H, has_bias=has_bias)
    dq_in_specs = [
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), j, Z)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), j, Z)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, i, j: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, i, j: (b, h, i, Z)),
    ]
    dq_inputs = [q, k, v, do, lse, delta]
    if key_bias is not None:
        bmap = ((lambda b, h, i, j: (Z, Z, j)) if key_bias.shape[0] == 1
                else (lambda b, h, i, j: (b, Z, j)))
        dq_in_specs.append(pl.BlockSpec((1, 1, block_k), bmap))
        dq_inputs.append(key_bias.reshape(key_bias.shape[0], 1,
                                          key_bias.shape[1]))
    if rate > 0.0:
        dq_in_specs.append(pl.BlockSpec((1,), lambda b, h, i, j: (Z,),
                                  memory_space=pltpu.SMEM))
        dq_inputs.append(seed)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, H, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, Z)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        name="flash_bwd_dq",
        interpret=interpret,
    )(*dq_inputs)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nq=nq, offset=Sk - Sq,
        rate=rate, n_heads=H, has_bias=has_bias)
    dkv_in_specs = [
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, i: (b, kv_head(h), j, Z)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, i: (b, kv_head(h), j, Z)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, j, i: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, j, i: (b, h, i, Z)),
    ]
    dkv_inputs = [q, k, v, do, lse, delta]
    if key_bias is not None:
        # note swapped grid axes here: j=pid2 (k block), i=pid3 (q block)
        bmap = ((lambda b, h, j, i: (Z, Z, j)) if key_bias.shape[0] == 1
                else (lambda b, h, j, i: (b, Z, j)))
        dkv_in_specs.append(pl.BlockSpec((1, 1, block_k), bmap))
        dkv_inputs.append(key_bias.reshape(key_bias.shape[0], 1,
                                           key_bias.shape[1]))
    if rate > 0.0:
        dkv_in_specs.append(pl.BlockSpec((1,), lambda b, h, i, j: (Z,),
                                  memory_space=pltpu.SMEM))
        dkv_inputs.append(seed)
    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        grid=(B, H, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, Z)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(*dkv_inputs)
    return dq, dk_h, dv_h


def _bwd_local(q, k, v, out, lse, do, *extras, causal, scale, rate,
               has_bias, interpret, layout="bhsd", shard_axes=(),
               blocks=None):
    """One shard's backward -> (dq, dk, dv), in ``layout`` like q, k, v,
    out and do; lse is [B, H, Sq]. One pass where its dq accumulator fits
    (``_single_pass_fits``), else the two kernels, which run head-major."""
    key_bias, seed = _split_extras(extras, has_bias, rate, shard_axes)
    B, H, Hkv, Sq, Sk, D, _ = _dims(q, k, v, layout)
    single = _single_pass_fits(Sq, D)
    if not single and layout != "bhsd":
        raise ValueError("flash backward: the split form runs head-major")
    _M_TRACED.inc(**{"pass": "bwd", "form": "single" if single else "split",
                     "layout": layout})
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if layout == "bshd":
        delta = delta.transpose(0, 2, 1)                # [B, H, Sq]
    kw = dict(causal=causal, scale=scale, rate=rate, interpret=interpret)
    if single:
        dq, dk, dv = _bwd_single(q, k, v, do, lse, delta, key_bias, seed,
                                 layout=layout, blocks=blocks, **kw)
    else:
        dq, dk, dv = _bwd_split(q, k, v, do, lse, delta, key_bias, seed,
                                **kw)
    # dk, dv come a q head: reduce each GQA group to its kv head
    g = H // Hkv
    grouped = ((B, Hkv, g, Sk, D) if layout == "bhsd"
               else (B, Sk, Hkv, g, D))
    dk, dv = (x.reshape(grouped).sum(axis=2 if layout == "bhsd" else 3)
              .astype(x.dtype) if g > 1 else x.reshape(k.shape)
              for x in (dk, dv))
    return dq.reshape(q.shape), dk, dv


@functools.partial(jax.jit, static_argnames=_JIT_STATICS)
def _flash_bwd_jit(q, k, v, out, lse, do, seed, key_bias, *, causal, scale,
                   dropout_rate, partition, interpret, layout="bhsd"):
    extras = [x for x in (key_bias, seed) if x is not None]
    return _run_flash(
        _bwd_local, "xkkxlx", "xkk", (q, k, v, out, lse, do, *extras),
        partition=partition, layout=layout, causal=causal, scale=scale,
        rate=dropout_rate, has_bias=key_bias is not None,
        interpret=interpret)


def _flash_bwd_bhsd(q, k, v, out, lse, do, seed=None, key_bias=None, *,
                    causal, scale, dropout_rate=0.0, partition=None):
    return _flash_bwd_jit(q, k, v, out, lse, do, seed, key_bias,
                          causal=causal, scale=scale,
                          dropout_rate=dropout_rate, partition=partition,
                          interpret=_interpret())


# ---------------------------------------------------------------------------
# array-level API (paddle [B, S, H, D] layout) + primitive registration
# ---------------------------------------------------------------------------
def _reads_bshd(*widths):
    """Heads a multiple of the lane width are blocked out of the model's
    own [B, S, H*D] layout; others go head-major."""
    return all(w % LANES == 0 for w in widths)


def flash_attention_bshd(q, k, v, *extras, causal=False, scale=None,
                         dropout_rate=0.0, has_bias=False, partition=None):
    """Array-level flash attention in paddle layout. Returns (out, lse).

    ``extras`` holds the optional inputs IN ORDER: ``key_bias`` ([B, Sk]
    additive logit bias, present when ``has_bias``) then ``seed``
    (int32 [1], present when ``dropout_rate > 0`` — reference flash_attn
    dropout parity, flash_attn_kernel.cu:35 rng plumbing)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    extras = list(extras)
    key_bias = extras.pop(0) if has_bias else None
    seed = extras.pop(0) if dropout_rate > 0.0 else None
    kw = dict(causal=causal, scale=float(scale),
              dropout_rate=float(dropout_rate), partition=partition,
              interpret=_interpret())
    if _reads_bshd(q.shape[-1], v.shape[-1]):
        return _flash_fwd_jit(q, k, v, seed, key_bias, layout="bshd", **kw)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out, lse = _flash_fwd_jit(qt, kt, vt, seed, key_bias, **kw)
    return jnp.swapaxes(out, 1, 2), lse


def _flash_vjp(grads_out, saved, *, causal, scale, dropout_rate=0.0,
               has_bias=False, partition=None):
    *ins, out, lse = saved
    q, k, v = ins[:3]
    rest = list(ins[3:])
    key_bias = rest.pop(0) if has_bias else None
    seed = rest.pop(0) if dropout_rate > 0.0 else None
    do = grads_out[0]
    kw = dict(causal=causal, scale=float(scale),
              dropout_rate=float(dropout_rate), partition=partition,
              interpret=_interpret())
    if _reads_bshd(q.shape[-1]) and _single_pass_fits(q.shape[1],
                                                      q.shape[-1]):
        grads = _flash_bwd_jit(q, k, v, out, lse, do, seed, key_bias,
                               layout="bshd", **kw)
    else:
        qt, kt, vt, ot, dot = (jnp.swapaxes(x, 1, 2)
                               for x in (q, k, v, out, do))
        grads = tuple(jnp.swapaxes(g, 1, 2) for g in _flash_bwd_jit(
            qt, kt, vt, ot, lse, dot, seed, key_bias, **kw))
    # optional inputs (bias, seed) take no grads: the bias is a mask
    return tuple(grads) + (None,) * (len(ins) - 3)


dispatch.register_primitive(
    "flash_attention_p",
    flash_attention_bshd,
    vjp=_flash_vjp,
    save=lambda arrays, outs: (*arrays, outs[0], outs[1]),
    multi_out=True,
    jittable=False,  # already jitted internally; pallas_call dislikes re-trace
)


def flash_attention_fused(q, k, v, *, causal=False, scale=None,
                          dropout_p=0.0, rng=None, key_bias=None,
                          partition=None):
    """Tensor-level entry used by nn.functional.scaled_dot_product_attention.
    Returns the attention output Tensor (lse is kept for backward only).
    ``dropout_p`` > 0 requires ``rng`` (a Tensor wrapping a jax PRNG key);
    the key is folded to an int32 seed for the in-kernel counter RNG.
    ``key_bias`` is a [B, Sk] additive logit bias Tensor (the padding-mask
    pattern), broadcast over heads and query rows inside the kernel.
    ``partition`` is the :class:`KernelPartition` of a sharded model; it
    reaches the backward kernels as a primitive static."""
    from ...core.tensor import Tensor, apply

    scale = (float(scale) if scale is not None
             else 1.0 / math.sqrt(q.shape[-1]))
    extras = []
    statics = dict(causal=bool(causal), scale=scale)
    if partition is not None:
        statics["partition"] = partition
    if key_bias is not None:
        if not getattr(key_bias, "stop_gradient", True):
            raise ValueError(
                "flash_attention_fused: key_bias is a mask input and "
                "receives no gradient; a trainable additive bias must "
                "use the XLA attention path (sdpa with attn_mask).")
        extras.append(key_bias)
        statics["has_bias"] = True
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(
            f"flash_attention_fused: dropout_p must be in [0, 1), "
            f"got {dropout_p} (the 1/(1-p) keep-scale diverges at 1)")
    if dropout_p > 0.0:
        if rng is None:
            raise ValueError(
                "flash_attention_fused: dropout_p > 0 requires rng (a "
                "Tensor wrapping a jax PRNG key) for the in-kernel "
                "counter RNG")
        key_bits = jax.lax.bitcast_convert_type(
            jax.random.key_data(rng._value), jnp.int32).ravel()
        extras.append(Tensor._from_value((key_bits[:1] ^ key_bits[-1:])))
        statics["dropout_rate"] = float(dropout_p)
    out, _lse = apply("flash_attention_p", q, k, v, *extras, **statics)
    return out
