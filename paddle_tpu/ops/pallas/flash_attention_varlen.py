"""Pallas varlen (unpadded) flash attention for TPU.

TPU-native replacement for the reference's varlen CUDA kernels
(reference: phi/kernels/gpu/flash_attn_kernel.cu:35 FlashAttnUnpaddedKernel,
Python surface python/paddle/nn/functional/flash_attention.py:602).

Design: the packed token axis [T, H, D] stays packed — no per-segment
slicing, no recompiles when the segment layout changes. cu_seqlens are
turned into three per-token int32 vectors outside the kernel (segment id
for q rows, segment id for k rows, and for causal masking the global
k-column bound each q row may attend to, bottom-right aligned per
segment). The kernels are the same online-softmax flash loops as the
dense ones (flash_attention.py), with the (row, col) mask computed from
the segment vectors: valid iff same segment and (causal) col <= bound.
Cross-segment blocks are skipped via block-level min/max tests on the
(sorted) segment ids, so the work done is ~block-diagonal, matching the
varlen kernel's O(sum_i len_i^2) cost rather than O(T^2).

GQA is expressed through the BlockSpec kv-head index map; grids carry no
batch axis (batch is the packing). Padding rows (to block multiples) get
sentinel segment ids that never match, and fully-masked rows emit zeros
(lse = -inf) exactly like the dense kernel's drain path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import dispatch
from .flash_attention import _dropout_keep
from .flash_attention import (_interpret, _kv_head_map, _pick_block,
                              LANES, NEG_INF, Z)


def _pad_to(n: int, mult: int) -> int:
    return (n + mult - 1) // mult * mult


def _seg_vectors(cu_q, cu_k, t_q, t_k, pad_q, pad_k, n_seqs):
    """Per-token segment ids + causal column bounds from cu_seqlens.

    Returns (seg_q [pad_q], seg_k [pad_k], bound [pad_q]) int32. Padding
    rows get sentinel ids (n_seqs for q, n_seqs+1 for k) that keep the
    vectors nondecreasing but never equal, and bound = -1 (mask all).
    """
    cu_q = cu_q.astype(jnp.int32)
    cu_k = cu_k.astype(jnp.int32)
    pos_q = jnp.arange(pad_q, dtype=jnp.int32)
    pos_k = jnp.arange(pad_k, dtype=jnp.int32)
    seg_q = jnp.searchsorted(cu_q[1:], pos_q, side="right").astype(jnp.int32)
    seg_k = jnp.searchsorted(cu_k[1:], pos_k, side="right").astype(jnp.int32)
    seg_q = jnp.where(pos_q < t_q, seg_q, n_seqs)
    seg_k = jnp.where(pos_k < t_k, seg_k, n_seqs + 1)
    sq = jnp.clip(seg_q, 0, n_seqs - 1)
    len_q = cu_q[sq + 1] - cu_q[sq]
    len_k = cu_k[sq + 1] - cu_k[sq]
    local = pos_q - cu_q[sq]
    bound = cu_k[sq] + local + (len_k - len_q)
    bound = jnp.where(pos_q < t_q, bound, -1)
    return seg_q, seg_k, bound


def _seg_operands(cu_q, cu_k, Tq, Tk, n_seqs):
    """The segment vectors as the 2-D operands the kernels block:
    per-q-row values lane-broadcast to [Tq, LANES], per-k-column values
    sublane-broadcast to [8, Tk]. A 1-D int32 operand cannot be blocked
    at (block,): XLA tiles it T(1024) and Mosaic wants the block's own
    tiling, so the chip refuses the call at T=8192. The 2-D forms also
    hand the kernel a column and a row without an in-kernel relayout."""
    seg_q, seg_k, bound = _seg_vectors(
        cu_q, cu_k, cu_q[-1], cu_k[-1], Tq, Tk, n_seqs)
    return (jnp.broadcast_to(seg_q[:, None], (Tq, LANES)),
            jnp.broadcast_to(seg_k[None, :], (8, Tk)),
            jnp.broadcast_to(bound[:, None], (Tq, LANES)))


def _seg_specs(block_q, block_k, q_map, k_map):
    """BlockSpecs of (seg_q, seg_k, bound); ``q_map``/``k_map`` pick the
    q / k block index out of the grid indices."""
    rows = pl.BlockSpec((block_q, LANES), lambda *g: (q_map(*g), Z))
    cols = pl.BlockSpec((8, block_k), lambda *g: (Z, k_map(*g)))
    return [rows, cols, rows]


def _load_segs(segq_ref, segk_ref, bound_ref):
    """(sq [bq, 1], sk [1, bk], bound [bq, 1]) from the broadcast blocks."""
    return segq_ref[:, :1], segk_ref[:1, :], bound_ref[:, :1]


def _mask_for(sq, sk, bound, j, block_k, causal):
    """[bq, bk] validity mask from the segment column/row."""
    same = sq == sk
    if causal:
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (sq.shape[0], block_k), 1)
        same = same & (cols <= bound)
    return same


def _skip_block(sq, sk, bound, j, block_k, causal):
    """True when this (q block, k block) pair has no valid pair: segment
    ids are nondecreasing, so ranges must overlap; under causal masking
    the k block must start at or below the largest row bound."""
    disjoint = (jnp.max(sq) < jnp.min(sk)) | (jnp.min(sq) > jnp.max(sk))
    if causal:
        disjoint = disjoint | (j * block_k > jnp.max(bound))
    return disjoint


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _vfwd_kernel(*refs, scale, causal, block_q, block_k, nk, rate):
    if rate > 0.0:
        (q_ref, k_ref, v_ref, segq_ref, segk_ref, bound_ref, seed_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, segq_ref, segk_ref, bound_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
        seed_ref = None
    h = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    sq, sk, bound = _load_segs(segq_ref, segk_ref, bound_ref)

    @pl.when(~_skip_block(sq, sk, bound, j, block_k, causal))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(_mask_for(sq, sk, bound, j, block_k, causal),
                      s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_eff = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_eff)
        p = jnp.exp(s - m_eff)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            # same contract as the dense kernel (_fwd_kernel): denominator
            # undropped, value accumulation masked+rescaled; bits keyed on
            # packed-token coordinates so fwd and both bwd kernels agree
            keep = _dropout_keep(seed_ref[0], h, i, j, block_q, block_k,
                                 rate)
            p_use = p * keep * (1.0 / (1.0 - rate))
        else:
            p_use = p
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p_use, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        m = m_scr[:, :1]
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "n_seqs",
                                              "dropout_rate", "interpret"))
def _vflash_fwd(q, k, v, cu_q, cu_k, seed=None, *, causal, scale, n_seqs,
                dropout_rate=0.0, interpret):
    """q: [H, Tq, D]; k, v: [Hkv, Tk, D] (already padded to block
    multiples); returns (out [H, Tq, D], lse [H, Tq])."""
    H, Tq, D = q.shape
    Hkv, Tk = k.shape[0], k.shape[1]
    g = H // Hkv
    block_q = _pick_block(Tq)
    block_k = _pick_block(Tk)
    nq, nk = Tq // block_q, Tk // block_k
    kv_head = _kv_head_map(g)
    seg_q, seg_k, bound = _seg_operands(cu_q, cu_k, Tq, Tk, n_seqs)
    kernel = functools.partial(
        _vfwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk, rate=dropout_rate)
    in_specs = [
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, Z)),
            pl.BlockSpec((1, block_k, D), lambda h, i, j: (kv_head(h), j, Z)),
            pl.BlockSpec((1, block_k, D), lambda h, i, j: (kv_head(h), j, Z)),
            *_seg_specs(block_q, block_k,
                        lambda h, i, j: i, lambda h, i, j: j),
    ]
    inputs = [q, k, v, seg_q, seg_k, bound]
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec((1,), lambda h, i, j: (Z,),
                                     memory_space=pltpu.SMEM))
        inputs.append(seed)
    out, lse = pl.pallas_call(
        kernel,
        grid=(H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, Z)),
            pl.BlockSpec((1, block_q, LANES), lambda h, i, j: (h, i, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((H, Tq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_varlen_fwd",
        interpret=interpret,
    )(*inputs)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _vbwd_dq_kernel(*refs, scale, causal, block_q, block_k, nk, rate):
    if rate > 0.0:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         segq_ref, segk_ref, bound_ref, seed_ref, dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         segq_ref, segk_ref, bound_ref, dq_ref, dq_scr) = refs
        seed_ref = None
    h = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    sq, sk, bound = _load_segs(segq_ref, segk_ref, bound_ref)

    @pl.when(~_skip_block(sq, sk, bound, j, block_k, causal))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(_mask_for(sq, sk, bound, j, block_k, causal),
                      s, NEG_INF)
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if rate > 0.0:
            keep = _dropout_keep(seed_ref[0], h, i, j, block_q, block_k,
                                 rate)
            dp = dp * keep * (1.0 / (1.0 - rate))
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _vbwd_dkv_kernel(*refs, scale, causal, block_q, block_k, nq, rate):
    if rate > 0.0:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         segq_ref, segk_ref, bound_ref, seed_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         segq_ref, segk_ref, bound_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        seed_ref = None
    h = pl.program_id(0)
    j = pl.program_id(1)  # k block
    i = pl.program_id(2)  # q block (innermost: accumulate)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    sq, sk, bound = _load_segs(segq_ref, segk_ref, bound_ref)

    @pl.when(~_skip_block(sq, sk, bound, j, block_k, causal))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(_mask_for(sq, sk, bound, j, block_k, causal),
                      s, NEG_INF)
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        if rate > 0.0:
            keep = _dropout_keep(seed_ref[0], h, i, j, block_q, block_k,
                                 rate)
            p_drop = p * keep * (1.0 / (1.0 - rate))
        else:
            p_drop = p
        dv_scr[:] += jax.lax.dot_general(
            p_drop, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if rate > 0.0:
            dp = dp * keep * (1.0 / (1.0 - rate))
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "n_seqs",
                                              "dropout_rate", "interpret"))
def _vflash_bwd(q, k, v, cu_q, cu_k, out, lse, do, seed=None, *, causal,
                scale, n_seqs, dropout_rate=0.0, interpret):
    H, Tq, D = q.shape
    Hkv, Tk = k.shape[0], k.shape[1]
    g = H // Hkv
    block_q = _pick_block(Tq)
    block_k = _pick_block(Tk)
    nq, nk = Tq // block_q, Tk // block_k
    kv_head = _kv_head_map(g)
    seg_q, seg_k, bound = _seg_operands(cu_q, cu_k, Tq, Tk, n_seqs)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse_p = jnp.broadcast_to(lse[..., None], (H, Tq, LANES))
    delta_p = jnp.broadcast_to(delta[..., None], (H, Tq, LANES))

    dq_in_specs = [
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, Z)),
            pl.BlockSpec((1, block_k, D), lambda h, i, j: (kv_head(h), j, Z)),
            pl.BlockSpec((1, block_k, D), lambda h, i, j: (kv_head(h), j, Z)),
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, Z)),
            pl.BlockSpec((1, block_q, LANES), lambda h, i, j: (h, i, Z)),
            pl.BlockSpec((1, block_q, LANES), lambda h, i, j: (h, i, Z)),
            *_seg_specs(block_q, block_k,
                        lambda h, i, j: i, lambda h, i, j: j),
    ]
    dq_inputs = [q, k, v, do, lse_p, delta_p, seg_q, seg_k, bound]
    if dropout_rate > 0.0:
        dq_in_specs.append(pl.BlockSpec((1,), lambda h, i, j: (Z,),
                                        memory_space=pltpu.SMEM))
        dq_inputs.append(seed)
    dq = pl.pallas_call(
        functools.partial(_vbwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          rate=dropout_rate),
        grid=(H, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, Z)),
        out_shape=jax.ShapeDtypeStruct((H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_varlen_bwd_dq",
        interpret=interpret,
    )(*dq_inputs)

    dkv_in_specs = [
            pl.BlockSpec((1, block_q, D), lambda h, j, i: (h, i, Z)),
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (kv_head(h), j, Z)),
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (kv_head(h), j, Z)),
            pl.BlockSpec((1, block_q, D), lambda h, j, i: (h, i, Z)),
            pl.BlockSpec((1, block_q, LANES), lambda h, j, i: (h, i, Z)),
            pl.BlockSpec((1, block_q, LANES), lambda h, j, i: (h, i, Z)),
            *_seg_specs(block_q, block_k,
                        lambda h, j, i: i, lambda h, j, i: j),
    ]
    dkv_inputs = [q, k, v, do, lse_p, delta_p, seg_q, seg_k, bound]
    if dropout_rate > 0.0:
        dkv_in_specs.append(pl.BlockSpec((1,), lambda h, j, i: (Z,),
                                         memory_space=pltpu.SMEM))
        dkv_inputs.append(seed)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_vbwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          rate=dropout_rate),
        grid=(H, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (h, j, Z)),
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (h, j, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((H, Tk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_varlen_bwd_dkv",
        interpret=interpret,
    )(*dkv_inputs)
    if g > 1:
        dk = dk_h.reshape(Hkv, g, Tk, D).sum(axis=1).astype(k.dtype)
        dv = dv_h.reshape(Hkv, g, Tk, D).sum(axis=1).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


# ---------------------------------------------------------------------------
# array-level API (packed [T, H, D] layout) + primitive registration
# ---------------------------------------------------------------------------
def _to_htd(x, t_pad):
    """[T, H, D] -> [H, T_pad, D] (transpose + zero-pad the token axis)."""
    x = jnp.swapaxes(x, 0, 1)
    if t_pad > x.shape[1]:
        x = jnp.pad(x, ((0, 0), (0, t_pad - x.shape[1]), (0, 0)))
    return x


def flash_attn_varlen_thd(q, k, v, cu_q, cu_k, seed=None, *, causal=False,
                          scale=None, n_seqs=None, dropout_rate=0.0):
    """Array-level varlen attention over packed [T, H, D] tensors.

    cu_seqlens are data (not static): one compile serves every segment
    layout with the same packed lengths. ``seed`` (int32 [1]) enables
    in-kernel attention dropout at ``dropout_rate``. Returns
    (out [Tq, H, D], lse [H, Tq_pad])."""
    Tq = q.shape[0]
    Tk = k.shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if n_seqs is None:
        n_seqs = cu_q.shape[0] - 1
    pad_q = _pad_to(Tq, 128)
    pad_k = _pad_to(Tk, 128)
    qh = _to_htd(q, pad_q)
    kh = _to_htd(k, pad_k)
    vh = _to_htd(v, pad_k)
    out, lse = _vflash_fwd(qh, kh, vh, cu_q, cu_k, seed, causal=bool(causal),
                           scale=float(scale), n_seqs=int(n_seqs),
                           dropout_rate=float(dropout_rate),
                           interpret=_interpret())
    return jnp.swapaxes(out[:, :Tq], 0, 1), lse


def _varlen_fwd_prim(q, k, v, cu_q, cu_k, seed=None, *, causal, scale,
                     n_seqs, dropout_rate=0.0):
    out, lse = flash_attn_varlen_thd(q, k, v, cu_q, cu_k, seed,
                                     causal=causal, scale=scale,
                                     n_seqs=n_seqs,
                                     dropout_rate=dropout_rate)
    return out, lse


def _varlen_vjp(grads_out, saved, *, causal, scale, n_seqs,
                dropout_rate=0.0):
    *ins, out, lse = saved
    q, k, v, cu_q, cu_k = ins[:5]
    seed = ins[5] if len(ins) > 5 else None
    do = grads_out[0]
    Tq, Tk = q.shape[0], k.shape[0]
    pad_q = lse.shape[1]
    pad_k = _pad_to(Tk, 128)
    dq, dk, dv = _vflash_bwd(
        _to_htd(q, pad_q), _to_htd(k, pad_k), _to_htd(v, pad_k),
        cu_q, cu_k, _to_htd(out, pad_q), lse, _to_htd(do, pad_q), seed,
        causal=causal, scale=float(scale), n_seqs=int(n_seqs),
        dropout_rate=float(dropout_rate), interpret=_interpret())
    grads = (jnp.swapaxes(dq[:, :Tq], 0, 1), jnp.swapaxes(dk[:, :Tk], 0, 1),
             jnp.swapaxes(dv[:, :Tk], 0, 1), None, None)
    if seed is not None:
        grads = grads + (None,)
    return grads


dispatch.register_primitive(
    "flash_attn_varlen_p",
    _varlen_fwd_prim,
    vjp=_varlen_vjp,
    save=lambda arrays, outs: (*arrays, outs[0], outs[1]),
    multi_out=True,
    jittable=False,  # jitted internally; pallas_call dislikes re-trace
)
