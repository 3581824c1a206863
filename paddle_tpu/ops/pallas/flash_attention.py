"""Pallas flash attention (forward + backward) for TPU.

TPU-native replacement for the reference's CUDA flashattn integration
(reference: phi/kernels/gpu/flash_attn_kernel.cu:35, Python surface
python/paddle/nn/functional/flash_attention.py:198,991). Design: classic
flash-attention online-softmax over a (batch, q_head, q_block, k_block)
sequential grid — the k_block axis is innermost so VMEM scratch carries the
running (max, sum, accumulator) across k blocks; backward recomputes P from
the saved logsumexp (no O(S^2) residuals). GQA is expressed in the BlockSpec
index maps (kv head = q head // group), so grouped KV blocks are fetched
once per q head without materialising the repeat.

Layouts: public API uses paddle's [B, S, H, D]; kernels run [B, H, S, D].
Compute is fp32 on the MXU (`preferred_element_type`), outputs cast back.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import dispatch
from ...core.flags import pallas_mode
from ..kernel_partition import shard_kernel

NEG_INF = float("-inf")
Z = __import__("numpy").int32(0)  # index-map literal: stays i32 under jax_enable_x64
LANES = 128  # lse/delta lane padding (TPU (8,128) tiling; see _fwd_kernel)


def _c32(u):
    """uint32 literal as a wrapping int32 constant."""
    import numpy as np

    return jnp.int32(np.uint32(u).astype(np.int32))


def _dropout_keep(seed, bh, i, j, block_q, block_k, rate):
    """Counter-based attention-dropout mask for the (i, j) tile of head bh.

    P(keep) = 1 - rate. murmur3-style int32 mixing over
    (seed, batch*head, global row, global col) — pure vector int ops, so
    the SAME bits regenerate in the forward and both backward kernels
    (their grids visit the same (b, h, i, j) tiles) and under
    ``interpret=True`` (``pltpu.prng_*`` has no interpret lowering).
    Reference semantics: dropout on the softmax WEIGHTS
    (flash_attention.py:991 attn_dropout), denominator excluded.
    """
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
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



def _kv_head_map(g: int):
    """Index-map component mapping q head -> kv head (GQA). `h // g` via
    jnp inside an index map trips an int-promotion convert_element_type
    cycle in Mosaic lowering; use an identity map for g==1 and a
    same-dtype lax.div otherwise."""
    if g == 1:
        return lambda h: h
    import numpy as _np

    return lambda h: jax.lax.div(h, _np.int32(g))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _band_blocks(i, block_q, block_k, offset, window):
    """(first, last) key block that query block ``i`` sees under a causal
    mask with a window: query ``r`` sees keys ``(r + offset - window,
    r + offset]``. int32 throughout (``jax_enable_x64`` is on)."""
    i32 = type(Z)
    lo = jnp.maximum(i * i32(block_q) + i32(offset - window + 1), i32(0))
    hi = i * i32(block_q) + i32(block_q - 1 + offset)
    return jax.lax.div(lo, i32(block_k)), jax.lax.div(hi, i32(block_k))


def _fwd_kernel(*refs, scale, causal, block_q, block_k, nk, offset,
                rate, n_heads, has_bias=False, window=None):
    # offset = Sk - Sq: bottom-right-aligned causal mask (query i attends
    # keys <= i + offset), matching paddle/XLA semantics for Sq != Sk.
    # window (causal only): and keys > i + offset - window, a band
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    n = 3
    bias_ref = refs[n] if has_bias else None
    n += int(has_bias)
    seed_ref = refs[n] if rate > 0.0 else None
    n += int(rate > 0.0)
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[n:]
    i = pl.program_id(2)
    j = pl.program_id(3)
    # hoisted: pl.program_id is not available inside a pl.when body under
    # interpret mode
    bh = pl.program_id(0) * n_heads + pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            # additive per-key bias (broadcast over query rows): the
            # [B, 1, 1, Sk] padding-mask pattern of sdpa_mask_p
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
            if window is not None:
                s = jnp.where(rows + offset - cols < window, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows may be fully masked inside a partially-causal block; keep the
        # exp args finite so those rows stay exactly zero instead of NaN
        m_eff = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_eff)  # exp(-inf)=0 for first visit
        p = jnp.exp(s - m_eff)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            # softmax denominator (l) stays over the UNDROPPED weights;
            # only the value accumulation sees the mask (post-softmax
            # dropout semantics, matching the XLA oracle path)
            keep = _dropout_keep(seed_ref[0], bh, i, j, block_q, block_k,
                                 rate)
            p_use = p * keep * (1.0 / (1.0 - rate))
        else:
            p_use = p
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p_use, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal and window is not None:
        # skip blocks outside the band on either side
        first, last = _band_blocks(i, block_q, block_k, offset, window)

        @pl.when((j >= first) & (j <= last))
        def _():
            _compute()
    elif causal:
        # skip blocks strictly above the (offset) diagonal
        @pl.when(j * block_k <= i * block_q + (block_q - 1) + offset)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        m = m_scr[:, :1]
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        # lse is carried in a 128-lane layout ([..., Sq, LANES]) — TPU block
        # shapes need the last two dims (8, 128)-tileable, so a [B, H, Sq]
        # output with (1, 1, block_q) blocks is not expressible
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


# ---------------------------------------------------------------------------
# sharded programs: batch and head are independent, sequence and head_dim
# are not (see ops/kernel_partition.py)
# ---------------------------------------------------------------------------
def _run_flash(local, operands, results, arrays, *, partition, **statics):
    """Call one shard-level flash function: directly, or under shard_map
    when the program is sharded. ``operands``/``results`` name each
    array's layout (x = q-shaped, k = kv-shaped, l = lse-shaped); the
    optional [B|1, Sk] bias and [1] seed follow the operands."""
    local = functools.partial(local, **statics)
    if partition is None:
        return local(*arrays)
    q, k = arrays[0], arrays[1]
    b_ax = partition.axis_if_divides(partition.batch, q.shape[0])
    # heads split on kv-head boundaries: a shard holds whole GQA groups
    h_ax = partition.axis_if_divides(partition.heads, k.shape[1])
    specs = {"x": (b_ax, h_ax, None, None), "l": (b_ax, h_ax, None)}
    specs["k"] = specs["x"]
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


def _fwd_local(q, k, v, *extras, causal, scale, rate, has_bias, interpret,
               shard_axes=(), window=None):
    """One shard's forward: q [B,H,Sq,D]; k [B,Hkv,Sk,D]; v
    [B,Hkv,Sk,Dv] -> (out [B,H,Sq,Dv], lse [B,H,Sq]). The value head may
    be another size than the query-key head (a latent-attention prefill:
    192 and 128). ``window`` (with ``causal``): a query sees its last
    ``window`` keys only; key blocks outside that band are neither
    computed nor fetched (forward only)."""
    key_bias, seed = _split_extras(extras, has_bias, rate, shard_axes)
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    g = H // Hkv
    block_q = _pick_block(Sq)
    block_k = _pick_block(Sk)
    nq, nk = Sq // block_q, Sk // block_k
    kv_head = _kv_head_map(g)
    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk, offset=Sk - Sq,
        rate=rate, n_heads=H, has_bias=has_bias,
        **({} if window is None else {"window": int(window)}))
    if window is None:
        kv_map = lambda b, h, i, j: (b, kv_head(h), j, Z)
    else:
        if not causal or has_bias or rate > 0.0:
            raise ValueError("flash forward: a window needs causal=True "
                             "and takes neither key bias nor dropout")

        def kv_map(b, h, i, j):
            # a block outside the band is pinned to the nearest inside
            # it, which the step before or after holds: no DMA
            first, last = _band_blocks(i, block_q, block_k, Sk - Sq,
                                       int(window))
            return (b, kv_head(h), jnp.clip(j, first, last), Z)
    in_specs = [
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, Dv), kv_map),
    ]
    inputs = [q, k, v]
    if key_bias is not None:
        # [B, 1, Sk] with (1, 1, block_k) blocks: Mosaic wants the last
        # two block dims (8, 128)-divisible or equal to the array dims.
        # A batch-1 bias (mask shared across the batch) pins the index
        # map to row 0 instead of materializing B copies.
        bmap = ((lambda b, h, i, j: (Z, Z, j)) if key_bias.shape[0] == 1
                else (lambda b, h, i, j: (b, Z, j)))
        in_specs.append(pl.BlockSpec((1, 1, block_k), bmap))
        inputs.append(key_bias.reshape(key_bias.shape[0], 1,
                                       key_bias.shape[1]))
    if rate > 0.0:
        in_specs.append(pl.BlockSpec((1,), lambda b, h, i, j: (Z,),
                                  memory_space=pltpu.SMEM))
        inputs.append(seed)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, i, j: (b, h, i, Z)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, i, j: (b, h, i, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * Sq * Sk * (D + Dv),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=B * H * Sq * Sk,
        ),
        name="flash_fwd",
        interpret=interpret,
    )(*inputs)
    return out, lse[:, :, :, 0]


_JIT_STATICS = ("causal", "scale", "dropout_rate", "partition", "interpret")


@functools.partial(jax.jit, static_argnames=_JIT_STATICS + ("window",))
def _flash_fwd_jit(q, k, v, seed, key_bias, *, causal, scale, dropout_rate,
                   partition, interpret, window=None):
    extras = [x for x in (key_bias, seed) if x is not None]
    band = {} if window is None else {"window": window}
    return _run_flash(
        _fwd_local, "xkk", "xl", (q, k, v, *extras), partition=partition,
        causal=causal, scale=scale, rate=dropout_rate,
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
# backward
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


def _bwd_local(q, k, v, out, lse, do, *extras, causal, scale, rate,
               has_bias, interpret, shard_axes=()):
    """One shard's backward -> (dq, dk, dv)."""
    key_bias, seed = _split_extras(extras, has_bias, rate, shard_axes)
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    block_q = _pick_block(Sq)
    block_k = _pick_block(Sk)
    nq, nk = Sq // block_q, Sk // block_k
    kv_head = _kv_head_map(g)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # lane-pad lse/delta to [B, H, Sq, LANES] (see _fwd_kernel finalize)
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
    # dK/dV computed per q-head ([B,H,Sk,D]) then group-reduced to kv heads
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
    if g > 1:
        dk = dk_h.reshape(B, Hkv, g, Sk, D).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, Hkv, g, Sk, D).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


@functools.partial(jax.jit, static_argnames=_JIT_STATICS)
def _flash_bwd_jit(q, k, v, out, lse, do, seed, key_bias, *, causal, scale,
                   dropout_rate, partition, interpret):
    extras = [x for x in (key_bias, seed) if x is not None]
    return _run_flash(
        _bwd_local, "xkkxlx", "xkk", (q, k, v, out, lse, do, *extras),
        partition=partition, causal=causal, scale=scale, rate=dropout_rate,
        has_bias=key_bias is not None, interpret=interpret)


def _flash_bwd_bhsd(q, k, v, out, lse, do, seed=None, key_bias=None, *,
                    causal, scale, dropout_rate=0.0, partition=None):
    return _flash_bwd_jit(q, k, v, out, lse, do, seed, key_bias,
                          causal=causal, scale=scale,
                          dropout_rate=dropout_rate, partition=partition,
                          interpret=_interpret())


# ---------------------------------------------------------------------------
# array-level API (paddle [B, S, H, D] layout) + primitive registration
# ---------------------------------------------------------------------------
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
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out, lse = _flash_fwd_bhsd(qt, kt, vt, seed, key_bias, causal=causal,
                               scale=float(scale),
                               dropout_rate=float(dropout_rate),
                               partition=partition)
    return jnp.swapaxes(out, 1, 2), lse


def _flash_vjp(grads_out, saved, *, causal, scale, dropout_rate=0.0,
               has_bias=False, partition=None):
    *ins, out, lse = saved
    q, k, v = ins[:3]
    rest = list(ins[3:])
    key_bias = rest.pop(0) if has_bias else None
    seed = rest.pop(0) if dropout_rate > 0.0 else None
    do = grads_out[0]
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    ot, dot = jnp.swapaxes(out, 1, 2), jnp.swapaxes(do, 1, 2)
    dq, dk, dv = _flash_bwd_bhsd(qt, kt, vt, ot, lse, dot, seed, key_bias,
                                 causal=causal, scale=float(scale),
                                 dropout_rate=float(dropout_rate),
                                 partition=partition)
    grads = (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
             jnp.swapaxes(dv, 1, 2))
    # optional inputs (bias, seed) take no grads: the bias is a mask
    grads = grads + (None,) * (len(ins) - 3)
    return grads


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
