"""Pallas fused RMSNorm (forward + backward) for TPU.

TPU-native analog of the reference fused kernel
(reference: phi/kernels/gpu/rms_norm_kernel.cu, surfaced as
paddle.incubate.nn.functional.fused_rms_norm). One pass per row block:
fp32 mean-of-squares on the VPU, scaled write-back. Backward recomputes the
inverse RMS from the saved input (cheaper than storing a residual) and
accumulates the weight gradient across row blocks in VMEM scratch — the grid
is sequential on TPU so the accumulator carries without atomics.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import dispatch
from ..kernel_partition import shard_kernel
from .flash_attention import Z, _interpret, _pick_block


def _fwd_kernel(x_ref, w_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    invr = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y_ref[:] = (x * invr * w[None, :]).astype(y_ref.dtype)


def _bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dw_ref, dw_scr, *, eps, nr):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        dw_scr[:] = jnp.zeros(dw_scr.shape, jnp.float32)

    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    invr = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    gw = g * w[None, :]
    c = jnp.mean(gw * x, axis=-1, keepdims=True) * invr * invr * invr
    dx_ref[:] = (gw * invr - x * c).astype(dx_ref.dtype)
    dw_scr[:] += jnp.sum(g * x * invr, axis=0)

    @pl.when(r == nr - 1)
    def _finalize():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


def _fwd_local(x, w, *, eps, interpret):
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    rows = x2.shape[0]
    block_r = _pick_block(rows, 256)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(rows // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, hidden), lambda r: (r, Z)),
            pl.BlockSpec((hidden,), lambda r: (Z,)),
        ],
        out_specs=pl.BlockSpec((block_r, hidden), lambda r: (r, Z)),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="rms_norm_fwd",
        interpret=interpret,
    )(x2, w)
    return y.reshape(x.shape)


def _bwd_local(x, w, g, *, eps, interpret, row_axes=()):
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    g2 = g.reshape(-1, hidden)
    rows = x2.shape[0]
    block_r = _pick_block(rows, 256)
    nr = rows // block_r
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, nr=nr),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_r, hidden), lambda r: (r, Z)),
            pl.BlockSpec((hidden,), lambda r: (Z,)),
            pl.BlockSpec((block_r, hidden), lambda r: (r, Z)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, hidden), lambda r: (r, Z)),
            pl.BlockSpec((hidden,), lambda r: (Z,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, hidden), x.dtype),
            jax.ShapeDtypeStruct((hidden,), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hidden,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="rms_norm_bwd",
        interpret=interpret,
    )(x2, w, g2)
    if row_axes:
        # each shard reduced its own rows; the weight gradient is the sum
        dw = jax.lax.psum(dw, row_axes)
    return dx.reshape(x.shape), dw.astype(w.dtype)


def _row_axis(x, partition):
    """Rows are independent, the feature axis is not: a shard keeps the
    batch axis of x — unless that leaves it a row count the (8, 128)
    tiling cannot block, in which case x is made whole."""
    ax = partition.axis_if_divides(partition.batch, x.shape[0])
    if ax is None or x.ndim < 2:
        return None
    rows = math.prod(x.shape[:-1]) // partition.mesh.get_dim_size(ax)
    return ax if rows % 8 == 0 else None


@functools.partial(jax.jit, static_argnames=("eps", "partition", "interpret"))
def _rms_fwd_jit(x, w, *, eps, partition, interpret):
    local = functools.partial(_fwd_local, eps=eps, interpret=interpret)
    if partition is None:
        return local(x, w)
    xs = (_row_axis(x, partition),) + (None,) * (x.ndim - 1)
    return shard_kernel(local, partition, [xs, (None,)], xs)(x, w)


@functools.partial(jax.jit, static_argnames=("eps", "partition", "interpret"))
def _rms_bwd_jit(x, w, g, *, eps, partition, interpret):
    local = functools.partial(_bwd_local, eps=eps, interpret=interpret)
    if partition is None:
        return local(x, w, g)
    ax = _row_axis(x, partition)
    xs = (ax,) + (None,) * (x.ndim - 1)
    local = functools.partial(local, row_axes=() if ax is None else (ax,))
    return shard_kernel(local, partition, [xs, (None,), xs],
                        [xs, (None,)])(x, w, g)


def _rms_fwd(x, w, *, eps, partition=None):
    return _rms_fwd_jit(x, w, eps=eps, partition=partition,
                        interpret=_interpret())


def _rms_bwd(x, w, g, *, eps, partition=None):
    return _rms_bwd_jit(x, w, g, eps=eps, partition=partition,
                        interpret=_interpret())


def _vjp(grads_out, saved, *, eps, partition=None):
    x, w = saved
    return _rms_bwd(x, w, grads_out[0], eps=eps, partition=partition)


dispatch.register_primitive(
    "rms_norm_pallas_p",
    _rms_fwd,
    vjp=_vjp,
    save=lambda arrays, outs: arrays,
    jittable=False,  # jitted internally
)


# NOTE: the dispatch gate lives in nn/functional/norm.py (_use_pallas_rms)
# so the XLA fallback path never imports this module.
