"""Normalization functional ops.

Reference: python/paddle/nn/functional/norm.py over phi layer_norm /
batch_norm / group_norm kernels; rms_norm parity with
incubate.nn.functional.fused_rms_norm. All forms reduce in float32 and cast
back (bf16-safe on TPU), matching the reference kernels' accumulation dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.tensor import Tensor, apply
from ...ops._helpers import defprim, ensure_tensor

__all__ = [
    "layer_norm", "batch_norm", "instance_norm", "group_norm", "rms_norm",
    "normalize", "local_response_norm",
]


def _layer_norm_fwd(x, w, b, *, begin_axis, eps):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    axes = tuple(range(begin_axis, x.ndim))
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    shape = [1] * begin_axis + list(x.shape[begin_axis:])
    y = y * w.astype(jnp.float32).reshape(shape) + b.astype(jnp.float32).reshape(shape)
    return y.astype(dtype)


defprim("layer_norm_p", _layer_norm_fwd)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    x = ensure_tensor(x)
    if isinstance(normalized_shape, (int, np.integer)):
        normalized_shape = [int(normalized_shape)]
    begin = x.ndim - len(normalized_shape)
    from ...ops.creation import ones, zeros

    w = ensure_tensor(weight) if weight is not None else ones(normalized_shape, x.dtype)
    b = ensure_tensor(bias) if bias is not None else zeros(normalized_shape, x.dtype)
    return apply("layer_norm_p", x, w, b, begin_axis=begin, eps=float(epsilon))


def _rms_norm_fwd(x, w, *, eps):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)
    return y.astype(dtype)


defprim("rms_norm_p", _rms_norm_fwd)


def _use_pallas_rms(x) -> bool:
    # the gate lives here so the XLA path never imports the pallas stack
    from ...core.flags import get_flag, pallas_mode

    if not get_flag("use_pallas_rms_norm") or pallas_mode() == "off":
        return False
    hidden = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    return hidden % 128 == 0 and rows % 8 == 0


def rms_norm(x, weight, epsilon=1e-6, name=None, *, partition=None):
    """RMSNorm (reference: paddle.incubate.nn.functional.fused_rms_norm,
    phi/kernels/gpu/rms_norm_kernel.cu). Pallas fused kernel on TPU when the
    hidden dim is lane-aligned; XLA composition otherwise. ``partition`` is
    the ``KernelPartition`` a sharded model's shard plan recorded (the
    kernel then runs per shard; XLA partitions the composition itself)."""
    x = ensure_tensor(x)
    w = ensure_tensor(weight)
    if _use_pallas_rms(x):
        from ...ops.pallas import rms_norm as _  # registers the primitive

        statics = {} if partition is None else {"partition": partition}
        return apply("rms_norm_pallas_p", x, w, eps=float(epsilon),
                     **statics)
    return apply("rms_norm_p", x, w, eps=float(epsilon))


def _batch_norm_train_fwd(x, w, b, *, eps, ch_axis):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    y = (xf - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + eps)
    y = y * w.astype(jnp.float32).reshape(shape) + b.astype(jnp.float32).reshape(shape)
    return y.astype(dtype), mean, var


defprim("batch_norm_train_p", _batch_norm_train_fwd, multi_out=True)


def _batch_norm_infer_fwd(x, w, b, rm, rv, *, eps, ch_axis):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    y = (xf - rm.astype(jnp.float32).reshape(shape)) * jax.lax.rsqrt(
        rv.astype(jnp.float32).reshape(shape) + eps
    )
    y = y * w.astype(jnp.float32).reshape(shape) + b.astype(jnp.float32).reshape(shape)
    return y.astype(dtype)


defprim("batch_norm_infer_p", _batch_norm_infer_fwd)


def batch_norm(x, running_mean, running_var, weight, bias, training=False,
               momentum=0.9, epsilon=1e-05, data_format="NCHW", use_global_stats=None,
               name=None):
    """Functional batch_norm; updates running stats in-place when training
    (reference: nn/functional/norm.py batch_norm → phi batch_norm kernel
    which outputs new mean/var)."""
    x = ensure_tensor(x)
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    if x.ndim == 2:
        ch_axis = 1
    use_stats = use_global_stats if use_global_stats is not None else not training
    w, b = ensure_tensor(weight), ensure_tensor(bias)
    if use_stats:
        return apply(
            "batch_norm_infer_p", x, w, b, ensure_tensor(running_mean),
            ensure_tensor(running_var), eps=float(epsilon), ch_axis=ch_axis,
        )
    y, batch_mean, batch_var = apply(
        "batch_norm_train_p", x, w, b, eps=float(epsilon), ch_axis=ch_axis
    )
    # running-stat update (no grad)
    rm, rv = ensure_tensor(running_mean), ensure_tensor(running_var)
    m = float(momentum)
    n = x.size // x.shape[ch_axis]
    unbias = n / max(n - 1, 1)
    rm._replace_value(
        (rm._value.astype(jnp.float32) * m + batch_mean._value * (1 - m)).astype(rm._value.dtype)
    )
    rv._replace_value(
        (rv._value.astype(jnp.float32) * m + batch_var._value * unbias * (1 - m)).astype(
            rv._value.dtype
        )
    )
    return y


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    x = ensure_tensor(x)
    from ...ops.creation import ones, zeros

    c = x.shape[1] if data_format.startswith("NC") else x.shape[-1]
    w = ensure_tensor(weight) if weight is not None else ones([c], x.dtype)
    b = ensure_tensor(bias) if bias is not None else zeros([c], x.dtype)
    return apply(
        "instance_norm_p", x, w, b, eps=float(eps),
        channels_first=data_format.startswith("NC"),
    )


def _instance_norm_fwd(x, w, b, *, eps, channels_first):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if channels_first:
        axes = tuple(range(2, x.ndim))
        shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    else:
        axes = tuple(range(1, x.ndim - 1))
        shape = [1] * (x.ndim - 1) + [x.shape[-1]]
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * w.astype(jnp.float32).reshape(shape) + b.astype(jnp.float32).reshape(shape)
    return y.astype(dtype)


defprim("instance_norm_p", _instance_norm_fwd)


def _group_norm_fwd(x, w, b, *, groups, eps, channels_first):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if channels_first:
        c_ax = 1
    else:
        c_ax = x.ndim - 1
        xf = jnp.moveaxis(xf, -1, 1)
    n, c = xf.shape[0], xf.shape[1]
    rest = xf.shape[2:]
    g = xf.reshape(n, groups, c // groups, *rest)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    y = ((g - mean) * jax.lax.rsqrt(var + eps)).reshape(n, c, *rest)
    shape = [1, c] + [1] * len(rest)
    y = y * w.astype(jnp.float32).reshape(shape) + b.astype(jnp.float32).reshape(shape)
    if not channels_first:
        y = jnp.moveaxis(y, 1, -1)
    return y.astype(dtype)


defprim("group_norm_p", _group_norm_fwd)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channels_first = data_format.startswith("NC")
    c = x.shape[1] if channels_first else x.shape[-1]
    from ...ops.creation import ones, zeros

    w = ensure_tensor(weight) if weight is not None else ones([c], x.dtype)
    b = ensure_tensor(bias) if bias is not None else zeros([c], x.dtype)
    return apply(
        "group_norm_p", x, w, b, groups=int(num_groups), eps=float(epsilon),
        channels_first=channels_first,
    )


defprim(
    "l2_normalize_p",
    lambda x, *, axis, eps, p: x
    / jnp.maximum(
        jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True), 1.0 / p),
        eps,
    ),
)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    x = ensure_tensor(x)
    return apply(
        "l2_normalize_p", x, axis=int(axis) % x.ndim, eps=float(epsilon), p=float(p)
    )


def _lrn_fwd(x, *, size, alpha, beta, k, channels_first):
    ch_axis = 1 if channels_first else x.ndim - 1
    sq = jnp.square(x)
    c = x.shape[ch_axis]
    half = size // 2
    pads = [(0, 0)] * x.ndim
    pads[ch_axis] = (half, size - half - 1)
    sq = jnp.pad(sq, pads)
    windows = [1] * x.ndim
    windows[ch_axis] = size
    s = jax.lax.reduce_window(sq, 0.0, jax.lax.add, tuple(windows), (1,) * x.ndim, "VALID")
    return x / jnp.power(k + alpha * s, beta)


defprim("lrn_p", _lrn_fwd)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    return apply(
        "lrn_p", ensure_tensor(x), size=int(size), alpha=float(alpha),
        beta=float(beta), k=float(k), channels_first=data_format.startswith("NC"),
    )
