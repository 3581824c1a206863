"""Concrete optimizers: SGD, Momentum, Adam, AdamW, RMSProp, Adagrad, Adadelta,
Adamax, Lamb.

Reference: python/paddle/optimizer/{sgd,momentum,adam,adamw,rmsprop,...}.py →
phi optimizer kernels (sgd_kernel, adam_kernel, adamw_kernel with
multi_precision master weights). Updates are pure jax fns, jitted per shape.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter
from .optimizer import Optimizer


@functools.lru_cache(maxsize=None)
def _jit(fn):
    return jax.jit(fn)


# ---------------------------------------------------------------------------
@jax.jit
def _sgd_update(p, g, lr):
    return p - lr * g.astype(p.dtype)


class SGD(Optimizer):
    _accum_names = ()
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def _update_param(self, p, grad, lr):
        master = self._master(p)
        if master is not None:
            new_master = _sgd_update(master, grad.astype(jnp.float32), jnp.asarray(lr, jnp.float32))
            self._apply(p, None, new_master)
        else:
            self._apply(p, _sgd_update(p._value, grad, jnp.asarray(lr, p._value.dtype)))


@jax.jit
def _momentum_update(p, g, vel, lr, mu, use_nesterov):
    g = g.astype(vel.dtype)
    vel_new = mu * vel + g
    upd = jnp.where(use_nesterov, g + mu * vel_new, vel_new)
    return (p.astype(vel.dtype) - lr * upd).astype(p.dtype), vel_new


class Momentum(Optimizer):
    _accum_names = ("velocity",)
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _update_param(self, p, grad, lr):
        vel = self._accum("velocity", p)
        master = self._master(p)
        base = master if master is not None else p._value
        new_p, new_vel = _momentum_update(
            base, grad, vel, jnp.asarray(lr, jnp.float32), jnp.float32(self._momentum),
            jnp.bool_(self._use_nesterov),
        )
        self._set_accum("velocity", p, new_vel)
        if master is not None:
            self._apply(p, None, new_p.astype(jnp.float32))
        else:
            self._apply(p, new_p)


@jax.jit
def _adam_update(p32, g, m, v, lr, beta1, beta2, eps, t):
    g32 = g.astype(jnp.float32)
    m_new = beta1 * m + (1 - beta1) * g32
    v_new = beta2 * v + (1 - beta2) * g32 * g32
    mhat = m_new / (1 - jnp.power(beta1, t))
    vhat = v_new / (1 - jnp.power(beta2, t))
    p_new = p32 - lr * mhat / (jnp.sqrt(vhat) + eps)
    return p_new, m_new, v_new


class Adam(Optimizer):
    _accum_names = ("moment1", "moment2")
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _update_param(self, p, grad, lr):
        m = self._accum("moment1", p)
        v = self._accum("moment2", p)
        master = self._master(p)
        p32 = master if master is not None else p._value.astype(jnp.float32)
        t = self._step_num()
        p_new, m_new, v_new = _adam_update(
            p32, grad, m, v, jnp.asarray(lr, jnp.float32), jnp.float32(self._beta1),
            jnp.float32(self._beta2), jnp.float32(self._epsilon), t,
        )
        self._set_accum("moment1", p, m_new)
        self._set_accum("moment2", p, v_new)
        if master is not None:
            self._apply(p, None, p_new)
        else:
            self._apply(p, p_new.astype(p._value.dtype))


@jax.jit
def _adamw_update(p32, g, m, v, lr, beta1, beta2, eps, t, wd):
    g32 = g.astype(jnp.float32)
    # decoupled weight decay (adamw.py:493 semantics: p *= (1 - lr*coeff))
    p32 = p32 * (1.0 - lr * wd)
    m_new = beta1 * m + (1 - beta1) * g32
    v_new = beta2 * v + (1 - beta2) * g32 * g32
    mhat = m_new / (1 - jnp.power(beta1, t))
    vhat = v_new / (1 - jnp.power(beta2, t))
    p_new = p32 - lr * mhat / (jnp.sqrt(vhat) + eps)
    return p_new, m_new, v_new


class AdamW(Optimizer):
    _accum_names = ("moment1", "moment2")

    """Decoupled weight decay Adam (reference: optimizer/adamw.py — decay
    applied directly to params, excluded via apply_decay_param_fun)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._weight_decay = float(weight_decay)
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _update_param(self, p, grad, lr):
        m = self._accum("moment1", p)
        v = self._accum("moment2", p)
        master = self._master(p)
        p32 = master if master is not None else p._value.astype(jnp.float32)
        wd = self._weight_decay
        if self._apply_decay_param_fun is not None and not self._apply_decay_param_fun(
            p.name
        ):
            wd = 0.0
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        t = self._step_num()
        p_new, m_new, v_new = _adamw_update(
            p32, grad, m, v, jnp.asarray(lr, jnp.float32), jnp.float32(self._beta1),
            jnp.float32(self._beta2), jnp.float32(self._epsilon), t,
            jnp.float32(wd),
        )
        self._set_accum("moment1", p, m_new)
        self._set_accum("moment2", p, v_new)
        if master is not None:
            self._apply(p, None, p_new)
        else:
            self._apply(p, p_new.astype(p._value.dtype))


@jax.jit
def _rmsprop_update(p32, g, mean_sq, mom, lr, rho, eps, momentum, centered, mean_g):
    g32 = g.astype(jnp.float32)
    ms_new = rho * mean_sq + (1 - rho) * g32 * g32
    mg_new = jnp.where(centered, rho * mean_g + (1 - rho) * g32, mean_g)
    denom = jnp.sqrt(ms_new - jnp.where(centered, mg_new * mg_new, 0.0) + eps)
    mom_new = momentum * mom + lr * g32 / denom
    return p32 - mom_new, ms_new, mom_new, mg_new


class RMSProp(Optimizer):
    _accum_names = ("mean_square", "momentum", "mean_grad")
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _update_param(self, p, grad, lr):
        ms = self._accum("mean_square", p)
        mom = self._accum("momentum", p)
        mg = self._accum("mean_grad", p)
        master = self._master(p)
        p32 = master if master is not None else p._value.astype(jnp.float32)
        p_new, ms_new, mom_new, mg_new = _rmsprop_update(
            p32, grad, ms, mom, jnp.asarray(lr, jnp.float32), jnp.float32(self._rho),
            jnp.float32(self._epsilon), jnp.float32(self._momentum),
            jnp.bool_(self._centered), mg,
        )
        self._set_accum("mean_square", p, ms_new)
        self._set_accum("momentum", p, mom_new)
        self._set_accum("mean_grad", p, mg_new)
        if master is not None:
            self._apply(p, None, p_new)
        else:
            self._apply(p, p_new.astype(p._value.dtype))


@jax.jit
def _adagrad_update(p32, g, moment, lr, eps):
    g32 = g.astype(jnp.float32)
    m_new = moment + g32 * g32
    return p32 - lr * g32 / (jnp.sqrt(m_new) + eps), m_new


class Adagrad(Optimizer):
    _accum_names = ("moment",)
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _update_param(self, p, grad, lr):
        m = self._accum(
            "moment", p,
            init=jnp.full_like(p._value, self._init_acc, dtype=jnp.float32),
        )
        master = self._master(p)
        p32 = master if master is not None else p._value.astype(jnp.float32)
        p_new, m_new = _adagrad_update(
            p32, grad, m, jnp.asarray(lr, jnp.float32), jnp.float32(self._epsilon)
        )
        self._set_accum("moment", p, m_new)
        if master is not None:
            self._apply(p, None, p_new)
        else:
            self._apply(p, p_new.astype(p._value.dtype))


@jax.jit
def _adadelta_update(p32, g, avg_sq_g, avg_sq_u, lr, rho, eps):
    g32 = g.astype(jnp.float32)
    avg_sq_g_new = rho * avg_sq_g + (1 - rho) * g32 * g32
    upd = jnp.sqrt(avg_sq_u + eps) / jnp.sqrt(avg_sq_g_new + eps) * g32
    avg_sq_u_new = rho * avg_sq_u + (1 - rho) * upd * upd
    return p32 - lr * upd, avg_sq_g_new, avg_sq_u_new


class Adadelta(Optimizer):
    _accum_names = ("avg_squared_grad", "avg_squared_update")
    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._rho = rho

    def _update_param(self, p, grad, lr):
        g1 = self._accum("avg_squared_grad", p)
        g2 = self._accum("avg_squared_update", p)
        master = self._master(p)
        p32 = master if master is not None else p._value.astype(jnp.float32)
        p_new, g1n, g2n = _adadelta_update(
            p32, grad, g1, g2, jnp.asarray(lr, jnp.float32), jnp.float32(self._rho),
            jnp.float32(self._epsilon),
        )
        self._set_accum("avg_squared_grad", p, g1n)
        self._set_accum("avg_squared_update", p, g2n)
        if master is not None:
            self._apply(p, None, p_new)
        else:
            self._apply(p, p_new.astype(p._value.dtype))


@jax.jit
def _adamax_update(p32, g, m, inf_norm, lr, beta1, beta2, eps, t):
    g32 = g.astype(jnp.float32)
    m_new = beta1 * m + (1 - beta1) * g32
    inf_new = jnp.maximum(beta2 * inf_norm, jnp.abs(g32))
    p_new = p32 - lr / (1 - jnp.power(beta1, t)) * m_new / (inf_new + eps)
    return p_new, m_new, inf_new


class Adamax(Optimizer):
    _accum_names = ("moment", "inf_norm")
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update_param(self, p, grad, lr):
        m = self._accum("moment", p)
        inf = self._accum("inf_norm", p)
        master = self._master(p)
        p32 = master if master is not None else p._value.astype(jnp.float32)
        t = self._step_num()
        p_new, m_new, inf_new = _adamax_update(
            p32, grad, m, inf, jnp.asarray(lr, jnp.float32), jnp.float32(self._beta1),
            jnp.float32(self._beta2), jnp.float32(self._epsilon), t,
        )
        self._set_accum("moment", p, m_new)
        self._set_accum("inf_norm", p, inf_new)
        if master is not None:
            self._apply(p, None, p_new)
        else:
            self._apply(p, p_new.astype(p._value.dtype))


@jax.jit
def _lamb_update(p32, g, m, v, lr, beta1, beta2, eps, t, wd):
    g32 = g.astype(jnp.float32)
    m_new = beta1 * m + (1 - beta1) * g32
    v_new = beta2 * v + (1 - beta2) * g32 * g32
    mhat = m_new / (1 - jnp.power(beta1, t))
    vhat = v_new / (1 - jnp.power(beta2, t))
    r = mhat / (jnp.sqrt(vhat) + eps) + wd * p32
    w_norm = jnp.linalg.norm(p32)
    r_norm = jnp.linalg.norm(r)
    ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
    return p32 - lr * ratio * r, m_new, v_new


class Lamb(Optimizer):
    _accum_names = ("moment1", "moment2")
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-06, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _update_param(self, p, grad, lr):
        m = self._accum("moment1", p)
        v = self._accum("moment2", p)
        master = self._master(p)
        p32 = master if master is not None else p._value.astype(jnp.float32)
        wd = self._wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        t = self._step_num()
        p_new, m_new, v_new = _lamb_update(
            p32, grad, m, v, jnp.asarray(lr, jnp.float32), jnp.float32(self._beta1),
            jnp.float32(self._beta2), jnp.float32(self._epsilon), t,
            jnp.float32(wd),
        )
        self._set_accum("moment1", p, m_new)
        self._set_accum("moment2", p, v_new)
        if master is not None:
            self._apply(p, None, p_new)
        else:
            self._apply(p, p_new.astype(p._value.dtype))
