"""Optimizer surface completion.

Reference: python/paddle/optimizer/ — asgd.py (ASGD with the d/y running
averages), radam.py (RAdam rectified moment schedule), rprop.py (sign-based
step adaptation), nadam.py (Nesterov Adam with mu-product schedule); LBFGS
re-exported from incubate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .optimizer import Optimizer

__all__ = ["ASGD", "RAdam", "Rprop", "NAdam"]


class ASGD(Optimizer):
    """Reference: optimizer/asgd.py — averaged SGD. Keeps a window of n
    historical gradients (n=batch_num) as an accumulator [n, *shape] so the
    whole state lifts to functional form under jit capture; update uses
    d = d - y_old + g and the running mean d/n. The rolling write position
    is derived from the shared step counter (same for every param)."""

    _accum_names = ("d", "grad_window")

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        if batch_num <= 0:
            raise ValueError("batch_num must be positive")
        self._n = int(batch_num)

    def _ensure_accumulators(self):
        # grad_window is [n, *shape]; the base pre-creation would make it
        # p-shaped zeros, so create both accumulators with their real inits
        for p in self._parameter_list:
            if not getattr(p, "trainable", True):
                continue
            self._accum("d", p)
            self._accum("grad_window", p, init=jnp.zeros(
                (self._n,) + tuple(p._value.shape), jnp.float32))
            self._master(p)

    def _update_param(self, p, grad, lr):
        master = self._master(p)
        pv = (master if master is not None else p._value).astype(jnp.float32)
        g = grad.astype(jnp.float32)
        d = self._accum("d", p)
        window = self._accum(
            "grad_window", p,
            init=jnp.zeros((self._n,) + tuple(p._value.shape), jnp.float32))
        pos = jnp.mod(self._step_num().astype(jnp.int32) - 1, self._n)
        y_old = jax.lax.dynamic_index_in_dim(window, pos, 0, keepdims=False)
        d = d - y_old + g
        window = jax.lax.dynamic_update_index_in_dim(window, g, pos, 0)
        self._set_accum("d", p, d)
        self._set_accum("grad_window", p, window)
        new = pv - lr * d / self._n
        if master is not None:
            self._apply(p, None, new)
        else:
            self._apply(p, new.astype(p._value.dtype))


class RAdam(Optimizer):
    """Reference: optimizer/radam.py — rectified Adam (Liu et al. 2020)."""

    _accum_names = ("moment1", "moment2")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _update_param(self, p, grad, lr):
        master = self._master(p)
        pv = (master if master is not None else p._value).astype(jnp.float32)
        g = grad.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        t = self._step_num()
        m = self._accum("moment1", p)
        v = self._accum("moment2", p)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        self._set_accum("moment1", p, m)
        self._set_accum("moment2", p, v)
        m_hat = m / (1 - b1 ** t)
        rho_inf = 2.0 / (1 - b2) - 1.0
        rho_t = rho_inf - 2.0 * t * (b2 ** t) / (1 - b2 ** t)
        tractable = rho_t > 5.0
        r = jnp.sqrt(jnp.maximum(
            ((rho_t - 4) * (rho_t - 2) * rho_inf)
            / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t, 1e-12),
            0.0))
        v_hat = jnp.sqrt(v / (1 - b2 ** t)) + self._eps
        step_adapt = jnp.where(tractable, r * m_hat / v_hat, m_hat)
        new = pv - lr * step_adapt
        if master is not None:
            self._apply(p, None, new)
        else:
            self._apply(p, new.astype(p._value.dtype))


class Rprop(Optimizer):
    """Reference: optimizer/rprop.py — resilient backprop: per-weight step
    size grows when successive gradient signs agree, shrinks on sign flip
    (batch-mode only)."""

    _accum_names = ("prev_grad", "learning_rate_step")

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas
        self._init_lr = learning_rate

    def _update_param(self, p, grad, lr):
        master = self._master(p)
        pv = (master if master is not None else p._value).astype(jnp.float32)
        g = grad.astype(jnp.float32)
        prev = self._accum("prev_grad", p)
        steps = self._accum("learning_rate_step", p)
        steps = jnp.where(steps == 0.0, self._init_lr, steps)
        sign = jnp.sign(prev * g)
        steps = jnp.clip(
            jnp.where(sign > 0, steps * self._eta_pos,
                      jnp.where(sign < 0, steps * self._eta_neg, steps)),
            self._lr_min, self._lr_max)
        # on sign flip the gradient is zeroed (no step) like the reference
        g_eff = jnp.where(sign < 0, 0.0, g)
        self._set_accum("prev_grad", p, g_eff)
        self._set_accum("learning_rate_step", p, steps)
        new = pv - steps * jnp.sign(g_eff)
        if master is not None:
            self._apply(p, None, new)
        else:
            self._apply(p, new.astype(p._value.dtype))


class NAdam(Optimizer):
    """Reference: optimizer/nadam.py — Adam with Nesterov momentum
    (mu-product schedule, Dozat 2016)."""

    _accum_names = ("moment1", "moment2", "mu_product")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._psi = momentum_decay

    def _ensure_accumulators(self):
        for p in self._parameter_list:
            if not getattr(p, "trainable", True):
                continue
            self._accum("moment1", p)
            self._accum("moment2", p)
            self._accum("mu_product", p,
                        init=jnp.ones_like(p._value, dtype=jnp.float32))
            self._master(p)

    def _update_param(self, p, grad, lr):
        master = self._master(p)
        pv = (master if master is not None else p._value).astype(jnp.float32)
        g = grad.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        t = self._step_num()
        mu_t = b1 * (1.0 - 0.5 * 0.96 ** (t * self._psi))
        mu_t1 = b1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        # seeded to ones at creation; never use 0 as an init sentinel (the
        # product legitimately underflows toward 0 late in training)
        mu_prod_prev = self._accum(
            "mu_product", p,
            init=jnp.ones_like(p._value, dtype=jnp.float32))
        mu_prod = mu_prod_prev * mu_t
        self._set_accum("mu_product", p, mu_prod)
        m = self._accum("moment1", p)
        v = self._accum("moment2", p)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        self._set_accum("moment1", p, m)
        self._set_accum("moment2", p, v)
        m_hat = mu_t1 * m / (1 - mu_prod * mu_t1) + \
            (1 - mu_t) * g / (1 - mu_prod)
        v_hat = v / (1 - b2 ** t)
        new = pv - lr * m_hat / (jnp.sqrt(v_hat) + self._eps)
        if master is not None:
            self._apply(p, None, new)
        else:
            self._apply(p, new.astype(p._value.dtype))
