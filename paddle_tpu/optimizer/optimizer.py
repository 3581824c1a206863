"""Optimizer base.

Reference: python/paddle/optimizer/optimizer.py (Optimizer — accumulators,
lr scheduling, grad clip, regularization, master weights for low-precision
params per adamw.py:493 multi_precision semantics).

TPU design: each parameter update is a pure jax function over
(param, grad, accumulators, hyperparams) jitted once per dtype/shape — the
multi-tensor-apply analog. Low-precision (bf16/fp16) params keep a float32
master copy when multi_precision=True.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Parameter, Tensor
from ..autograd import no_grad


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        from . import lr as lr_mod

        if parameters is None:
            raise ValueError(
                "parameters must be provided (dygraph-style optimizer)"
            )
        self._parameter_list = list(parameters)
        self._param_groups: List[Dict[str, Any]] = []
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            groups = self._parameter_list
            self._parameter_list = []
            for g in groups:
                ps = list(g["params"])
                self._parameter_list.extend(ps)
                self._param_groups.append({**g, "params": ps})
        else:
            self._param_groups.append({"params": self._parameter_list})

        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        if isinstance(weight_decay, float) or isinstance(weight_decay, int):
            from ..regularizer import L2Decay

            self.regularization = L2Decay(float(weight_decay))
        else:
            self.regularization = weight_decay
        self._multi_precision = multi_precision
        self._accumulators: Dict[str, Dict[int, jax.Array]] = defaultdict(dict)
        self._master_weights: Dict[int, jax.Array] = {}
        self._step_count = 0
        # jit.to_static trace overrides: traced scalars standing in for the
        # python-side lr / step counter so compiled steps don't bake them in.
        self._lr_override = None
        self._step_override = None

    # ------------------------------------------------------------------
    def get_lr(self):
        from . import lr as lr_mod

        if self._lr_override is not None:
            return self._lr_override
        if isinstance(self._learning_rate, lr_mod.LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def _step_num(self):
        """1-based step index for bias correction (traced under capture)."""
        if self._step_override is not None:
            return self._step_override
        return jnp.float32(self._step_count + 1)

    def set_lr(self, value: float):
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # ------------------------------------------------------------------
    def _accum(self, name: str, p: Parameter, init=None):
        store = self._accumulators[name]
        if id(p) not in store:
            # zeros_like, not zeros(shape): the accumulator takes its
            # parameter's sharding, so the state of a sharded parameter
            # is never whole on one device
            store[id(p)] = (
                jnp.zeros_like(p._value, dtype=jnp.float32)
                if init is None else init
            )
        return store[id(p)]

    def _set_accum(self, name: str, p: Parameter, value):
        self._accumulators[name][id(p)] = value

    # accumulator names per optimizer class (used by jit state lifting)
    _accum_names: tuple = ()

    def _ensure_accumulators(self):
        """Pre-create all accumulators/master weights so jit.to_static can
        lift them to functional state before the first step() runs."""
        for p in self._parameter_list:
            if not getattr(p, "trainable", True):
                continue
            for name in self._accum_names:
                self._accum(name, p)
            self._master(p)

    def _master(self, p: Parameter):
        if not self._multi_precision or p._value.dtype == jnp.float32:
            return None
        if id(p) not in self._master_weights:
            self._master_weights[id(p)] = p._value.astype(jnp.float32)
        return self._master_weights[id(p)]

    # ------------------------------------------------------------------
    def _params_grads(self):
        pg = []
        for p in self._parameter_list:
            if not p.trainable:
                continue
            g = None
            if p._grad_value is not None:
                g = Tensor._from_value(p._grad_value)
            pg.append((p, g))
        return pg

    @no_grad()
    @jax.named_scope("optimizer")   # sets the update apart from the layers
    def step(self):
        params_grads = self._params_grads()
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        lr = self.get_lr()
        for p, g in params_grads:
            if g is None:
                continue
            gv = g._value
            if self.regularization is not None and getattr(p, "regularizer", None) is None:
                gv = self.regularization._apply(p._value, gv)
            elif getattr(p, "regularizer", None) is not None:
                gv = p.regularizer._apply(p._value, gv)
            plr = lr * p.optimize_attr.get("learning_rate", 1.0)
            self._update_param(p, gv, plr)
        self._step_count += 1

    minimize_step = step

    def _update_param(self, p: Parameter, grad, lr: float):
        raise NotImplementedError

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    @no_grad()
    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        self.step()
        return None, None

    # ------------------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        sd: Dict[str, Any] = {}
        id2name = {id(p): (p.name or f"param_{i}") for i, p in enumerate(self._parameter_list)}
        for accum_name, store in self._accumulators.items():
            for pid, arr in store.items():
                sd[f"{id2name.get(pid, pid)}__{accum_name}"] = Tensor._from_value(arr)
        for pid, arr in self._master_weights.items():
            sd[f"{id2name.get(pid, pid)}__master"] = Tensor._from_value(arr)
        from . import lr as lr_mod

        if isinstance(self._learning_rate, lr_mod.LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        sd["__step__"] = self._step_count
        return sd

    def set_state_dict(self, state_dict: Dict[str, Any]):
        from . import lr as lr_mod

        name2id = {(p.name or f"param_{i}"): id(p) for i, p in enumerate(self._parameter_list)}
        for k, v in state_dict.items():
            if k == "LR_Scheduler":
                if isinstance(self._learning_rate, lr_mod.LRScheduler):
                    self._learning_rate.set_state_dict(v)
                continue
            if k == "__step__":
                self._step_count = int(v)
                continue
            pname, _, accum_name = k.rpartition("__")
            pid = name2id.get(pname)
            if pid is None:
                continue
            arr = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            if accum_name == "master":
                self._master_weights[pid] = arr
            else:
                self._accumulators[accum_name][pid] = arr

    load_state_dict = set_state_dict

    def _apply(self, p: Parameter, new_value, master=None):
        """Write back an updated value (and master copy)."""
        if master is not None:
            self._master_weights[id(p)] = master
            p._replace_value(master.astype(p._value.dtype))
        else:
            p._replace_value(new_value)
