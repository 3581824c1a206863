"""nn.Layer base class.

Reference: python/paddle/nn/layer/layers.py:351 (Layer — parameters,
sublayers, state_dict, hooks, train/eval). Parameters are Tensors with
stop_gradient=False; buffers are non-trainable persistent state (running
stats etc.).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd.engine import layer_scope
from ..core.tensor import Parameter, Tensor


class HookRemoveHelper:
    def __init__(self, hooks: Dict[int, Callable], hook_id: int):
        self._hooks = hooks
        self._id = hook_id

    def remove(self):
        self._hooks.pop(self._id, None)


def _name_scope_of(parent: "Layer", name: str, sub: "Layer"):
    """`parent` holds `sub` as `name`: that is the `jax.named_scope` its
    forward runs under. A bare container (a LayerList: no forward of its
    own, so it never opens a scope) puts its own name before its
    children's, `layers.3`, whether they join before or after it got that
    name; one that has no name (a slice of a LayerList) renames nothing.
    A layer held under two names keeps the last."""
    held = parent.__dict__.get("_scope")
    if type(parent).forward is Layer.forward:
        if held is not None:
            name = f"{held}.{name}"
        elif sub.__dict__.get("_scope") is not None:
            return
    object.__setattr__(sub, "_scope", name)
    if type(sub).forward is Layer.forward:
        for k, child in sub._sub_layers.items():
            if child is not None:
                object.__setattr__(child, "_scope", f"{name}.{k}")


class Layer:
    _hook_counter = 0

    def __init__(self, name_scope: Optional[str] = None, dtype: str = "float32"):
        self.training = True
        self._dtype = dtype
        self._parameters: "OrderedDict[str, Optional[Parameter]]" = OrderedDict()
        self._sub_layers: "OrderedDict[str, Optional[Layer]]" = OrderedDict()
        self._buffers: "OrderedDict[str, Optional[Tensor]]" = OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks: Dict[int, Callable] = OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = OrderedDict()
        self._casted_by_pure_fp16 = False
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ------------------------------------------------------------------
    # attribute protocol
    # ------------------------------------------------------------------
    def __setattr__(self, name: str, value: Any):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            layers and layers.pop(name, None)
            buffers is not None and buffers.pop(name, None)
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            layers[name] = value
            params and params.pop(name, None)
            object.__setattr__(self, name, value)
            _name_scope_of(self, name, value)
        else:
            if params is not None and name in params and value is None:
                params[name] = None
            if buffers is not None and name in buffers:
                buffers[name] = value
            object.__setattr__(self, name, value)

    # ------------------------------------------------------------------
    # registration
    # ------------------------------------------------------------------
    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        self._parameters[name] = parameter
        object.__setattr__(self, name, parameter)
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        object.__setattr__(self, name, sublayer)
        if sublayer is not None:
            _name_scope_of(self, name, sublayer)
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        object.__setattr__(self, name, tensor)
        return tensor

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias: bool = False,
        default_initializer=None,
    ) -> Parameter:
        """Reference: layers.py create_parameter — ParamAttr + initializer."""
        from . import initializer as I
        from .param_attr import ParamAttr

        dtype = dtype or self._dtype
        attr = ParamAttr._to_attr(attr)
        init = None
        if attr is not None and attr.initializer is not None:
            init = attr.initializer
        elif default_initializer is not None:
            init = default_initializer
        else:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        name = attr.name if attr is not None else None
        value = init(shape, dtype)
        p = Parameter(value, trainable=(attr is None or attr.trainable), name=name)
        if attr is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
            p.regularizer = attr.regularizer
        return p

    # ------------------------------------------------------------------
    # traversal
    # ------------------------------------------------------------------
    def named_parameters(
        self, prefix: str = "", include_sublayers: bool = True
    ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer, in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p
            if not include_sublayers:
                break

    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_sublayers(
        self, prefix: str = "", include_self: bool = False, layers_set=None
    ) -> Iterator[Tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(
                prefix=sub_prefix, include_self=True, layers_set=layers_set
            )

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def buffers(self, include_sublayers: bool = True) -> List[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ------------------------------------------------------------------
    # mode
    # ------------------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # ------------------------------------------------------------------
    # state dict
    # ------------------------------------------------------------------
    def state_dict(
        self,
        destination=None,
        include_sublayers: bool = True,
        structured_name_prefix: str = "",
        use_hook: bool = True,
    ) -> Dict[str, Tensor]:
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[name] = p
        for name, layer in self.named_sublayers(
            prefix=structured_name_prefix.rstrip("."), include_self=True
        ):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                dest[(f"{name}.{bname}" if name else bname)] = b
        return dest

    def set_state_dict(self, state_dict: Dict[str, Any], use_structured_name: bool = True):
        """Reference: layers.py set_state_dict — copies values into existing
        params/buffers (shape-checked)."""
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            target = own[k]
            src = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            if tuple(src.shape) != tuple(target._value.shape):
                raise ValueError(
                    f"state_dict shape mismatch for {k}: "
                    f"{tuple(src.shape)} vs {tuple(target._value.shape)}"
                )
            target._replace_value(src.astype(target._value.dtype))
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    # ------------------------------------------------------------------
    # dtype / device movement
    # ------------------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        from ..core.dtype import convert_dtype, is_floating_point

        dt = convert_dtype(dtype) if dtype is not None else None
        for t in list(self.state_dict().values()):
            v = t._value
            if dt is not None and is_floating_point(v.dtype):
                v = v.astype(dt)
            t._replace_value(v)
        if dt is not None:
            for l in self.sublayers(include_self=True):
                l._dtype = np.dtype(dt).name
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    # ------------------------------------------------------------------
    # hooks
    # ------------------------------------------------------------------
    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        Layer._hook_counter += 1
        self._forward_pre_hooks[Layer._hook_counter] = hook
        return HookRemoveHelper(self._forward_pre_hooks, Layer._hook_counter)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        Layer._hook_counter += 1
        self._forward_post_hooks[Layer._hook_counter] = hook
        return HookRemoveHelper(self._forward_post_hooks, Layer._hook_counter)

    # ------------------------------------------------------------------
    # call
    # ------------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        # the name under which the parent holds this layer (the class's
        # for a root), so that a compiled program's op metadata, and with
        # it XProf and profiler.scope_seconds, tell `layers.3/attn` from
        # `layers.4/linear1`; nothing at run time in a compiled step
        name = self.__dict__.get("_scope") or self._name_scope
        outer = layer_scope.path
        layer_scope.path = f"{outer}/{name}" if outer else name
        try:
            with jax.named_scope(name):
                outputs = self.forward(*inputs, **kwargs)
        finally:
            layer_scope.path = outer
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # ------------------------------------------------------------------
    def full_name(self) -> str:
        return self._name_scope

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            mod_str = repr(sub)
            mod_str = "\n".join(
                "  " + line for line in mod_str.split("\n")
            )
            lines.append(f"  ({name}): " + mod_str.lstrip())
        main = self.__class__.__name__ + "(" + extra
        if lines:
            main += "\n" + "\n".join(lines) + "\n"
        return main + ")"

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()
