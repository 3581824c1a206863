"""paddle.jit — whole-function capture to XLA.

Reference: python/paddle/jit/api.py:196 to_static (SOT bytecode translator,
program_translator.py:711) + jit.save/load (api.py:953/:1523).

TPU re-design: the reference needs a CPython eval-frame interpreter to build
a static program from dygraph code; here the eager Tensor already wraps jax
values, so capture is plain jax tracing — the same user function runs on
tracers and the recorded tape/ops become one XLA program. Guards collapse to
a cache key over input avals + layer modes (the SOT guard system's shape/
type guards, executor/guard.py).

Crucially this compiles ENTIRE TRAIN STEPS: parameters, buffers, optimizer
accumulators and RNG are lifted to functional state (inputs + outputs of the
jitted program, donated for in-place buffer reuse), so `loss.backward()` and
`opt.step()` inside the captured function fuse into one XLA executable —
this is the eager-dispatch-cost answer flagged in SURVEY §7.
"""
from __future__ import annotations

import functools
import inspect
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..core import generator
from ..core.tensor import Parameter, Tensor
from .trace_state import in_tracing, tracing_scope

_M_JIT_COMPILES = _obs.counter(
    "jit.compiles", "to_static compiles (new input-signature cache entry)")
_M_JIT_HITS = _obs.counter(
    "jit.cache_hits", "to_static calls served by an existing entry")
_M_JIT_COMPILE_SECONDS = _obs.histogram(
    "jit.compile_seconds",
    "wall time of a to_static entry's first run (trace + XLA compile)")
_M_JIT_FALLBACKS = _obs.counter(
    "jit.fallbacks", "to_static signatures that fell back to eager")

__all__ = ["to_static", "not_to_static", "ignore_module", "save", "load",
           "enable_to_static", "TracedLayer"]

_to_static_enabled = True


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# --------------------------------------------------------------------------
# state slots
# --------------------------------------------------------------------------
class _TensorSlot:
    """A mutable Tensor owned by a Layer (param or buffer) lifted to
    functional state."""

    __slots__ = ("t",)

    def __init__(self, t: Tensor):
        self.t = t

    def get(self):
        return self.t._value

    def set(self, v):
        self.t._replace_value(v)


class _AccumSlot:
    __slots__ = ("opt", "name", "pid")

    def __init__(self, opt, name, pid):
        self.opt, self.name, self.pid = opt, name, pid

    def get(self):
        return self.opt._accumulators[self.name][self.pid]

    def set(self, v):
        self.opt._accumulators[self.name][self.pid] = v


class _MasterSlot:
    __slots__ = ("opt", "pid")

    def __init__(self, opt, pid):
        self.opt, self.pid = opt, pid

    def get(self):
        return self.opt._master_weights[self.pid]

    def set(self, v):
        self.opt._master_weights[self.pid] = v


def _closure_objects(fn):
    objs = []
    if hasattr(fn, "__self__") and fn.__self__ is not None:
        objs.append(fn.__self__)
    closure = getattr(fn, "__closure__", None)
    if closure:
        for cell in closure:
            try:
                objs.append(cell.cell_contents)
            except ValueError:
                pass
    # module-level globals the function references by name
    code = getattr(fn, "__code__", None)
    if code is not None:
        g = getattr(fn, "__globals__", {})
        for name in code.co_names:
            if name in g:
                objs.append(g[name])
    return objs


def _discover(fn, args, kwargs):
    """Find Layers and Optimizers the function touches (self, closure cells,
    positional args) — the dygraph-module discovery the reference does via
    its bytecode walker."""
    from ..nn.layer import Layer
    from ..optimizer.optimizer import Optimizer

    import types

    layers: List[Any] = []
    optimizers: List[Any] = []
    seen = set()

    def visit(o, depth=0):
        if id(o) in seen or depth > 6:
            return
        seen.add(id(o))
        if isinstance(o, Layer):
            layers.append(o)
        elif isinstance(o, Optimizer):
            optimizers.append(o)
        elif isinstance(o, (list, tuple)):
            for x in o:
                visit(x, depth + 1)
        elif isinstance(o, dict):
            for x in o.values():
                visit(x, depth + 1)
        elif isinstance(o, types.FunctionType):
            # nested helper closures (e.g. a step fn calling a local
            # forward fn that holds the model)
            for c in _closure_objects(o):
                visit(c, depth + 1)
        elif hasattr(o, "__dict__") and not isinstance(
            o, (Tensor, type, types.ModuleType)
        ):
            # plain containers (wrapper objects like DistModel) — scan
            # their attributes for Layers/Optimizers
            for x in vars(o).values():
                visit(x, depth + 1)

    for o in _closure_objects(fn):
        visit(o)
    for a in list(args) + list(kwargs.values()):
        visit(a)
    return layers, optimizers


# --------------------------------------------------------------------------
# pytree over Tensors
# --------------------------------------------------------------------------
def _flatten_args(obj, arrays: List[Any]):
    """Returns a hashable template; Tensor leaves become ('T', idx, sg)."""
    if isinstance(obj, Tensor):
        arrays.append(obj._value)
        return ("T", len(arrays) - 1, bool(obj.stop_gradient))
    if isinstance(obj, (list, tuple)):
        return (
            "L" if isinstance(obj, list) else "t",
            tuple(_flatten_args(o, arrays) for o in obj),
        )
    if isinstance(obj, dict):
        return (
            "D",
            tuple(sorted((k, _flatten_args(v, arrays)) for k, v in obj.items())),
        )
    if isinstance(obj, (int, float, str, bool, type(None), np.integer, np.floating)):
        return ("C", obj)
    if isinstance(obj, np.ndarray):
        arrays.append(jnp.asarray(obj))
        return ("T", len(arrays) - 1, True)
    # opaque static object (Layer/Optimizer instance etc.): key by identity
    return ("O", id(obj))


def _unflatten_args(template, arrays, objs_by_id):
    kind = template[0]
    if kind == "T":
        t = Tensor._from_value(arrays[template[1]], stop_gradient=template[2])
        return t
    if kind in ("L", "t"):
        seq = [_unflatten_args(t_, arrays, objs_by_id) for t_ in template[1]]
        return seq if kind == "L" else tuple(seq)
    if kind == "D":
        return {k: _unflatten_args(v, arrays, objs_by_id) for k, v in template[1]}
    if kind == "C":
        return template[1]
    return objs_by_id[template[1]]


def _flatten_out(obj, arrays: List[Any]):
    if isinstance(obj, Tensor):
        arrays.append(obj._value)
        return ("T", len(arrays) - 1, bool(obj.stop_gradient))
    if isinstance(obj, (list, tuple)):
        return (
            "L" if isinstance(obj, list) else "t",
            tuple(_flatten_out(o, arrays) for o in obj),
        )
    if isinstance(obj, dict):
        return ("D", tuple((k, _flatten_out(v, arrays)) for k, v in obj.items()))
    return ("C", obj)


def _unflatten_out(template, arrays):
    kind = template[0]
    if kind == "T":
        return Tensor._from_value(arrays[template[1]], stop_gradient=template[2])
    if kind in ("L", "t"):
        seq = [_unflatten_out(t_, arrays) for t_ in template[1]]
        return seq if kind == "L" else tuple(seq)
    if kind == "D":
        return {k: _unflatten_out(v, arrays) for k, v in template[1]}
    return template[1]


def _aval_key(arrays):
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


class _CompiledEntry:
    __slots__ = ("jitted", "slots", "out_template_box", "optimizers",
                 "step_deltas", "fallback", "ran_ok", "call_avals")

    def __init__(self):
        self.jitted = None
        self.slots = []
        self.out_template_box = [None]
        self.optimizers = []
        self.step_deltas = []
        self.fallback = False
        self.ran_ok = False
        self.call_avals = None


class StaticFunction:
    """The compiled-function cache (reference: program_translator.py
    ProgramCache keyed by guards; here keyed by input avals + layer modes)."""

    def __init__(self, fn, input_spec=None, build_strategy=None,
                 full_graph=False, donate_state: bool = True):
        functools.update_wrapper(self, fn)
        self._fn = fn
        self._cache: Dict[Any, _CompiledEntry] = {}
        self._donate = donate_state
        self._input_spec = input_spec
        self._full_graph = full_graph
        self._label = getattr(fn, "__name__", "?")
        self._ring = _obs.tracing.ring(f"jit.{self._label}", "steps")

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = StaticFunction(self._fn.__get__(instance, owner),
                               self._input_spec, full_graph=self._full_graph,
                               donate_state=self._donate)
        # cache the bound wrapper on the instance
        name = self._fn.__name__
        try:
            object.__setattr__(instance, name, bound)
        except Exception:
            pass
        return bound

    # ------------------------------------------------------------------
    def _mode_key(self, layers):
        return tuple(l.training for l in layers)

    def __call__(self, *args, **kwargs):
        """One call is the span ``jit.call`` with children ``jit.lookup``
        (discovery, flattening, the cache key; a miss builds the entry),
        ``jit.state`` (gathering state, lr and step arrays),
        ``jit.dispatch`` (the compiled call until it returns; on a first
        run the trace and the XLA compile) and ``jit.writeback``: one
        clock pair each, on any live profiler trace, and one step record
        a call in ``observability.tracing``'s ring ``jit.<function>``."""
        if not _to_static_enabled or in_tracing():
            return self._fn(*args, **kwargs)
        fn_label = self._label
        with _obs.span("jit.call", fn=fn_label) as whole:
            with _obs.span("jit.lookup") as lookup:
                layers, optimizers = _discover(self._fn, args, kwargs)
                arrays: List[Any] = []
                template = _flatten_args((args, kwargs), arrays)
                key = (template, _aval_key(arrays), self._mode_key(layers),
                       tuple(id(o) for o in optimizers))
                entry = self._cache.get(key)
                if entry is None:
                    if _obs.state.on:
                        _M_JIT_COMPILES.inc(fn=fn_label)
                    entry = self._compile(template, arrays, layers,
                                          optimizers, args, kwargs)
                    self._cache[key] = entry
                elif _obs.state.on:
                    _M_JIT_HITS.inc(fn=fn_label)
            if entry.fallback:
                # counted once at the transition below, not per call
                return self._fn(*args, **kwargs)
            # runtime invocation
            with _obs.span("jit.state") as gather:
                state = [s.get() for s in entry.slots]
                lr_vals = jnp.asarray(
                    [o.get_lr() for o in entry.optimizers], jnp.float32
                ) if entry.optimizers else jnp.zeros((0,), jnp.float32)
                steps = jnp.asarray(
                    [o._step_count + 1 for o in entry.optimizers],
                    jnp.float32
                ) if entry.optimizers else jnp.zeros((0,), jnp.float32)
                rng = generator.next_key("local_seed")
                call_args = (state, arrays, rng, lr_vals, steps)
            first_run = not entry.ran_ok  # pays jax trace + XLA compile
            if first_run:
                # shapes + placements of this signature, for lowered()
                # (taken before the call donates the state buffers); an
                # uncommitted array follows the others, as it does in
                # the call
                entry.call_avals = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype,
                        sharding=a.sharding if a.committed else None),
                    call_args)
            with _obs.span("jit.dispatch", compiled=first_run) as dispatch:
                if first_run and not self._full_graph \
                        and not self._traces(entry, call_args, fn_label):
                    return self._fn(*args, **kwargs)
                out_arrays, new_state = entry.jitted(*call_args)
            entry.ran_ok = True
            if first_run and _obs.state.on:
                _M_JIT_COMPILE_SECONDS.observe(dispatch.seconds,
                                               fn=fn_label)
                _obs.emit("jit.compile", fn=fn_label,
                          seconds=dispatch.seconds, n_inputs=len(arrays),
                          n_state=len(entry.slots))
            with _obs.span("jit.writeback") as writeback:
                for s, v in zip(entry.slots, new_state):
                    s.set(v)
                # replay python-side step-count increments observed at
                # trace time
                for o, d in zip(entry.optimizers, entry.step_deltas):
                    o._step_count += d
                out = _unflatten_out(entry.out_template_box[0], out_arrays)
        secs = {"lookup": lookup.seconds, "state": gather.seconds,
                "dispatch": dispatch.seconds,
                "writeback": writeback.seconds}
        secs["other"] = whole.seconds - sum(secs.values())
        self._ring.append({"begin": whole.start, "end": whole.end,
                           "seconds": secs})
        return out

    def _traces(self, entry, call_args, fn_label) -> bool:
        """Trace only (the call reuses the cached trace); False once this
        signature has fallen back to eager."""
        try:
            entry.jitted.trace(*call_args)
        except jax.errors.JaxRuntimeError:
            raise
        except Exception as e:  # noqa: BLE001 — SOT-style graph break
            # Reference contract (jit/sot program_translator.py:711): an
            # untraceable construct (data-dependent Python control flow,
            # reverse-mode through a while_loop, ...) must not crash the
            # user's function — fall back to eager for this signature.
            # Only a failure to TRACE falls back. Whatever the device or
            # its compilers refuse — a Mosaic kernel, an out-of-memory
            # program, a fault while running — raises from the call
            # itself: an eager rerun would hide it behind a slow step
            # that still prints a number (and the donated state may be
            # gone). Note the failed trace already ran the function's
            # Python body, so Python-level side effects execute twice on
            # a fallback call.
            import warnings

            warnings.warn(
                f"to_static: tracing '{fn_label}' "
                f"failed ({type(e).__name__}: {e}); falling back to eager "
                "execution for this input signature. Pass full_graph=True "
                "to make this an error.")
            entry.fallback = True
            if _obs.state.on:
                _M_JIT_FALLBACKS.inc(fn=fn_label)
            return False
        return True

    # ------------------------------------------------------------------
    def _compile(self, template, arrays, layers, optimizers, args, kwargs):
        entry = _CompiledEntry()
        entry.optimizers = optimizers
        slots: List[Any] = []
        slot_ids = set()

        def add_slot(s, key_id):
            if key_id in slot_ids:
                return
            slot_ids.add(key_id)
            slots.append(s)

        for l in layers:
            for _, p in l.named_parameters():
                add_slot(_TensorSlot(p), id(p))
            for _, b in l.named_buffers():
                add_slot(_TensorSlot(b), id(b))
        for o in optimizers:
            # ensure accumulators/masters exist before lifting: run a dummy
            # discovery pass — accumulators appear lazily on first step(); to
            # keep first-call compile correct we pre-create via _accum on
            # trainable params using the optimizer's own step-0 path.
            o._ensure_accumulators()
            for p in o._parameter_list:
                if isinstance(p, Tensor):
                    add_slot(_TensorSlot(p), id(p))
            for name, store in o._accumulators.items():
                for pid in store:
                    add_slot(_AccumSlot(o, name, pid), (id(o), name, pid))
            for pid in o._master_weights:
                add_slot(_MasterSlot(o, pid), (id(o), "master", pid))
        entry.slots = slots

        objs_by_id = {}

        def collect_ids(obj):
            if isinstance(obj, (list, tuple)):
                for x in obj:
                    collect_ids(x)
            elif isinstance(obj, dict):
                for x in obj.values():
                    collect_ids(x)
            elif not isinstance(
                obj, (Tensor, int, float, str, bool, type(None), np.ndarray,
                      np.integer, np.floating)
            ):
                objs_by_id[id(obj)] = obj

        collect_ids((args, kwargs))

        fn = self._fn
        out_box = entry.out_template_box

        def pure_fn(state, arg_arrays, rng, lr_vals, steps):
            originals = [s.get() for s in slots]
            grads_snapshot = [
                (s.t, s.t._grad_value) for s in slots if isinstance(s, _TensorSlot)
            ]
            lr_prev = [(o, o._lr_override, o._step_override) for o in optimizers]
            pre_counts = [o._step_count for o in optimizers]
            try:
                for s, v in zip(slots, state):
                    s.set(v)
                for i, o in enumerate(optimizers):
                    o._lr_override = lr_vals[i]
                    o._step_override = steps[i]
                with tracing_scope(), generator.trace_key_scope(rng):
                    a2, k2 = _unflatten_args(template, arg_arrays, objs_by_id)
                    out = fn(*a2, **k2)
                out_arrays: List[Any] = []
                out_box[0] = _flatten_out(out, out_arrays)
                new_state = [s.get() for s in slots]
                return out_arrays, new_state
            finally:
                for s, v in zip(slots, originals):
                    s.set(v)
                for t, g in grads_snapshot:
                    t._grad_value = g
                for o, lro, so in lr_prev:
                    o._lr_override = lro
                    o._step_override = so
                entry.step_deltas = [
                    o._step_count - c for o, c in zip(optimizers, pre_counts)
                ]
                for o, c in zip(optimizers, pre_counts):
                    o._step_count = c

        donate = (0,) if self._donate else ()
        entry.jitted = jax.jit(pure_fn, donate_argnums=donate)
        return entry

    def lowered(self):
        """The jax ``Lowered`` program of every signature run so far, for
        inspection: ``.as_text()`` is the StableHLO (Pallas kernels show
        as ``tpu_custom_call`` with their kernel name),
        ``.compile().as_text()`` the partitioned HLO the devices run.
        Lowering re-traces the function but runs nothing and leaves the
        state untouched."""
        return [e.jitted.lower(*e.call_avals)
                for e in self._cache.values() if e.ran_ok]

    @property
    def code(self):
        import textwrap

        return textwrap.dedent(inspect.getsource(self._fn))

    def concrete_program(self):
        return None


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, **kwargs):
    """paddle.jit.to_static parity (api.py:196). full_graph=False (the
    reference SOT default) falls back to eager when tracing fails;
    full_graph=True surfaces trace errors."""

    def decorate(fn):
        from ..nn.layer import Layer

        if isinstance(fn, Layer):
            layer = fn
            static = StaticFunction(layer.forward, input_spec,
                                    full_graph=full_graph)
            layer.forward = static
            return layer
        return StaticFunction(fn, input_spec, full_graph=full_graph)

    if function is not None:
        return decorate(function)
    return decorate


class TracedLayer:
    pass


# --------------------------------------------------------------------------
# save / load — export a traced inference program (StableHLO) + params.
# Reference: jit/api.py:953 jit.save (program+params for AnalysisPredictor),
# jit/api.py:1523 jit.load.
# --------------------------------------------------------------------------
def save(layer, path, input_spec=None, **configs):
    """Serialize layer params + an exported StableHLO forward (when
    input_spec with concrete shapes is given)."""
    import pickle

    from ..framework.io_ import _pack
    from ..nn.layer import Layer

    payload: Dict[str, Any] = {}
    if isinstance(layer, Layer):
        payload["state_dict"] = _pack(layer.state_dict())
        if input_spec:
            specs = []
            for s in input_spec:
                if isinstance(s, Tensor):
                    specs.append(jax.ShapeDtypeStruct(tuple(s.shape), s.dtype))
                elif isinstance(s, InputSpec):
                    specs.append(jax.ShapeDtypeStruct(tuple(s.shape), s.dtype))
            was_training = layer.training
            layer.eval()

            def fwd(*xs):
                outs = layer(*[Tensor._from_value(x) for x in xs])
                if isinstance(outs, Tensor):
                    return outs._value
                return [o._value for o in outs]

            try:
                exported = jax.export.export(jax.jit(fwd))(*specs)
                payload["stablehlo"] = exported.mlir_module()
                payload["serialized"] = bytes(exported.serialize())
                payload["in_specs"] = [(tuple(s.shape), str(s.dtype)) for s in specs]
            except Exception as e:  # export is best-effort; params always saved
                payload["export_error"] = repr(e)
            finally:
                # saving must not flip the live model's train/eval state
                if was_training:
                    layer.train()
    else:
        payload["state_dict"] = _pack(layer)
    with open(path + (".pdmodel" if not path.endswith(".pdmodel") else ""), "wb") as f:
        pickle.dump(payload, f, protocol=4)
    return payload


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        from ..core.dtype import convert_dtype

        self.shape = tuple(-1 if s is None else int(s) for s in shape)
        self.dtype = convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient


class _LoadedFunction:
    def __init__(self, payload):
        import pickle

        self._payload = payload
        self._state = payload.get("state_dict", {})
        self._callable = None
        if "serialized" in payload:
            exported = jax.export.deserialize(bytearray(payload["serialized"]))
            self._callable = exported.call

    def __call__(self, *args):
        if self._callable is None:
            raise RuntimeError("loaded program has no executable graph")
        arrays = [a._value if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        out = self._callable(*arrays)
        if isinstance(out, (list, tuple)):
            return [Tensor._from_value(o) for o in out]
        return Tensor._from_value(out)

    def state_dict(self):
        from ..framework.io_ import _unpack

        return _unpack(self._state)


def load(path, **configs):
    import pickle

    p = path if path.endswith(".pdmodel") else path + ".pdmodel"
    with open(p, "rb") as f:
        payload = pickle.load(f)
    return TranslatedLayer(payload)


class TranslatedLayer(_LoadedFunction):
    """Reference: jit/translated_layer.py:1285 — the Layer-like object
    jit.load returns: callable, exposes state_dict/parameters, eval/train
    toggles.

    Limitation vs the reference: the loaded program is a serialized
    StableHLO executable with baked weights, so optimizer updates on
    parameters() do NOT feed back into __call__ — the artifact is an
    inference program (the reference's fine-tune path re-executes the
    stored ProgramDesc, which this build does not reconstruct)."""

    def __init__(self, payload):
        super().__init__(payload)
        self.training = False
        self._parameters_cache = None

    def forward(self, *args):
        return self(*args)

    def parameters(self, include_sublayers=True):
        from ..core.tensor import Parameter

        if self._parameters_cache is None:
            # stable identity: repeated calls return the same objects
            self._parameters_cache = [
                v if isinstance(v, Parameter)
                else Parameter(v._value if hasattr(v, "_value") else v)
                for v in self.state_dict().values()
            ]
        return list(self._parameters_cache)

    def eval(self):
        self.training = False
        return self

    def train(self):
        self.training = True
        return self


_SOT_CODE_LEVEL = 0
_SOT_VERBOSITY = 0


def set_code_level(level=100, also_to_stdout=False):
    """Reference: jit/sot/utils/code_status.py via paddle.jit.set_code_level
    — bytecode-translation logging. The TPU build traces through jax (no
    bytecode simulation); the level gates trace-cache debug output."""
    global _SOT_CODE_LEVEL
    _SOT_CODE_LEVEL = int(level)


def set_verbosity(level=0, also_to_stdout=False):
    """Reference: paddle.jit.set_verbosity — dy2static logging level."""
    global _SOT_VERBOSITY
    _SOT_VERBOSITY = int(level)


__all__.extend(["TranslatedLayer", "set_code_level", "set_verbosity"])
