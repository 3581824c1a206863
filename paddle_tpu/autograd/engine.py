"""Eager autograd engine.

TPU-native re-design of the reference eager autograd
(reference: paddle/fluid/eager/grad_node_info.h:197 GradNodeBase / :53 Edge,
backward.cc:105 RunBackward with in-degree topo sort at :23,
grad_tensor_holder.cc GradTensorHolder accumulation,
tensor_wrapper.h saved-tensor wrappers).

Design: a tape of GradNodes is recorded as primitives execute. Nodes hold raw
jax arrays (concrete in eager mode, tracers under ``jit.to_static`` capture),
so ONE engine serves both execution modes — backward inside a traced step
becomes part of the compiled XLA program and fuses with forward.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import dispatch

# --------------------------------------------------------------------------
# grad-recording state (paddle.no_grad / enable_grad)
# --------------------------------------------------------------------------
_state = threading.local()


def grad_enabled() -> bool:
    return getattr(_state, "grad_enabled", True)


class no_grad:
    """paddle.no_grad parity (context manager + decorator)."""

    def __enter__(self):
        self._prev = grad_enabled()
        _state.grad_enabled = False
        return self

    def __exit__(self, *exc):
        _state.grad_enabled = self._prev
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with no_grad():
                return fn(*a, **k)

        return wrapper


class enable_grad:
    def __enter__(self):
        self._prev = grad_enabled()
        _state.grad_enabled = True
        return self

    def __exit__(self, *exc):
        _state.grad_enabled = self._prev
        return False


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)
    return no_grad() if not mode else enable_grad()


# --------------------------------------------------------------------------
# Graph nodes
# --------------------------------------------------------------------------
class AccumulationNode:
    """Grad sink for a leaf tensor (GradNodeAccumulation analog)."""

    __slots__ = ("tensor_ref",)

    def __init__(self, tensor):
        import weakref

        self.tensor_ref = weakref.ref(tensor)

    def accumulate(self, grad):
        t = self.tensor_ref()
        if t is None:
            return
        for hook in t._grad_hooks:
            new = hook(_wrap_grad(grad, t))
            if new is not None:
                grad = new._value if hasattr(new, "_value") else new
        if t._grad_value is None:
            t._grad_value = grad
        else:
            t._grad_value = t._grad_value + grad


def _wrap_grad(grad, like):
    from ..core.tensor import Tensor

    return Tensor._from_value(grad)


class _LayerScope(threading.local):
    """Path of the `nn.Layer`s whose forward is running on this thread
    (`gpt/layers.3/attn`): `Layer.__call__` keeps it beside the
    `jax.named_scope` it opens, and a GradNode remembers it, so that the
    backward of an op runs under `bwd/<path>` although `backward()` is
    called outside every layer."""

    path = ""


layer_scope = _LayerScope()


class GradNode:
    """One recorded primitive application (GradNodeBase analog).

    in_edges[i] is (producer: GradNode|AccumulationNode, slot: int) for each
    differentiable input, or None when that input needs no grad.
    """

    __slots__ = (
        "prim_name",
        "static",
        "saved",
        "saved_tensors",
        "out_avals",
        "in_edges",
        "out_hooks",
        "capture_slots",
        "name_hint",
        "scope",
    )

    def __init__(self, prim_name, static, saved, out_avals, in_edges,
                 saved_tensors=None):
        self.prim_name = prim_name
        self.static = static
        self.saved = saved
        # input Tensor refs: keep the upstream graph reachable for
        # create_graph double backward — the TensorWrapper analog
        # (fluid/eager/tensor_wrapper.h keeps the autograd graph of saved
        # tensors for higher-order grad). Trade-off: prims with a slim
        # custom save (e.g. save=()) now also retain their input arrays
        # until release(); paddle pays the same via TensorWrapper.
        self.saved_tensors = saved_tensors
        self.out_avals = out_avals  # [(shape, dtype)] per forward output
        self.in_edges: List[Optional[Tuple[Any, int]]] = in_edges
        self.out_hooks: Dict[int, List[Callable]] = {}
        self.capture_slots: Dict[int, Any] = {}
        self.name_hint = prim_name
        self.scope = layer_scope.path

    def release(self):
        self.saved = None
        self.saved_tensors = None

    def __repr__(self):
        return f"<GradNode {self.name_hint}>"


def record_op(prim_name, static, saved, in_tensors, out_arrays,
              saved_tensors=None, force=False):
    """Create the GradNode for a primitive call; returns it (or None when
    nothing requires grad / grad is disabled). Mirrors the node-creation block
    eager_gen.py emits into every *_ad_func (eager_gen.py:1132).

    force=True records the node even when no INPUT requires grad — needed by
    opaque-backward blocks (recompute/PyLayer) whose internal parameters
    still need gradients (the reference PyLayer records unconditionally)."""
    if not grad_enabled():
        return None
    edges: List[Optional[Tuple[Any, int]]] = []
    any_grad = False
    for t in in_tensors:
        if t is None or t.stop_gradient:
            edges.append(None)
            continue
        any_grad = True
        if t._node is not None:
            edges.append((t._node, t._out_slot))
        else:
            edges.append((t._accum_node(), 0))
    if not any_grad and not force:
        return None
    out_avals = [(tuple(o.shape), o.dtype) for o in out_arrays]
    if _saved_tensor_hooks and saved is not None:
        # saved_tensors_hooks pack stage (reference:
        # autograd/saved_tensors_hooks.py — wrap each saved array; the
        # unpack fn is captured so backward works after the context exits)
        pack, unpack = _saved_tensor_hooks[-1]
        saved = _SavedPacked(tuple(pack(a) for a in saved), unpack)
    return GradNode(prim_name, static, saved, out_avals, edges,
                    saved_tensors=saved_tensors)


_saved_tensor_hooks: List[Tuple[Any, Any]] = []


class _SavedPacked:
    """Marker wrapping hook-packed saved tensors until backward unpacks."""

    __slots__ = ("payload", "unpack_fn")

    def __init__(self, payload, unpack_fn):
        self.payload = payload
        self.unpack_fn = unpack_fn

    def unpack(self):
        return tuple(self.unpack_fn(a) for a in self.payload)


# --------------------------------------------------------------------------
# Backward execution (RunBackward analog, backward.cc:105)
# --------------------------------------------------------------------------
def _collect_indegree(roots: Sequence[GradNode]):
    """BFS the consumer graph to count, per node, how many times it is
    referenced as a producer (backward.cc:23 getInDegreeMap)."""
    indeg: Dict[int, int] = {}
    nodes: Dict[int, Any] = {}
    seen = set()
    q = deque(roots)
    for r in roots:
        seen.add(id(r))
        nodes[id(r)] = r
        indeg.setdefault(id(r), 0)
    while q:
        n = q.popleft()
        if isinstance(n, AccumulationNode):
            continue
        for e in n.in_edges:
            if e is None:
                continue
            p, _slot = e
            indeg[id(p)] = indeg.get(id(p), 0) + 1
            if id(p) not in seen:
                seen.add(id(p))
                nodes[id(p)] = p
                q.append(p)
    return indeg, nodes


def run_backward(
    tensors,
    grad_tensors=None,
    retain_graph: bool = False,
    capture: Optional[Dict[Tuple[int, int], Any]] = None,
    accumulate_leaves: bool = True,
):
    """Execute reverse-mode over the recorded tape.

    tensors: output Tensors to seed.  grad_tensors: matching seeds (or None
    → ones).  capture: optional {(id(node), slot): key} map — grads for those
    (node, slot) pairs are returned keyed by ``key`` instead of / in addition
    to leaf accumulation (GeneralGrad analog for paddle.grad).
    """
    from ..core.tensor import Tensor

    capture = capture or {}
    captured: Dict[Any, Any] = {}

    roots: List[GradNode] = []
    buffers: Dict[int, List[Optional[Any]]] = {}

    with no_grad():
        for i, t in enumerate(tensors):
            if t.stop_gradient and t._node is None:
                raise RuntimeError(
                    f"backward(): tensor {i} has stop_gradient=True and no grad graph"
                )
            g = None
            if grad_tensors is not None and grad_tensors[i] is not None:
                gt = grad_tensors[i]
                g = gt._value if isinstance(gt, Tensor) else jnp.asarray(gt)
            else:
                if t._value.size != 1:
                    if grad_tensors is None:
                        g = jnp.ones(t.shape, t.dtype)
                else:
                    g = jnp.ones(t.shape, t.dtype)
            node = t._node
            if node is None:
                # leaf with requires-grad: grad of itself is the seed
                acc = t._accum_node()
                key = capture.get((id(acc), 0))
                if key is not None:
                    captured[key] = g
                if accumulate_leaves:
                    acc.accumulate(g)
                continue
            if id(node) not in buffers:
                buffers[id(node)] = [None] * len(node.out_avals)
                roots.append(node)
            buf = buffers[id(node)]
            slot = t._out_slot
            buf[slot] = g if buf[slot] is None else buf[slot] + g

        if not roots:
            return captured

        indeg, nodes = _collect_indegree(roots)
        ready = deque(n for n in roots if indeg[id(n)] == 0)
        # roots referenced by other roots wait for their contributions
        processed = set()

        while ready:
            node = ready.popleft()
            if id(node) in processed:
                continue
            processed.add(id(node))
            buf = buffers.pop(id(node), [None] * len(node.out_avals))
            # fill zeros for outputs never used downstream (GradTensorHolder
            # fills with zeros-like, grad_tensor_holder.cc)
            grads_out = tuple(
                b
                if b is not None
                else jnp.zeros(shape, dtype)
                for b, (shape, dtype) in zip(buf, node.out_avals)
            )
            # per-(node,slot) hooks and captures fire on the finalized grad
            for slot, hooks in node.out_hooks.items():
                g = grads_out[slot]
                for hook in hooks:
                    new = hook(Tensor._from_value(g))
                    if new is not None:
                        g = new._value if isinstance(new, Tensor) else new
                grads_out = grads_out[:slot] + (g,) + grads_out[slot + 1 :]
            for slot in range(len(node.out_avals)):
                key = capture.get((id(node), slot))
                if key is not None:
                    captured[key] = grads_out[slot]

            if node.saved is None:
                raise RuntimeError(
                    "Trying to backward through the graph a second time; "
                    "set retain_graph=True to allow this."
                )
            saved = (node.saved.unpack()
                     if isinstance(node.saved, _SavedPacked) else node.saved)
            with jax.named_scope(
                    f"bwd/{node.scope}" if node.scope else "bwd"):
                in_grads = dispatch.call_vjp(
                    node.prim_name, grads_out, saved, node.static
                )
            if not retain_graph:
                node.release()

            for e, g in zip(node.in_edges, in_grads):
                if e is None or g is None:
                    continue
                p, slot = e
                if isinstance(p, AccumulationNode):
                    key = capture.get((id(p), 0))
                    if key is not None:
                        captured[key] = (
                            g if key not in captured else captured[key] + g
                        )
                    if accumulate_leaves:
                        p.accumulate(g)
                    continue
                b = buffers.setdefault(id(p), [None] * len(p.out_avals))
                b[slot] = g if b[slot] is None else b[slot] + g
                indeg[id(p)] -= 1
                if indeg[id(p)] == 0:
                    ready.append(p)

        # nodes whose indegree never hit zero are unreachable-from-seed
        # consumers; any buffered grads there are simply dropped (matches
        # reference partial-graph semantics).
    return captured


# --------------------------------------------------------------------------
# create_graph (double backward): replay the backward pass THROUGH the
# primitive-application layer so every gradient computation is itself
# recorded on the tape. Each forward primitive gets a derived "__vjp__"
# primitive whose forward runs its backward rule; nesting is handled by
# jax's nested vjp in the generic fallback. Reference analog: GradNode
# backward functions are themselves differentiable ops when TensorWrappers
# keep the autograd graph (fluid/eager/general_grad.h + eager_gen VJP
# emission for higher-order ops).
# --------------------------------------------------------------------------
import jax as _jax


def _ensure_vjp_prim(prim_name: str) -> str:
    """Derived primitive running ``jax.vjp`` over the forward with the
    ORIGINAL inputs. Custom save/vjp fast paths are deliberately bypassed:
    they may save forward outputs (severing input dependence), while
    rematerialising the forward keeps every second-order path intact and
    XLA CSE/fusion absorbs the recompute."""
    vname = f"__vjp__{prim_name}"
    if vname in dispatch.PRIMITIVES:
        return vname
    prim = dispatch.PRIMITIVES[prim_name]

    def vjp_forward(*arrays, n_out, inner):
        static = dict(inner)
        grads_out = arrays[:n_out]
        inputs = arrays[n_out:]
        f = lambda *a: prim.forward(*a, **static)
        outs, vjp_fn = _jax.vjp(f, *inputs)
        grads = vjp_fn(grads_out if isinstance(outs, tuple) else grads_out[0])
        grads = tuple(grads) if isinstance(grads, (tuple, list)) else (grads,)
        # non-differentiable inputs (ints, PRNG keys) yield None/float0
        # cotangents — replace with float32 zero placeholders; their edges
        # are None so the placeholders are never consumed
        from jax.dtypes import float0

        return tuple(
            jnp.zeros(a.shape, jnp.float32)
            if g is None or getattr(g, "dtype", None) == float0
            else g
            for g, a in zip(grads, inputs)
        )

    dispatch.register_primitive(
        vname, vjp_forward, multi_out=True, jittable=prim.jittable
    )
    return vname


def run_backward_create_graph(
    tensors,
    grad_tensors=None,
    capture: Optional[Dict[Tuple[int, int], Any]] = None,
    retain_graph: bool = True,
):
    """Backward pass where gradients are Tensors on the live tape, enabling
    paddle.grad(..., create_graph=True) and arbitrary-order derivatives."""
    from ..core.tensor import Tensor, apply as tensor_apply

    capture = capture or {}
    captured: Dict[Any, Any] = {}
    buffers: Dict[int, List[Optional[Any]]] = {}
    roots: List[GradNode] = []

    def seed_for(t, i):
        if grad_tensors is not None and grad_tensors[i] is not None:
            gt = grad_tensors[i]
            return gt if isinstance(gt, Tensor) else Tensor._from_value(jnp.asarray(gt))
        return Tensor._from_value(jnp.ones(t.shape, t.dtype))

    for i, t in enumerate(tensors):
        if t.stop_gradient and t._node is None:
            raise RuntimeError(
                f"backward(): tensor {i} has stop_gradient=True and no grad graph"
            )
        g = seed_for(t, i)
        node = t._node
        if node is None:
            acc = t._accum_node()
            key = capture.get((id(acc), 0))
            if key is not None:
                captured[key] = g if key not in captured else captured[key] + g
            continue
        if id(node) not in buffers:
            buffers[id(node)] = [None] * len(node.out_avals)
            roots.append(node)
        buf = buffers[id(node)]
        slot = t._out_slot
        buf[slot] = g if buf[slot] is None else buf[slot] + g

    if not roots:
        return captured

    indeg, _nodes = _collect_indegree(roots)
    ready = deque(n for n in roots if indeg[id(n)] == 0)
    processed = set()

    while ready:
        node = ready.popleft()
        if id(node) in processed:
            continue
        processed.add(id(node))
        buf = buffers.pop(id(node), [None] * len(node.out_avals))
        grads_out = [
            b if b is not None else Tensor._from_value(jnp.zeros(shape, dtype))
            for b, (shape, dtype) in zip(buf, node.out_avals)
        ]
        for slot, hooks in node.out_hooks.items():
            g = grads_out[slot]
            for hook in hooks:
                new = hook(g)
                if new is not None:
                    g = new if isinstance(new, Tensor) else Tensor._from_value(new)
            grads_out[slot] = g
        for slot in range(len(node.out_avals)):
            key = capture.get((id(node), slot))
            if key is not None:
                captured[key] = grads_out[slot]

        if node.saved is None:
            raise RuntimeError(
                "Trying to backward through the graph a second time; "
                "set retain_graph=True to allow this."
            )
        prim = dispatch.PRIMITIVES[node.prim_name]
        if node.saved_tensors is None or prim.forward is None:
            # non-replayable node (PyLayer / recompute: opaque Python
            # backward, no jax forward to differentiate) — run its
            # first-order vjp; the produced grads enter the new tape as
            # constants, so second order THROUGH this node is cut, matching
            # the reference's behavior for non-double-grad custom ops
            raw = dispatch.call_vjp(
                node.prim_name,
                tuple(g._value for g in grads_out),
                node.saved.unpack() if isinstance(node.saved, _SavedPacked)
                else node.saved,
                node.static,
            )
            in_grads = tuple(
                None if g is None else Tensor._from_value(g) for g in raw
            )
        else:
            vname = _ensure_vjp_prim(node.prim_name)
            in_grads = tensor_apply(
                vname, *grads_out, *node.saved_tensors,
                n_out=len(grads_out),
                inner=dispatch._hashable(node.static),
            )
            if not isinstance(in_grads, tuple):
                in_grads = (in_grads,)
        if not retain_graph:
            node.release()

        for e, g in zip(node.in_edges, in_grads):
            if e is None or g is None:
                continue
            p, slot = e
            if isinstance(p, AccumulationNode):
                key = capture.get((id(p), 0))
                if key is not None:
                    captured[key] = g if key not in captured else captured[key] + g
                continue
            b = buffers.setdefault(id(p), [None] * len(p.out_avals))
            b[slot] = g if b[slot] is None else b[slot] + g
            indeg[id(p)] -= 1
            if indeg[id(p)] == 0:
                ready.append(p)

    return captured
