"""The decoder stack of the causal LMs over a KV cache: ONE function of
arrays that every cached decode path calls.

A family hands over a *view* (``<Family>ForCausalLM.decode_view()``): its
parameter arrays under the names below, the statics ``nh`` / ``nkv`` /
``dh`` / ``eps`` (and ``theta`` where a layer rotates), and ``specs``,
one :class:`LayerSpec` a layer. :func:`stack_layers` reads the layer off
its spec (norm and its placement, projections, q/k norm, RoPE, FFN kind)
and is handed what legitimately differs between its callers, and nothing
else: ``write_kv`` (how new K/V rows enter a layer's cache) and ``attn``
(how a row attends). The callers are ``serve.ServeEngine``'s compiled
steps (a paged pool, ``ops/pallas`` kernels) and
``models/generation._cached_forward`` (a dense cache, a masked softmax);
a new kind of *cache* is theirs, a new kind of *sub-layer* is this
module's, a new family's leaves are its own ``decode_view``.

The ``jax.named_scope`` names here (``layer<i>/qkv|scatter_kv|attn|out|
ffn``, ``layer<i>/moe/...``, ``final_norm``) are what the compiled
steps' op metadata, XProf, ``profiler.scope_seconds`` and
``tools/scope_breakdown.py`` name device time by.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

__all__ = ["LayerSpec", "GPT_LAYER", "FFN_KINDS", "specs_of", "embed",
           "stack_layers", "head_logits"]


class LayerSpec(NamedTuple):
    """What :func:`stack_layers` reads of one layer. The defaults are a
    Llama layer's."""

    norm: str = "rms"              # "rms" | "layer"
    placement: str = "pre"         # norms before ("pre") or after a sub-layer
    proj: str = "split"            # "split" wq/wk/wv | "fused_bias" wqkv+bqkv
    rope: bool = True
    qk_norm: bool = False
    window: Optional[int] = None   # None: full attention
    ffn: str = "swiglu"            # a key of FFN_KINDS


#: a GPT-2 layer
GPT_LAYER = LayerSpec(norm="layer", proj="fused_bias", rope=False,
                      ffn="gelu")


def specs_of(p):
    """The view's ``specs``; a view without them is refused."""
    specs = p.get("specs")
    if not specs or len(specs) != len(p["layers"]):
        raise TypeError(
            "a decode view carries `specs`, one LayerSpec a layer "
            f"({len(p['layers'])} here); got {specs!r}")
    return specs


def rms(h, g, eps, dtype):
    """RMSNorm in f32."""
    import jax.numpy as jnp
    from jax import lax

    h32 = h.astype(jnp.float32)
    y = h32 * lax.rsqrt(jnp.mean(h32 * h32, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(dtype)


def ln(h, g, bb, eps, dtype):
    """LayerNorm in f32."""
    import jax.numpy as jnp
    from jax import lax

    h32 = h.astype(jnp.float32)
    mu = jnp.mean(h32, axis=-1, keepdims=True)
    var = jnp.mean((h32 - mu) ** 2, axis=-1, keepdims=True)
    y = (h32 - mu) * lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + bb.astype(jnp.float32)).astype(dtype)


def swiglu_ffn(h, lp, dtype):
    """SwiGLU MLP (``wg``, ``wu``, ``wd``)."""
    import jax
    import jax.numpy as jnp

    return (jax.nn.silu((h @ lp["wg"]).astype(jnp.float32)).astype(dtype)
            * (h @ lp["wu"])) @ lp["wd"]


def gelu_ffn(h, lp, dtype):
    """GELU MLP with biases (``w1``, ``b1``, ``w2``, ``b2``)."""
    import jax
    import jax.numpy as jnp

    return jax.nn.gelu((h @ lp["w1"] + lp["b1"]).astype(jnp.float32),
                       approximate=False).astype(dtype) \
        @ lp["w2"] + lp["b2"]


def _moe(i, h, lp, p, dtype, valid, backend):
    from .exaone_moe import moe_ffn

    return moe_ffn(h, lp, p["moe"], dtype, valid=valid, backend=backend,
                   scope=f"layer{i}/moe")


def _capacity_moe(i, h, lp, p, dtype, valid, backend):
    from .ernie_moe import capacity_moe_ffn

    return capacity_moe_ffn(h, lp, p["moe_statics"][i], dtype), None


class FfnKind(NamedTuple):
    """One kind of FFN sub-layer, under its name in ``LayerSpec.ffn``."""

    #: ``fn(i, h, lp, p, dtype, valid, backend) -> (out, the held
    #: experts' group sizes or None)``
    fn: Callable
    #: the scope ``fn`` runs under; None where it names its own parts
    inner: Optional[str]
    #: the scope of the sub-layer's norm and residual add
    outer: str
    #: a row's result does not depend on the call's other rows, so a
    #: stream batched with others decodes as it would alone
    per_row: bool = True


def _dense(ffn):
    return lambda i, h, lp, p, dtype, valid, backend: (
        ffn(h, lp, dtype), None)


FFN_KINDS = {
    "swiglu": FfnKind(_dense(swiglu_ffn), "ffn", "ffn"),
    "gelu": FfnKind(_dense(gelu_ffn), "ffn", "ffn"),
    # dropless, one chip's held experts (models/exaone_moe.py)
    "moe": FfnKind(_moe, None, "moe/combine"),
    # capacity-routed over the call's tokens (models/ernie_moe.py)
    "capacity_moe": FfnKind(_capacity_moe, "ffn", "ffn", per_row=False),
}


def head_logits(p, hidden):
    """LM-head logits; tied heads reuse the embedding in-graph."""
    if p.get("tied_head"):
        return hidden @ p["embed"].T
    return hidden @ p["head"]


def rope_rows(p, pos, s_max):
    """cos/sin rows at per-row positions ``pos`` — computed ONCE per
    compiled call and reused by every layer (the tables are
    position-only; rebuilding them per layer would stage L identical
    table subgraphs per trace)."""
    import jax.numpy as jnp

    from ..incubate.nn.functional import _rope_tables

    cos_full, sin_full = _rope_tables(s_max, p["dh"], p["theta"], True,
                                      jnp.float32)
    return (jnp.take(cos_full, pos, axis=0)[:, None, :],
            jnp.take(sin_full, pos, axis=0)[:, None, :])


def rotate(q, k, cos, sin, dtype):
    """Rotate q/k ([rows, heads, dh]) by precomputed cos/sin rows (the
    layers whose spec says ``rope``)."""
    import jax.numpy as jnp

    from ..incubate.nn.functional._rope_common import rotate_half

    q = (q.astype(jnp.float32) * cos
         + rotate_half(q.astype(jnp.float32), True) * sin)
    k = (k.astype(jnp.float32) * cos
         + rotate_half(k.astype(jnp.float32), True) * sin)
    return q.astype(dtype), k.astype(dtype)


def embed(p, tokens, positions, s_max):
    """Token rows and what the family adds to them of position: (x
    [rows, H], the rope rows or None). ``tokens`` is [rows] (or a
    prefill's [1, rows]) and ``positions`` [rows]; ``s_max`` is the
    length of the rope tables."""
    import jax.numpy as jnp

    x = jnp.take(p["embed"], tokens, axis=0)
    if tokens.ndim == 2:            # a prefill's [1, bucket] ids
        x = x[0]
    rope = None
    if any(s.rope for s in specs_of(p)):
        rope = rope_rows(p, positions, s_max)
    if "wpe" in p:
        x = x + jnp.take(p["wpe"], positions, axis=0)
    return x, rope


def stack_layers(p, x, rope, caches, write_kv, attn, *, valid=None,
                 backend="auto"):
    """ONE transformer stack for every cached decode path, read off each
    layer's ``LayerSpec``: norm and projection, q/k norm, rope, the new
    K/V rows into the layer's cache, attention, residual + FFN (by
    kind), final norm. ``x`` is [rows, H] and ``rope`` its rows' cos/sin
    (:func:`embed` gives both); ``caches`` one (K, V) a layer, of
    whatever kind the caller keeps. ``write_kv(i, spec, kc, vc, k, v) ->
    (kc, vc)`` takes layer ``i``'s new rows ([rows, kvh, dh]) and
    ``attn(i, spec, q, k, v, kc, vc) -> [rows, nh*dh]`` attends over the
    cache as written: they are all that the callers differ in. ``valid``
    marks the rows that are tokens (a sparse layer routes the others
    nowhere) and ``backend`` is ``moe_ffn``'s. Returns (normed hidden
    [rows, H], new caches, the held experts' group sizes of each sparse
    layer)."""
    import contextlib

    import jax

    specs = specs_of(p)
    rows = x.shape[0]
    nh, kvh, dh = p["nh"], p["nkv"], p["dh"]
    dtype = p["embed"].dtype
    eps = p["eps"]

    def norm(spec, x, lp, which):
        if spec.norm == "rms":
            return rms(x, lp[which], eps, dtype)
        return ln(x, lp[which + "_w"], lp[which + "_b"], eps, dtype)

    # scopes by hand, as nn.Layer.__call__ gives them to the eager
    # stack: they are what the op metadata of the compiled steps, and
    # with it XProf and profiler.scope_seconds, name device time by
    scope = jax.named_scope
    new_caches, moe_sizes = [], []
    for i, (lp, spec, (kc, vc)) in enumerate(
            zip(p["layers"], specs, caches)):
        pre = spec.placement == "pre"
        with scope(f"layer{i}/qkv"):
            h = norm(spec, x, lp, "ln1") if pre else x
            if spec.proj == "split":
                q = (h @ lp["wq"]).reshape(rows, nh, dh)
                k = (h @ lp["wk"]).reshape(rows, kvh, dh)
                v = (h @ lp["wv"]).reshape(rows, kvh, dh)
            else:
                qkv = (h @ lp["wqkv"] + lp["bqkv"]).reshape(
                    rows, 3, nh, dh)
                q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            if spec.qk_norm:
                q = rms(q, lp["qn"], eps, dtype)
                k = rms(k, lp["kn"], eps, dtype)
            if spec.rope:
                q, k = rotate(q, k, *rope, dtype)
        with scope(f"layer{i}/scatter_kv"):
            kc, vc = write_kv(i, spec, kc, vc, k, v)
        new_caches.append((kc, vc))
        with scope(f"layer{i}/attn"):
            ctx = attn(i, spec, q, k, v, kc, vc)
        with scope(f"layer{i}/out"):
            if spec.proj != "split":
                x = x + ctx.astype(dtype) @ lp["wo"] + lp["bo"]
            elif pre:
                x = x + ctx.astype(dtype) @ lp["wo"]
            else:
                x = x + norm(spec, ctx.astype(dtype) @ lp["wo"], lp,
                             "ln1")
        kind = FFN_KINDS[spec.ffn]
        with scope(f"layer{i}/{kind.outer}"):
            h = norm(spec, x, lp, "ln2") if pre else x
        with (scope(f"layer{i}/{kind.inner}") if kind.inner
              else contextlib.nullcontext()):
            f, sizes = kind.fn(i, h, lp, p, dtype, valid, backend)
        if sizes is not None:
            moe_sizes.append(sizes)
        with scope(f"layer{i}/{kind.outer}"):
            x = x + (f if pre else norm(spec, f, lp, "ln2"))
    with scope("final_norm"):
        if specs[-1].norm == "rms":
            out = rms(x, p["norm"], eps, dtype)
        else:
            out = ln(x, p["normf_w"], p["normf_b"], eps, dtype)
    return out, new_caches, moe_sizes
