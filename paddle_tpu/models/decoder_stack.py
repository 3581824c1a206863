"""The decoder stack of the causal LMs over a KV cache: ONE function of
arrays that every cached decode path calls.

A family hands over a *view* (``<Family>ForCausalLM.decode_view()``): its
parameter arrays under the names below, the statics ``nh`` / ``nkv`` /
``dh`` / ``eps`` (and ``theta`` where a layer rotates), and ``specs``,
one :class:`LayerSpec` a layer. :func:`stack_layers` reads the layer off
its spec (the mixer's kind, norm and its placement, projections, q/k norm,
RoPE, FFN kind) and is handed what legitimately differs between its
callers, and nothing else: ``write_kv`` (how new K/V rows enter a layer's
cache), ``attn`` (how a row attends) and, for a state-space layer,
``ssm`` (the convolution and the recurrence over the caller's cached
state) and, for a latent-attention layer, ``mla`` (the latent rows into
the caller's cache and attention over them). A view may also carry four
scalings, each 1 (or ``dh ** -0.5``)
where it is absent and then not applied: ``embedding_multiplier``,
``residual_multiplier``, ``logits_scaling`` and ``attn_scale``; and
``rope_dim`` / ``rope_scaling`` where the rotated dims are not a whole
head's or their frequencies are YaRN's. The
callers are ``serve.ServeEngine``'s compiled
steps (a paged pool, ``ops/pallas`` kernels) and
``models/generation._cached_forward`` (a dense cache, a masked softmax);
a new kind of *cache* is theirs, a new kind of *sub-layer* is this
module's, a new family's leaves are its own ``decode_view``.

The ``jax.named_scope`` names here (``layer<i>/qkv|scatter_kv|attn|out|
ffn``, ``layer<i>/moe/...``, ``layer<i>/ssm/in_proj|conv|scan|gate_norm|
out``, ``layer<i>/mla/q|kv|scatter_latent|attn|out`` (and, by the caller,
``/expand`` in a prefill, ``/absorb_q|absorb_o`` in a decode step),
``final_norm``) are what the compiled
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
    #: what mixes the tokens: "attention" (everything above ``ffn``
    #: describes it) | "mamba2" (a state-space layer: pre-norm, its
    #: sizes in the view's ``ssm`` statics) | "mla" (latent attention:
    #: pre-norm, RMSNorm, its sizes in the view's ``mla`` statics)
    mixer: str = "attention"


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


def swiglu_fused_ffn(h, lp, dtype):
    """SwiGLU MLP whose gate and up matrices are one leaf, side by side
    (``w_in`` ``[H, 2 I]``, ``wd``)."""
    import jax
    import jax.numpy as jnp

    gate_up = h @ lp["w_in"]
    i = gate_up.shape[-1] // 2
    return (jax.nn.silu(gate_up[:, :i].astype(jnp.float32)).astype(dtype)
            * gate_up[:, i:]) @ lp["wd"]


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
    "swiglu_fused": FfnKind(_dense(swiglu_fused_ffn), "ffn", "ffn"),
    "gelu": FfnKind(_dense(gelu_ffn), "ffn", "ffn"),
    # dropless, one chip's held experts (models/exaone_moe.py)
    "moe": FfnKind(_moe, None, "moe/combine"),
    # capacity-routed over the call's tokens (models/ernie_moe.py)
    "capacity_moe": FfnKind(_capacity_moe, "ffn", "ffn", per_row=False),
}


def head_logits(p, hidden):
    """LM-head logits; tied heads reuse the embedding in-graph. A view's
    ``logits_scaling`` divides them."""
    logits = hidden @ (p["embed"].T if p.get("tied_head") else p["head"])
    scaling = p.get("logits_scaling", 1)
    return logits if scaling == 1 else logits / scaling


def rope_rows(p, pos, s_max):
    """cos/sin rows at per-row positions ``pos`` — computed ONCE per
    compiled call and reused by every layer (the tables are
    position-only; rebuilding them per layer would stage L identical
    table subgraphs per trace)."""
    import jax.numpy as jnp

    from ..incubate.nn.functional import _rope_tables

    if p.get("rope_scaling"):
        cos_full, sin_full = yarn_tables(s_max, p["rope_dim"], p["theta"],
                                         **p["rope_scaling"])
    else:
        cos_full, sin_full = _rope_tables(
            s_max, p.get("rope_dim", p["dh"]), p["theta"], True,
            jnp.float32)
    return (jnp.take(cos_full, pos, axis=0)[:, None, :],
            jnp.take(sin_full, pos, axis=0)[:, None, :])


def yarn_inv_freq(dim, theta, *, factor, beta_fast, beta_slow, original):
    """[dim / 2] float32 rotation frequencies under YaRN (Peng et al.,
    2023, as ``transformers``' ``DeepseekV2YarnRotaryEmbedding`` has it):
    pair ``i`` turns at ``theta ** (-2 i / dim)``, divided by ``factor``
    for the pairs that make fewer than ``beta_slow`` turns in ``original``
    positions, left as it is for those that make more than ``beta_fast``,
    and blended by a linear ramp between the two (its ends rounded
    outwards to whole pairs)."""
    import math

    import jax.numpy as jnp

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_tables(s, dim, theta, *, factor, beta_fast, beta_slow, original,
                mscale=1.0):
    """(cos, sin) ``[s, dim]`` float32, half-split as ``_rope_tables``
    gives them, at YaRN's frequencies and times ``mscale`` (the ratio of
    the config's two ``yarn_get_mscale``: 1 where they are equal)."""
    import jax.numpy as jnp

    inv = yarn_inv_freq(dim, theta, factor=factor, beta_fast=beta_fast,
                        beta_slow=beta_slow, original=original)
    freqs = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * mscale, jnp.sin(emb) * mscale


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
    if p.get("embedding_multiplier", 1) != 1:
        x = x * p["embedding_multiplier"]
    rope = None
    if any(s.rope for s in specs_of(p)):
        rope = rope_rows(p, positions, s_max)
    if "wpe" in p:
        x = x + jnp.take(p["wpe"], positions, axis=0)
    return x, rope


def _attention_mixer(i, spec, p, lp, x, cache, rope, write_kv, attn,
                     norm, scaled):
    """Layer ``i``'s attention sub-layer on ``x`` [rows, H]: (x with the
    sub-layer's output added, the layer's cache as written)."""
    import jax

    rows = x.shape[0]
    nh, kvh, dh = p["nh"], p["nkv"], p["dh"]
    dtype, eps = p["embed"].dtype, p["eps"]
    scope = jax.named_scope
    kc, vc = cache
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
    with scope(f"layer{i}/attn"):
        ctx = attn(i, spec, q, k, v, kc, vc)
    with scope(f"layer{i}/out"):
        if spec.proj != "split":
            x = x + scaled(ctx.astype(dtype) @ lp["wo"]) + scaled(lp["bo"])
        elif pre:
            x = x + scaled(ctx.astype(dtype) @ lp["wo"])
        else:
            x = x + scaled(norm(spec, ctx.astype(dtype) @ lp["wo"], lp,
                                "ln1"))
    return x, (kc, vc)


def _no_closure(i, mixer, name, keeps):
    """A mixer whose cache only its caller can keep, and a caller that
    handed over no closure for it."""
    raise NotImplementedError(
        f"layer {i} is a `{mixer}` mixer: {keeps}, and this caller handed "
        f"stack_layers no `{name}` closure (generate()'s dense cache keeps "
        "per-head keys and values alone: such a model is served through "
        "ServeEngine)")


def _mamba2_mixer(i, spec, p, lp, x, cache, ssm, norm, scaled):
    """Layer ``i``'s state-space sub-layer (Mamba-2; ``ops/ssm.py`` has
    the equations): norm, ``[z | xBC | dt] = h W_in``, the caller's
    convolution and recurrence over its cache, ``RMSNorm(y * silu(z)) *
    w`` over all the channels (one group, the gate before the norm), the
    output projection."""
    import jax
    import jax.numpy as jnp

    if ssm is None:
        _no_closure(i, "mamba2", "ssm", "its convolution tail and recurrent "
                    "state are the caller's cache")
    if spec.placement != "pre":
        raise ValueError("a `mamba2` mixer is pre-norm")
    st = p["ssm"]
    dtype = p["embed"].dtype
    inner = st["heads"] * st["dh"]
    scope = jax.named_scope
    with scope(f"layer{i}/ssm/in_proj"):
        proj = norm(spec, x, lp, "ln1") @ lp["in_proj"]
        z = proj[:, :inner]
        xbc = proj[:, inner:inner + st["channels"]]
        dt = proj[:, inner + st["channels"]:]
    y, cache = ssm(i, spec, lp, xbc, dt, cache)
    with scope(f"layer{i}/ssm/gate_norm"):
        gated = rms(y * jax.nn.silu(z.astype(jnp.float32)), lp["gate_norm"],
                    p["eps"], dtype)
    with scope(f"layer{i}/ssm/out"):
        x = x + scaled(gated @ lp["out_proj"])
    return x, cache


def _mla_mixer(i, spec, p, lp, x, cache, rope, mla, norm, scaled):
    """Layer ``i``'s latent-attention sub-layer (``ops/mla.py`` has the
    equations): the low-rank query ``RMSNorm(h W_qa) W_qb`` split a head
    into ``[q_nope | q_pe]``, the joint compression ``[c_kv | k_pe] = h
    W_kva`` with ``c = RMSNorm(c_kv)``, RoPE on ``q_pe`` and the one
    ``k_pe``, the row ``[c | k_pe]`` and the queries to the caller's
    cache and attention, the output projection."""
    import jax
    import jax.numpy as jnp

    if mla is None:
        _no_closure(i, "mla", "mla", "its cache is one latent row a token, "
                    "the caller's to keep")
    if spec.placement != "pre" or spec.norm != "rms":
        raise ValueError("a `mla` mixer is pre-norm with RMSNorm")
    st = p["mla"]
    rows = x.shape[0]
    dtype, eps = p["embed"].dtype, p["eps"]
    scope = jax.named_scope
    with scope(f"layer{i}/mla/q"):
        h = norm(spec, x, lp, "ln1")
        q = (rms(h @ lp["wqa"], lp["qan"], eps, dtype) @ lp["wqb"]).reshape(
            rows, p["nh"], st["nope"] + st["rope"])
        q_nope, q_pe = q[:, :, :st["nope"]], q[:, :, st["nope"]:]
    with scope(f"layer{i}/mla/kv"):
        ckv = h @ lp["wkva"]
        c = rms(ckv[:, :st["rank"]], lp["kvan"], eps, dtype)
        q_pe, k_pe = rotate(q_pe, ckv[:, None, st["rank"]:], *rope, dtype)
        latent = jnp.concatenate([c, k_pe[:, 0]], axis=-1)
    ctx, cache = mla(i, spec, lp, q_nope, q_pe, latent, cache)
    with scope(f"layer{i}/mla/out"):
        x = x + scaled(ctx.astype(dtype) @ lp["wo"])
    return x, cache


def stack_layers(p, x, rope, caches, write_kv, attn, *, ssm=None, mla=None,
                 valid=None, backend="auto"):
    """ONE decoder stack for every cached decode path, read off each
    layer's ``LayerSpec``: norm and projection, q/k norm, rope, the new
    K/V rows into the layer's cache, attention (or, for a ``mamba2``
    mixer, the input projection, the caller's convolution and recurrence,
    the gated norm and the output projection), residual + FFN (by kind),
    final norm. ``x`` is [rows, H] and ``rope`` its rows' cos/sin
    (:func:`embed` gives both); ``caches`` one pair a layer, of whatever
    kind the caller keeps: (K, V) for an attention layer, whatever
    ``ssm`` takes for a state-space one. ``write_kv(i, spec, kc, vc, k,
    v) -> (kc, vc)`` takes layer ``i``'s new rows ([rows, kvh, dh]) and
    ``attn(i, spec, q, k, v, kc, vc) -> [rows, nh*dh]`` attends over the
    cache as written; ``ssm(i, spec, lp, xbc, dt, cache) -> (y [rows,
    heads * dh] float32, cache)`` runs the layer's convolution and its
    recurrence over the rows (under the scopes ``layer<i>/ssm/conv`` and
    ``/scan``); ``mla(i, spec, lp, q_nope, q_pe, latent, cache) -> (ctx
    [rows, nh * v], cache)`` writes a latent layer's new rows ``latent``
    ``[rows, rank + rope]`` into the caller's cache and attends (under
    ``layer<i>/mla/scatter_latent`` and ``/attn``): they are all that the
    callers differ in. ``valid``
    marks the rows that are tokens (a sparse layer routes the others
    nowhere) and ``backend`` is ``moe_ffn``'s. Returns (normed hidden
    [rows, H], new caches, the held experts' group sizes of each sparse
    layer)."""
    import contextlib

    import jax

    specs = specs_of(p)
    dtype = p["embed"].dtype
    eps = p["eps"]
    res = p.get("residual_multiplier", 1)

    def norm(spec, x, lp, which):
        if spec.norm == "rms":
            return rms(x, lp[which], eps, dtype)
        return ln(x, lp[which + "_w"], lp[which + "_b"], eps, dtype)

    def scaled(h):
        """A sub-layer's output as it joins the residual stream."""
        return h if res == 1 else h * res

    # scopes by hand, as nn.Layer.__call__ gives them to the eager
    # stack: they are what the op metadata of the compiled steps, and
    # with it XProf and profiler.scope_seconds, name device time by
    scope = jax.named_scope
    new_caches, moe_sizes = [], []
    for i, (lp, spec, cache) in enumerate(
            zip(p["layers"], specs, caches)):
        pre = spec.placement == "pre"
        if spec.mixer == "mamba2":
            x, cache = _mamba2_mixer(i, spec, p, lp, x, cache, ssm, norm,
                                     scaled)
        elif spec.mixer == "mla":
            x, cache = _mla_mixer(i, spec, p, lp, x, cache, rope, mla, norm,
                                  scaled)
        else:
            x, cache = _attention_mixer(i, spec, p, lp, x, cache, rope,
                                        write_kv, attn, norm, scaled)
        new_caches.append(cache)
        kind = FFN_KINDS[spec.ffn]
        with scope(f"layer{i}/{kind.outer}"):
            h = norm(spec, x, lp, "ln2") if pre else x
        with (scope(f"layer{i}/{kind.inner}") if kind.inner
              else contextlib.nullcontext()):
            f, sizes = kind.fn(i, h, lp, p, dtype, valid, backend)
        if sizes is not None:
            moe_sizes.append(sizes)
        with scope(f"layer{i}/{kind.outer}"):
            x = x + scaled(f if pre else norm(spec, f, lp, "ln2"))
    with scope("final_norm"):
        if specs[-1].norm == "rms":
            out = rms(x, p["norm"], eps, dtype)
        else:
            out = ln(x, p["normf_w"], p["normf_b"], eps, dtype)
    return out, new_caches, moe_sizes
