"""Incremental decoding (KV-cache generation) for the causal LMs.

Reference surface: PaddleNLP's ``model.generate`` (greedy / sampling
over a cached decoder) built on the serving ops the core repo ships —
masked_multihead_attention (single-step decode over a dense KV cache,
incubate/nn/functional/masked_multihead_attention.py:19) and the
block/paged variants. The core reference also exposes
``paddle.nn.BeamSearchDecoder``/``dynamic_decode`` (nn/decode.py) for
seq2seq; THIS module is the decoder-only LLM path.

TPU-first design: the ENTIRE decode loop is one jitted program — a
``lax.scan`` over ``max_new_tokens`` whose carry holds the dense KV
cache ``[L, B, S_max, kvh, dh]``; each tick is a single-token forward
through the transformer stack with the attention reading the cache
(static shapes throughout, one compile, zero host round-trips between
tokens).
Prefill runs the prompt through the same cached step with T=prompt_len
and a causal mask.

The math mirrors models/llama.py exactly (same rope tables via
incubate's ``_rope_tables``/``rotate_half``); the test suite pins the
cached greedy path token-for-token against the model's own full-prefix
forward, so any architecture drift fails loudly. Families: Llama, GPT,
and ERNIE-MoE (per-step expert routing through the same index-dispatch
program the training forward uses, EVAL routing).

Supports: greedy, temperature / top-k / top-p sampling with
repetition_penalty / min_length, eos early-stop (fixed-length scan
with post-eos masking — compiler-friendly control flow instead of a
data-dependent loop), BEAM SEARCH with GNMT length_penalty,
LEFT-PADDED mixed-length prompts (``pad_token_id=...``: per-row
rope/position offsets + a pad-aware visibility mask, every row pinned
against its own full-prefix oracle in tests), a PAGED block-KV-cache
decode path (``paged=True``, Llama and GPT families) that drives the
same ``block_mha_p`` program the serving op
``incubate.nn.functional.block_multihead_attention`` exposes
(reference: incubate/nn/functional/block_multihead_attention.py:19),
and SPECULATIVE draft-and-verify decoding (``generate_speculative``,
output exactly equal to the target's greedy by construction).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from ..core.tensor import Tensor

__all__ = ["generate", "generate_speculative"]


class LayerSpec(NamedTuple):
    """What ``ServeEngine._stack_layers`` reads of one layer. The
    defaults are a Llama layer's; a family whose layers differ among
    themselves gives one a layer under ``specs`` in its parameter view
    (``_exaone_decode_params``)."""

    norm: str = "rms"              # "rms" | "layer"
    placement: str = "pre"         # norms before ("pre") or after a sub-layer
    proj: str = "split"            # "split" wq/wk/wv | "fused_bias" wqkv+bqkv
    rope: bool = True
    qk_norm: bool = False
    window: Optional[int] = None   # None: full attention
    ffn: str = "swiglu"            # "swiglu" | "gelu" | "moe"


#: a GPT-2 layer
GPT_LAYER = LayerSpec(norm="layer", proj="fused_bias", rope=False,
                      ffn="gelu")


def _llama_decode_params(model):
    """Closure-friendly views of the model's parameter arrays."""
    cfg = model.config
    layers = []
    for layer in model.llama.layers:
        a, m = layer.self_attn, layer.mlp
        layers.append(dict(
            ln1=layer.input_layernorm.weight._value,
            wq=a.q_proj.weight._value, wk=a.k_proj.weight._value,
            wv=a.v_proj.weight._value, wo=a.o_proj.weight._value,
            ln2=layer.post_attention_layernorm.weight._value,
            wg=m.gate_proj.weight._value, wu=m.up_proj.weight._value,
            wd=m.down_proj.weight._value,
        ))
    return dict(
        embed=model.llama.embed_tokens.weight._value,
        norm=model.llama.norm.weight._value,
        head=model.lm_head.weight._value,
        layers=layers,
        nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
        dh=cfg.hidden_size // cfg.num_attention_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
    )


def _rms(h, g, eps, dtype):
    """RMSNorm in f32 — ONE implementation for the dense and paged
    decode paths so the norm math can't drift between them."""
    import jax.numpy as jnp
    from jax import lax

    h32 = h.astype(jnp.float32)
    y = h32 * lax.rsqrt(jnp.mean(h32 * h32, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(dtype)


def _ln(h, g, bb, eps, dtype):
    """LayerNorm in f32 — shared by the dense and paged GPT paths."""
    import jax.numpy as jnp
    from jax import lax

    h32 = h.astype(jnp.float32)
    mu = jnp.mean(h32, axis=-1, keepdims=True)
    var = jnp.mean((h32 - mu) ** 2, axis=-1, keepdims=True)
    y = (h32 - mu) * lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + bb.astype(jnp.float32)).astype(dtype)


def _llama_ffn(h, lp, dtype):
    """SwiGLU MLP — shared by the dense and paged Llama paths."""
    import jax
    import jax.numpy as jnp

    return (jax.nn.silu((h @ lp["wg"]).astype(jnp.float32)).astype(dtype)
            * (h @ lp["wu"])) @ lp["wd"]


def _gpt_ffn(h, lp, dtype):
    """GELU MLP with biases — shared by the dense and paged GPT paths."""
    import jax
    import jax.numpy as jnp

    return jax.nn.gelu((h @ lp["w1"] + lp["b1"]).astype(jnp.float32),
                       approximate=False).astype(dtype) \
        @ lp["w2"] + lp["b2"]


def _cached_forward(p, tokens, caches, pos, s_max, pads=None,
                    return_all=False):
    """Forward ``tokens`` [B, T] through the stack at absolute positions
    ``pos..pos+T-1``, reading/updating the per-layer KV caches
    [B, S_max, kvh, dh]. Returns (last-position hidden [B, H], caches) —
    or every position's hidden [B, T, H] with ``return_all`` (the
    speculative verify pass needs all of them). Causal within the new
    tokens; full attention to everything cached before ``pos``.
    ``pads`` [B] (left-pad counts) offsets each row's rope positions and
    blanks its pad slots out of the visibility mask — the ragged-prompt
    path. ``pos`` may be a traced scalar (speculative decoding advances
    it dynamically)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..incubate.nn.functional import _rope_tables
    from ..incubate.nn.functional._rope_common import rotate_half

    b, t = tokens.shape
    nh, nkv, dh = p["nh"], p["nkv"], p["dh"]
    x = jnp.take(p["embed"], tokens, axis=0)          # [B, T, H]
    dtype = x.dtype

    def rms(h, g):
        return _rms(h, g, p["eps"], dtype)

    cos_full, sin_full = _rope_tables(s_max, dh, p["theta"], True,
                                      jnp.float32)
    positions = pos + jnp.arange(t)                   # absolute [T]
    if pads is None:
        cos = jnp.take(cos_full, positions, axis=0)[None, :, None, :]
        sin = jnp.take(sin_full, positions, axis=0)[None, :, None, :]
        # query i (absolute pos+i) may see cache slot j iff j <= pos+i
        slot = jnp.arange(s_max)[None, :]             # [1, S_max]
        visible = (slot <= positions[:, None])[None]  # [1, T, S_max]
    else:
        # per-row logical positions: absolute minus this row's pad run
        rel = jnp.maximum(positions[None, :] - pads[:, None], 0)  # [B, T]
        cos = jnp.take(cos_full, rel, axis=0)[:, :, None, :]
        sin = jnp.take(sin_full, rel, axis=0)[:, :, None, :]
        slot = jnp.arange(s_max)[None, None, :]
        visible = (slot <= positions[None, :, None]) \
            & (slot >= pads[:, None, None])           # [B, T, S_max]

    new_caches = []
    moe_statics = p.get("moe_statics")
    for li, (lp, cache) in enumerate(zip(p["layers"], caches)):
        h = rms(x, lp["ln1"])
        q = (h @ lp["wq"]).reshape(b, t, nh, dh)
        k = (h @ lp["wk"]).reshape(b, t, nkv, dh)
        v = (h @ lp["wv"]).reshape(b, t, nkv, dh)
        q = (q.astype(jnp.float32) * cos
             + rotate_half(q.astype(jnp.float32), True) * sin).astype(dtype)
        k = (k.astype(jnp.float32) * cos
             + rotate_half(k.astype(jnp.float32), True) * sin).astype(dtype)
        ctx, cache = _cached_attention(q, k, v, cache, pos, visible,
                                       nh // nkv)
        new_caches.append(cache)
        x = x + ctx @ lp["wo"]
        h2 = rms(x, lp["ln2"])
        if "moe" in lp:
            x = x + _moe_mlp(h2, lp, moe_statics[li], dtype)
        else:
            x = x + _llama_ffn(h2, lp, dtype)
    out = rms(x, p["norm"])
    return (out if return_all else out[:, -1, :]), new_caches


def _ernie_decode_params(model):
    """ERNIE-MoE views: Llama-style attention/norms, per-layer MLP is
    either the dense SwiGLU or a routed expert bank. Generation runs
    the gate's current-mode routing (eval: deterministic top-k, eval
    capacity factor). Expert CAPACITY is computed over the tokens of
    each decode call (prefill: B*prompt_len; steps: B) with the same
    shared formula as the training forward — so decode matches the
    model's full-prefix forward whenever no expert saturates (the
    oracle-pinned regime); when capacity binds, drop behavior is
    per-call, mirroring the reference's step-wise serving ops
    (masked/block MHA process only the step's tokens too)."""
    cfg = model.config
    layers = []
    moe_statics = []
    for layer in model.model.layers:
        a = layer.self_attn
        entry = dict(
            ln1=layer.input_layernorm.weight._value,
            wq=a.q_proj.weight._value, wk=a.k_proj.weight._value,
            wv=a.v_proj.weight._value, wo=a.o_proj.weight._value,
            ln2=layer.post_attention_layernorm.weight._value,
        )
        if layer.is_moe:
            gate, ex = layer.mlp.gate, layer.mlp.experts
            entry["moe"] = dict(
                gw=gate.weight._value, gb=gate.bias._value,
                w0=ex.w0._value, b0=ex.b0._value,
                w1=ex.w1._value, b1=ex.b1._value,
            )
            # routing statics live OUTSIDE the layer dict: the layers
            # list rides as a jit ARGUMENT, and a string inside it
            # would break tracing. _train_factor() already respects
            # gate.training (GShard: capacity[0] train / [1] eval;
            # Naive: flat factor).
            moe_statics.append((int(gate.topk),
                                float(gate._train_factor()),
                                ex.activation, bool(gate._normalize)))
        else:
            m = layer.mlp
            entry.update(wg=m.gate_proj.weight._value,
                         wu=m.up_proj.weight._value,
                         wd=m.down_proj.weight._value)
            moe_statics.append(None)
        layers.append(entry)
    return dict(
        embed=model.model.embed_tokens.weight._value,
        norm=model.model.norm.weight._value,
        head=model.lm_head.weight._value,
        layers=layers,
        moe_statics=tuple(moe_statics),   # hashable → static_cfg
        nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
        dh=cfg.hidden_size // cfg.num_attention_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
    )


def _moe_mlp(h, lp, statics, dtype):
    """Routed expert FFN for the decode mirror: EVAL GShard/naive
    routing (top-k softmax gate, deterministic) through the same
    index-dispatch program the model's own forward uses
    (moe_layer._moe_idx_ffn_fwd), so decode and full-prefix forward
    route identically."""
    import jax
    import jax.numpy as jnp

    from ..incubate.distributed.models.moe.moe_layer import _moe_idx_ffn_fwd

    from ..incubate.distributed.models.moe.gate import _capacity

    topk, factor, activation, normalize = statics
    m = lp["moe"]
    shape = h.shape
    x = h.reshape(-1, shape[-1])
    n, e = x.shape[0], m["gw"].shape[1]
    probs = jax.nn.softmax(
        (x @ m["gw"] + m["gb"]).astype(jnp.float32), axis=-1)
    # the SHARED capacity rule (gate._capacity) over THIS call's tokens
    cap = _capacity(n, e, topk, factor)
    out = _moe_idx_ffn_fwd(
        probs, x, m["w0"], m["b0"], m["w1"], m["b1"],
        jax.random.PRNGKey(0), k=topk, capacity=cap,
        activation=activation, normalize=normalize, random2=False)
    return out.astype(dtype).reshape(shape)


def _gpt_decode_params(model):
    """GPT-family views: learned positions, pre-LN, fused qkv, GELU."""
    cfg = model.config
    layers = []
    for layer in model.gpt.layers:
        a = layer.attn
        layers.append(dict(
            ln1_w=layer.norm1.weight._value, ln1_b=layer.norm1.bias._value,
            wqkv=a.qkv_proj.weight._value, bqkv=a.qkv_proj.bias._value,
            wo=a.out_proj.weight._value, bo=a.out_proj.bias._value,
            ln2_w=layer.norm2.weight._value, ln2_b=layer.norm2.bias._value,
            w1=layer.linear1.weight._value, b1=layer.linear1.bias._value,
            w2=layer.linear2.weight._value, b2=layer.linear2.bias._value,
        ))
    out = dict(
        embed=model.gpt.wte.weight._value,
        wpe=model.gpt.wpe.weight._value,
        normf_w=model.gpt.norm_f.weight._value,
        normf_b=model.gpt.norm_f.bias._value,
        layers=layers,
        nh=cfg.num_attention_heads, nkv=cfg.num_attention_heads,
        dh=cfg.hidden_size // cfg.num_attention_heads,
        eps=cfg.layer_norm_eps,
        # tied head: logits = hidden @ embed.T computed in-graph (a
        # materialized transpose would duplicate [V, H] on device)
        tied_head=bool(cfg.tie_word_embeddings),
        max_positions=int(cfg.max_position_embeddings),
    )
    if not cfg.tie_word_embeddings:
        out["head"] = model.lm_head.weight._value
    return out


def _gpt_cached_forward(p, tokens, caches, pos, s_max, pads=None,
                        return_all=False):
    """GPT block stack with a dense KV cache (pre-LN, learned
    positions); same contract as the llama `_cached_forward`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t = tokens.shape
    nh, dh = p["nh"], p["dh"]
    positions = pos + jnp.arange(t)
    if pads is None:
        wpe_rows = jnp.take(p["wpe"], positions, axis=0)[None, :, :]
        slot = jnp.arange(s_max)[None, :]
        visible = (slot <= positions[:, None])[None]  # [1, T, S_max]
    else:
        rel = jnp.maximum(positions[None, :] - pads[:, None], 0)  # [B, T]
        wpe_rows = jnp.take(p["wpe"], rel, axis=0)    # [B, T, H]
        slot = jnp.arange(s_max)[None, None, :]
        visible = (slot <= positions[None, :, None]) \
            & (slot >= pads[:, None, None])
    x = jnp.take(p["embed"], tokens, axis=0) + wpe_rows
    dtype = x.dtype

    def ln(h, g, bb):
        return _ln(h, g, bb, p["eps"], dtype)

    new_caches = []
    for lp, cache in zip(p["layers"], caches):
        h = ln(x, lp["ln1_w"], lp["ln1_b"])
        qkv = (h @ lp["wqkv"] + lp["bqkv"]).reshape(b, t, 3, nh, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        ctx, cache = _cached_attention(q, k, v, cache, pos, visible, 1)
        new_caches.append(cache)
        x = x + ctx @ lp["wo"] + lp["bo"]
        x = x + _gpt_ffn(ln(x, lp["ln2_w"], lp["ln2_b"]), lp, dtype)
    out = ln(x, p["normf_w"], p["normf_b"])
    return (out if return_all else out[:, -1, :]), new_caches


def _exaone_decode_params(model):
    """The EXAONE-MoE parameter view: the Llama view's names where the
    leaves mean the same, ``specs`` (one ``LayerSpec`` a layer: attention
    kind and window, RoPE or none, q/k norm, norm placement, dense or
    expert FFN) and ``moe`` (the router's statics and the held experts)
    for ``ServeEngine``; no dense-cache forward (serving is paged)."""
    cfg = model.config
    layers = []
    for l, layer in enumerate(model.exaone.layers):
        a, m = layer.self_attn, layer.mlp
        lp = dict(
            wq=a.q_proj.weight._value, wk=a.k_proj.weight._value,
            wv=a.v_proj.weight._value, wo=a.o_proj.weight._value,
            qn=a.q_norm.weight._value, kn=a.k_norm.weight._value,
            ln1=layer.post_attention_layernorm.weight._value,
            ln2=layer.post_feedforward_layernorm.weight._value)
        if cfg.is_sparse(l):
            lp.update(router=m.gate.weight._value,
                      router_bias=m.gate.e_score_correction_bias._value,
                      gate_up=m.experts.gate_up_proj._value,
                      down=m.experts.down_proj._value)
            m = m.shared_experts
        lp.update(wg=m.gate_proj.weight._value, wu=m.up_proj.weight._value,
                  wd=m.down_proj.weight._value)
        layers.append(lp)
    return dict(
        embed=model.exaone.embed_tokens.weight._value,
        norm=model.exaone.norm.weight._value,
        head=model.lm_head.weight._value,
        layers=layers,
        nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
        dh=cfg.head_dim, eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        specs=tuple(cfg.layer_spec(l)
                    for l in range(cfg.num_hidden_layers)),
        prefill="flash",
        moe=dict(top_k=cfg.num_experts_per_tok,
                 scale=cfg.routed_scaling_factor,
                 norm_topk=cfg.norm_topk_prob, first=cfg.experts_held[0],
                 count=cfg.experts_held[1], num_experts=cfg.num_experts),
    )


def _decode_family(model):
    """(params, cached_forward) for a supported causal-LM family."""
    if hasattr(model, "exaone"):
        return _exaone_decode_params(model), None
    if hasattr(model, "llama"):
        return _llama_decode_params(model), _cached_forward
    if hasattr(model, "gpt"):
        return _gpt_decode_params(model), _gpt_cached_forward
    from .ernie_moe import ErnieMoeForCausalLM

    if isinstance(model, ErnieMoeForCausalLM):
        return _ernie_decode_params(model), _cached_forward
    raise TypeError(
        f"generate() supports the Llama, GPT and ERNIE-MoE families; "
        f"got {type(model).__name__}")


def _head_logits(p, hidden):
    """LM-head logits; tied heads reuse the embedding in-graph."""
    if p.get("tied_head"):
        return hidden @ p["embed"].T
    return hidden @ p["head"]


def _cached_attention(q, k, v, cache, pos, visible, n_rep):
    """Shared cache-update + masked-softmax attention core: writes the
    new k/v at ``pos``, expands GQA kv heads by ``n_rep``, returns
    (context [B, T, nh*dh], updated cache). One implementation for
    every decode family so the mask/softmax/scale semantics can't
    drift."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t = q.shape[:2]
    dh = q.shape[-1]
    ck, cv = cache
    # pos may be traced int32 (speculative decode); literal indices must
    # match its dtype exactly under jax_enable_x64
    z = jnp.int32(0)
    pos_i = jnp.asarray(pos, jnp.int32)
    ck = lax.dynamic_update_slice(ck, k, (z, pos_i, z, z))
    cv = lax.dynamic_update_slice(cv, v, (z, pos_i, z, z))
    kk = jnp.repeat(ck, n_rep, axis=2) if n_rep > 1 else ck
    vv = jnp.repeat(cv, n_rep, axis=2) if n_rep > 1 else cv
    logits = jnp.einsum("bthd,bshd->bhts", q, kk,
                        preferred_element_type=jnp.float32)
    logits = logits * (dh ** -0.5)
    # visible: [1 or B, T, S_max] — broadcast over heads
    logits = jnp.where(visible[:, None, :, :], logits,
                       jnp.float32(-1e30))
    attn = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhts,bshd->bthd", attn, vv).reshape(b, t, -1)
    return ctx, (ck, cv)


def _sample_token(logits, key, *, do_sample, temperature, top_k, top_p):
    """logits [B, V] -> token ids [B]."""
    import jax
    import jax.numpy as jnp

    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / jnp.float32(max(temperature, 1e-6))
    v = logits.shape[-1]
    if top_k and top_k > 0 and top_k < v:
        kth = jnp.sort(logits, axis=-1)[:, v - top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:  # top_p=0.0 means keep-only-the-best, not "off"
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix whose mass exceeds top_p (always
        # keep the best token)
        cutoff_idx = jnp.sum((cum < top_p).astype(jnp.int32), axis=-1)
        kth = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _sample_slot_tokens(logits, temps, key):
    """Per-row mixed greedy/sampled decode for the serving engine:
    logits [B, V] and per-slot temperatures [B] (0.0 = greedy for that
    row) -> token ids [B]. Rows sample and argmax in one fused graph so
    a batch mixing greedy and sampled streams stays a single trace —
    this is the in-scan sampling step of the fused decode burst too,
    so it must remain shape-stable and key-pure."""
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(
        key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def _prep_decode(model, p, t0, max_new_tokens):
    """Shared decode-path setup (ONE copy for the greedy/beam/paged
    drivers): validate the learned-position table can hold the target
    length, split params into STATIC scalars (shapes depend on them)
    vs jit-argument arrays, and return the per-model jit cache."""
    max_pos = p.get("max_positions")
    if max_pos is not None and t0 + max_new_tokens > max_pos:
        raise ValueError(
            f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) = "
            f"{t0 + max_new_tokens} exceeds the learned position table "
            f"(max_position_embeddings={max_pos}); jnp.take would "
            f"silently clamp and repeat the last position embedding")
    static_cfg = {k: v for k, v in p.items()
                  if not hasattr(v, "dtype") and not isinstance(v, list)}
    arrays = {k: v for k, v in p.items() if k not in static_cfg}
    cache = model.__dict__.setdefault("_generation_jit_cache", {})
    return static_cfg, arrays, cache


def _check_left_padded(ids_np, pad: int):
    """Leading-pad counts [B]; reject pads anywhere but a left run."""
    b, t0 = ids_np.shape
    is_pad = ids_np == pad
    pads = np.argmax(~is_pad, axis=1).astype(np.int32)
    pads = np.where(is_pad.all(axis=1), t0, pads)
    if (pads >= t0).any():
        raise ValueError("generate: a prompt row is entirely padding")
    for r in range(b):
        if is_pad[r, pads[r]:].any():
            raise ValueError(
                "generate(pad_token_id=...) expects LEFT-padded prompts; "
                f"row {r} has pad tokens after its first real token")
    return pads


def generate(model, input_ids, max_new_tokens: int = 32,
             do_sample: bool = False, temperature: float = 1.0,
             top_k: int = 0, top_p: float = 1.0,
             eos_token_id: Optional[int] = None, seed: int = 0,
             pad_token_id: Optional[int] = None, paged: bool = False,
             block_size: int = 64, num_blocks: Optional[int] = None,
             num_beams: int = 1,
             length_penalty: float = 0.0, repetition_penalty: float = 1.0,
             min_length: int = 0):
    """Decode ``max_new_tokens`` from a Llama- or GPT-family causal
    LM with a KV cache; the whole loop is ONE jitted scan. Returns
    ``[B, prompt_len + max_new_tokens]`` (prompt included); positions
    after an emitted ``eos_token_id`` are filled with eos.

    ``pad_token_id``: enables LEFT-padded mixed-length prompts (each
    row decodes at its own logical positions). ``paged=True`` decodes
    over a paged/block KV cache via the serving ``block_mha_p`` program
    (Llama and GPT families; composes with ragged prompts).
    ``num_blocks`` caps the paged pool size: the call FAILS LOUDLY
    (``ValueError`` naming required vs available blocks) when the
    batch's KV working set cannot fit, instead of clamping the block
    table and silently gathering another row's cache — the
    ``serve.BlockPool`` exhaustion contract applied to the library
    call (``None`` sizes the pool exactly to the batch).
    ``num_beams > 1``: beam search (reference surface:
    nn.BeamSearchDecoder / ecosystem generate), ranked by sum logprob /
    len**``length_penalty`` (0.0 = no length normalization).
    ``repetition_penalty`` (CTRL-style: seen tokens' logits divided by
    the factor when positive, multiplied when negative — prompt tokens
    count as seen) and ``min_length`` (eos masked out for the first
    ``min_length`` new tokens) apply to the greedy/sampling paths."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ids = input_ids._value if isinstance(input_ids, Tensor) \
        else jnp.asarray(np.asarray(input_ids))
    ids = ids.astype(jnp.int32)
    if ids.ndim != 2:
        raise ValueError("generate expects [batch, prompt_len] input_ids")
    b, t0 = ids.shape
    if max_new_tokens <= 0:
        return Tensor._from_value(ids)
    pads_np = None
    if pad_token_id is not None:
        pads_np = _check_left_padded(np.asarray(ids), int(pad_token_id))
        if not pads_np.any():
            pads_np = None                    # no row is actually padded
    if repetition_penalty <= 0.0:
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty}")
    if length_penalty != 0.0 and num_beams <= 1:
        raise ValueError(
            "generate: length_penalty ranks beam-search hypotheses; it "
            "has no effect with num_beams=1 — refusing to silently "
            "ignore it")
    if num_blocks is not None and not paged:
        # checked BEFORE the beam early-return so num_beams>1 cannot
        # silently swallow a num_blocks the caller thought was in force
        raise ValueError(
            "generate: num_blocks sizes the paged KV pool; it has no "
            "effect without paged=True — refusing to silently ignore it")
    if num_beams > 1:
        if do_sample:
            raise ValueError(
                "generate: num_beams > 1 is deterministic beam search; "
                "it does not compose with do_sample")
        if paged or pads_np is not None:
            raise NotImplementedError(
                "generate: beam search runs on the dense same-length "
                "cache path (no paged=True / ragged prompts)")
        if repetition_penalty != 1.0 or min_length:
            raise NotImplementedError(
                "generate: repetition_penalty/min_length apply to the "
                "greedy/sampling paths, not beam search")
        return _generate_beam(model, ids, max_new_tokens=max_new_tokens,
                              num_beams=num_beams,
                              eos_token_id=eos_token_id,
                              length_penalty=length_penalty)
    if paged:
        if repetition_penalty != 1.0 or min_length:
            raise NotImplementedError(
                "generate: repetition_penalty/min_length run on the "
                "dense cache path (no paged=True)")
        return _generate_paged(model, ids, pads_np,
                               max_new_tokens=max_new_tokens,
                               do_sample=do_sample, temperature=temperature,
                               top_k=top_k, top_p=top_p,
                               eos_token_id=eos_token_id, seed=seed,
                               block_size=block_size,
                               num_blocks=num_blocks)
    if min_length > 0 and eos_token_id is None:
        # the beam/paged branches above already reject min_length loudly;
        # on the greedy/sampling path it works by masking eos, so with no
        # eos it would be a silent no-op — refuse instead (the module's
        # no-silently-ignored-arguments posture)
        raise ValueError(
            "generate: min_length works by masking the eos token for the "
            "first min_length new tokens; it has no effect with "
            "eos_token_id=None — refusing to silently ignore it")
    p, fwd = _decode_family(model)
    if pads_np is not None and any("moe" in lp for lp in p["layers"]):
        raise NotImplementedError(
            "generate: ragged (left-padded) prompts are not supported "
            "for MoE models — pad rows would consume expert capacity, "
            "so a padded row could not reproduce its solo decode")
    s_max = t0 + max_new_tokens
    nkv, dh, L = p["nkv"], p["dh"], len(p["layers"])
    dtype = p["embed"].dtype
    eos = -1 if eos_token_id is None else int(eos_token_id)
    static_cfg, arrays, cache = _prep_decode(model, p, t0, max_new_tokens)

    rep = float(repetition_penalty)
    min_new = int(min_length)

    def _run(arrs, ids, pads, key):
        p = {**arrs, **static_cfg}
        vocab = p["embed"].shape[0]

        def penalize(logits, presence, i):
            """CTRL repetition penalty over seen tokens + min-length
            eos mask; identity when both knobs are off (rep==1, the
            common case, compiles to nothing)."""
            if rep != 1.0:
                scaled = jnp.where(logits > 0, logits / rep, logits * rep)
                logits = jnp.where(presence, scaled, logits)
            if min_new > 0 and eos >= 0:
                blocked = jnp.full_like(logits[:, eos], -jnp.inf)
                logits = logits.at[:, eos].set(
                    jnp.where(i < min_new, blocked, logits[:, eos]))
            return logits

        # tokens already in the prompt count as seen (pad runs don't)
        row = jnp.arange(b)[:, None]
        seen_ok = (jnp.ones((b, t0), bool) if pads is None
                   else jnp.arange(t0)[None, :] >= pads[:, None])
        presence0 = jnp.zeros((b, vocab), bool).at[row, ids].max(seen_ok)
        caches = [(jnp.zeros((b, s_max, nkv, dh), dtype),
                   jnp.zeros((b, s_max, nkv, dh), dtype))
                  for _ in range(L)]
        hidden, caches = fwd(p, ids, caches, 0, s_max, pads=pads)
        logits0 = penalize(
            _head_logits(p, hidden).astype(jnp.float32), presence0, 0)
        key, sub = jax.random.split(key)
        tok0 = _sample_token(logits0, sub, do_sample=do_sample,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p)
        done0 = tok0 == eos
        presence0 = presence0.at[jnp.arange(b), tok0].set(True)
        flat_caches = [c for pair in caches for c in pair]

        def step(carry, i):
            # the carried token is the sequence element at absolute
            # position t0 + i - 1: that is its cache slot and its RoPE
            # position (feeding it one slot later leaves the all-zeros
            # slot t0 visible and shifts every rope angle — caught by
            # review, pinned by the multi-token oracle test)
            tok, done, presence, key, *flat = carry
            caches_ = [(flat[2 * j], flat[2 * j + 1]) for j in range(L)]
            hidden, caches_ = fwd(
                p, tok[:, None], caches_, t0 + i - 1, s_max, pads=pads)
            logits = penalize(
                _head_logits(p, hidden).astype(jnp.float32), presence, i)
            key, sub = jax.random.split(key)
            nxt = _sample_token(logits, sub, do_sample=do_sample,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p)
            nxt = jnp.where(done, jnp.int32(eos), nxt)
            done = done | (nxt == eos)
            presence = presence.at[jnp.arange(b), nxt].set(True)
            flat_ = [c for pair in caches_ for c in pair]
            return (nxt, done, presence, key, *flat_), tok

        (last, _done, _pres, _key, *_rest), toks = lax.scan(
            step, (tok0, done0, presence0, key, *flat_caches),
            jnp.arange(1, max_new_tokens))
        toks = jnp.concatenate([toks.swapaxes(0, 1), last[:, None]], axis=1)
        return jnp.concatenate([ids, toks], axis=1)

    # compiled-step cache on the model: params ride as jit ARGUMENTS
    # (weights update between calls; baking them as closure constants
    # would both bloat the executable and force a retrace per call)
    ragged = pads_np is not None
    # dtype is part of the key: _run closes over the cache dtype/layer
    # count captured at first trace — a model.bfloat16() after a float32
    # generate must not reuse the stale closure
    sig = (b, t0, max_new_tokens, do_sample, float(temperature),
           int(top_k), float(top_p), eos, ragged, str(dtype), L,
           rep, min_new, p.get("moe_statics"))
    fn = cache.get(sig)
    if fn is None:
        fn = jax.jit(_run, static_argnums=() if ragged else (2,))
        cache[sig] = fn
    pads_arg = jnp.asarray(pads_np) if ragged else None
    out = fn(arrays, ids, pads_arg, jax.random.PRNGKey(seed))
    return Tensor._from_value(out)


def _generate_beam(model, ids, *, max_new_tokens, num_beams,
                   eos_token_id, length_penalty=0.0):
    """Beam search over the SAME cached single-jit scan as greedy: the
    batch dim carries B*K beam rows, each tick forwards every beam one
    token, expands to K*V candidates, keeps the top K per batch row,
    and reorders the KV caches by each survivor's parent beam. Finished
    beams (emitted eos) are frozen: their only continuation is eos at
    zero added logprob. Returns each row's highest-sum-logprob beam.

    ``length_penalty`` != 0 ranks final beams by
    sum_logprob / len(generated)**length_penalty (GNMT normalization;
    0.0 keeps the raw sum — the oracle-pinned default).

    Reference surface: nn/decode.py BeamSearchDecoder/dynamic_decode is
    the seq2seq cell path; this is the decoder-only LLM analog (the
    reference ecosystem's model.generate(decode_strategy=
    "beam_search"))."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    p, fwd = _decode_family(model)
    b, t0 = ids.shape
    K = int(num_beams)
    s_max = t0 + max_new_tokens
    vocab = p["embed"].shape[0]
    if K > vocab:
        raise ValueError(f"num_beams ({K}) > vocab size ({vocab})")
    nkv, dh, L = p["nkv"], p["dh"], len(p["layers"])
    dtype = p["embed"].dtype
    eos = -1 if eos_token_id is None else int(eos_token_id)
    static_cfg, arrays, cache = _prep_decode(model, p, t0, max_new_tokens)

    def _run(arrs, ids):
        p = {**arrs, **static_cfg}
        # eos-continuation row for finished beams: only eos, at +0
        frozen = jnp.full((vocab,), -jnp.inf)
        if eos >= 0:
            frozen = frozen.at[eos].set(0.0)

        # ---- prefill on the B prompt rows, then expand to K beams ----
        caches = [(jnp.zeros((b, s_max, nkv, dh), dtype),
                   jnp.zeros((b, s_max, nkv, dh), dtype))
                  for _ in range(L)]
        hidden, caches = fwd(p, ids, caches, 0, s_max)
        lp0 = jax.nn.log_softmax(
            _head_logits(p, hidden).astype(jnp.float32), axis=-1)
        scores, tok0 = lax.top_k(lp0, K)               # [B, K] each
        tok0 = tok0.astype(jnp.int32)
        done = tok0 == eos
        gen_len = jnp.ones((b, K), jnp.int32)          # tokens incl. eos
        flat = [jnp.repeat(c, K, axis=0)               # [B*K, S, kvh, dh]
                for pair in caches for c in pair]
        tok_buf = jnp.full((b, K, max_new_tokens), eos, jnp.int32)
        tok_buf = tok_buf.at[:, :, 0].set(tok0)

        def reorder(arr, parent):
            """[B*K, ...] gathered by each survivor's parent beam."""
            v = arr.reshape((b, K) + arr.shape[1:])
            idx = parent.reshape((b, K) + (1,) * (v.ndim - 2))
            return jnp.take_along_axis(v, idx, axis=1).reshape(arr.shape)

        def step(carry, i):
            tok, scores, done, gen_len, tok_buf, *flat = carry
            caches_ = [(flat[2 * j], flat[2 * j + 1]) for j in range(L)]
            hidden, caches_ = fwd(
                p, tok.reshape(b * K, 1), caches_, t0 + i - 1, s_max)
            lp = jax.nn.log_softmax(
                _head_logits(p, hidden).astype(jnp.float32),
                axis=-1).reshape(b, K, vocab)
            lp = jnp.where(done[:, :, None], frozen[None, None, :], lp)
            cand = (scores[:, :, None] + lp).reshape(b, K * vocab)
            scores, idx = lax.top_k(cand, K)           # [B, K]
            parent = (idx // vocab).astype(jnp.int32)
            token = (idx % vocab).astype(jnp.int32)
            flat_ = [reorder(c, parent)
                     for pair in caches_ for c in pair]
            parent_done = jnp.take_along_axis(done, parent, axis=1)
            done = parent_done | (token == eos)
            gen_len = jnp.take_along_axis(gen_len, parent, axis=1) \
                + (~parent_done).astype(jnp.int32)
            tok_buf = jnp.take_along_axis(
                tok_buf, parent[:, :, None], axis=1).at[:, :, i].set(token)
            return (token, scores, done, gen_len, tok_buf, *flat_), ()

        (_tok, scores, _done, gen_len, tok_buf, *_rest), _ = lax.scan(
            step, (tok0, scores, done, gen_len, tok_buf, *flat),
            jnp.arange(1, max_new_tokens))
        if length_penalty != 0.0:
            scores = scores / (gen_len.astype(jnp.float32)
                               ** float(length_penalty))
        best = jnp.argmax(scores, axis=1)              # [B]
        out = jnp.take_along_axis(
            tok_buf, best[:, None, None], axis=1)[:, 0, :]
        return jnp.concatenate([ids, out], axis=1)

    sig = ("beam", b, t0, max_new_tokens, K, eos, str(dtype), L,
           float(length_penalty), p.get("moe_statics"))
    fn = cache.get(sig)
    if fn is None:
        fn = jax.jit(_run)
        cache[sig] = fn
    return Tensor._from_value(fn(arrays, ids))


def generate_speculative(model, draft_model, input_ids,
                         max_new_tokens: int = 32, gamma: int = 4,
                         eos_token_id: Optional[int] = None):
    """Speculative GREEDY decoding: ``draft_model`` proposes ``gamma``
    tokens per round with its own cached scan, the target verifies all
    of them in ONE batched cached forward, and the longest matching
    prefix plus the target's own next token are accepted — so the
    output is EXACTLY ``model``'s greedy decode (the acceptance rule
    only ever keeps tokens the target itself would have emitted), while
    each accepted draft token saves one full target forward.

    The whole loop is one jitted ``lax.while_loop``; cache "rollback"
    after a rejection is free because the dense cache is addressed by
    position — stale slots are simply overwritten before they become
    visible. Batch size 1 (the latency-bound serving regime speculative
    decoding exists for). Reference surface: the ecosystem's
    speculative/draft-model decoding over the same serving cache ops.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    ids = input_ids._value if isinstance(input_ids, Tensor) \
        else jnp.asarray(np.asarray(input_ids))
    ids = ids.astype(jnp.int32)
    if ids.ndim != 2 or ids.shape[0] != 1:
        raise ValueError(
            "generate_speculative expects [1, prompt_len] input_ids "
            "(batch 1 — the latency-bound regime)")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    t0 = ids.shape[1]
    if max_new_tokens <= 0:
        return Tensor._from_value(ids)
    pt, fwd_t = _decode_family(model)
    pd, fwd_d = _decode_family(draft_model)
    if pt.get("moe_statics") or pd.get("moe_statics"):
        raise NotImplementedError(
            "generate_speculative supports dense families only: a MoE "
            "model's expert capacity is computed per call, so the "
            "multi-token verify window could drop tokens that the "
            "one-token-per-step greedy decode keeps, breaking the "
            "exact-equality guarantee")
    if pt["embed"].shape[0] != pd["embed"].shape[0]:
        raise ValueError(
            f"target and draft vocabularies differ "
            f"({pt['embed'].shape[0]} vs {pd['embed'].shape[0]})")
    # buffer leaves room for one full overshoot round past max_new
    cap = max_new_tokens + gamma + 1
    s_max = t0 + cap
    eos = -1 if eos_token_id is None else int(eos_token_id)
    st_t, arr_t, cache = _prep_decode(model, pt, t0, cap)
    st_d, arr_d, _ = _prep_decode(draft_model, pd, t0, cap)
    L_t, L_d = len(pt["layers"]), len(pd["layers"])

    def _mk_caches(p, L):
        return [(jnp.zeros((1, s_max, p["nkv"], p["dh"]),
                           p["embed"].dtype),
                 jnp.zeros((1, s_max, p["nkv"], p["dh"]),
                           p["embed"].dtype)) for _ in range(L)]

    def _run(at, ad, ids):
        pt = {**at, **st_t}
        pd = {**ad, **st_d}

        # prefill BOTH models; target's argmax is the first pending tok
        ct = _mk_caches(pt, L_t)
        cd = _mk_caches(pd, L_d)
        hid, ct = fwd_t(pt, ids, ct, 0, s_max)
        pending = jnp.argmax(_head_logits(pt, hid),
                             axis=-1).astype(jnp.int32)     # [1]
        _hd, cd = fwd_d(pd, ids, cd, 0, s_max)
        out_buf = jnp.full((1, cap), eos if eos >= 0 else 0, jnp.int32)
        flat_t = [c for pair in ct for c in pair]
        flat_d = [c for pair in cd for c in pair]

        def cond(state):
            n_gen = state[0]
            return n_gen < max_new_tokens

        def body(state):
            n_gen, pending, out_buf, *flat = state
            ct_ = [(flat[2 * j], flat[2 * j + 1]) for j in range(L_t)]
            off = 2 * L_t
            cd_ = [(flat[off + 2 * j], flat[off + 2 * j + 1])
                   for j in range(L_d)]
            P = t0 + n_gen                 # pending token's position

            # --- draft phase: gamma greedy tokens from the draft ---
            def dstep(carry, i):
                tok, *dflat = carry
                dc = [(dflat[2 * j], dflat[2 * j + 1])
                      for j in range(L_d)]
                hid, dc = fwd_d(pd, tok[:, None], dc, P + i, s_max)
                nxt = jnp.argmax(_head_logits(pd, hid),
                                 axis=-1).astype(jnp.int32)
                dflat_ = [c for pair in dc for c in pair]
                return (nxt, *dflat_), nxt

            dflat0 = [c for pair in cd_ for c in pair]
            (last_d, *dflat_), drafts = lax.scan(
                dstep, (pending, *dflat0), jnp.arange(gamma))
            drafts = drafts[:, 0]                         # [gamma]
            cd_ = [(dflat_[2 * j], dflat_[2 * j + 1])
                   for j in range(L_d)]
            # forward d_gamma too (logits discarded): a fully-accepted
            # round advances past slot P+gamma, which would otherwise
            # stay an unwritten-but-visible hole in the draft's cache
            # and silently corrupt every later draft proposal
            _hd, cd_ = fwd_d(pd, last_d[:, None], cd_, P + gamma, s_max)

            # --- verify: ONE target forward over pending + drafts ---
            window = jnp.concatenate([pending, drafts])[None, :]
            hid_all, ct_ = fwd_t(pt, window, ct_, P, s_max,
                                 return_all=True)
            t_preds = jnp.argmax(
                _head_logits(pt, hid_all[0]), axis=-1
            ).astype(jnp.int32)                           # [gamma+1]

            # longest matching prefix, then the target's own token:
            # this round emits [pending, d_1..d_a] (a+1 tokens, all of
            # them the target's own greedy choices) and the fix/bonus
            # token y becomes the next pending
            matches = t_preds[:gamma] == drafts
            a = jnp.sum(jnp.cumprod(matches.astype(jnp.int32)))
            y = t_preds[a]
            # the verify window IS the emit candidate list; slots past
            # a+1 hold rejected drafts that the NEXT round overwrites
            # (the loop exits only once n_gen >= max_new, so every slot
            # below max_new ends up final)
            out_buf = lax.dynamic_update_slice(
                out_buf, window, (jnp.int32(0), n_gen))
            n_gen = (n_gen + a + 1).astype(jnp.int32)
            flat_t_ = [c for pair in ct_ for c in pair]
            flat_d_ = [c for pair in cd_ for c in pair]
            return (n_gen, y[None], out_buf, *flat_t_, *flat_d_)

        state = (jnp.int32(0), pending, out_buf, *flat_t, *flat_d)
        state = lax.while_loop(cond, body, state)
        out = state[2][:, :max_new_tokens]
        if eos >= 0:
            # greedy-equivalent eos semantics: everything after the
            # first eos is eos
            seen = jnp.cumsum((out == eos).astype(jnp.int32), axis=1)
            prior = seen - (out == eos).astype(jnp.int32)
            out = jnp.where(prior > 0, jnp.int32(eos), out)
        return jnp.concatenate([ids, out], axis=1)

    # the compiled fn closes over BOTH models' statics only (weights
    # ride as jit arguments), so the key is the statics themselves — a
    # recreated draft with identical architecture reuses the executable,
    # and no stale closure can survive an id() reuse
    sig = ("spec", t0, max_new_tokens, gamma, eos,
           str(pt["embed"].dtype), L_t, str(pd["embed"].dtype), L_d,
           tuple(sorted((k, v) for k, v in st_t.items())),
           tuple(sorted((k, v) for k, v in st_d.items())))
    fn = cache.get(sig)
    if fn is None:
        fn = jax.jit(_run)
        cache[sig] = fn
    out = fn(arr_t, arr_d, ids)
    return Tensor._from_value(out)


def _paged_block_tables(b, s_max, block_size, num_blocks=None):
    """Disjoint row-major block allocation for a ``generate`` batch:
    row ``r`` owns blocks ``[r*blocks_per_seq, (r+1)*blocks_per_seq)``.

    Raises a CLEAR error when a caller-capped pool (``num_blocks``)
    cannot hold the batch's KV working set — the previous behavior was
    an out-of-range block id silently clamped by the gather, reading
    ANOTHER row's cache (ISSUE 14 satellite; regression-tested)."""
    blocks_per_seq = -(-s_max // block_size)
    needed = b * blocks_per_seq
    if num_blocks is not None and int(num_blocks) < needed:
        raise ValueError(
            f"generate(paged=True): KV block pool exhausted before "
            f"decode could start — the batch needs {needed} blocks "
            f"({b} rows x {blocks_per_seq} blocks of {block_size} "
            f"tokens for prompt+max_new_tokens={s_max}) but "
            f"num_blocks={int(num_blocks)}. Grow the pool, shrink the "
            f"batch/max_new_tokens, or serve the requests through "
            f"paddle_tpu.serve.ServeEngine, which queues and preempts "
            f"instead of failing")
    total = needed if num_blocks is None else int(num_blocks)
    tables = (np.arange(needed, dtype=np.int32)
              .reshape(b, blocks_per_seq))
    return tables, total


def _generate_paged(model, ids, pads_np, *, max_new_tokens, do_sample,
                    temperature, top_k, top_p, eos_token_id, seed,
                    block_size, num_blocks=None):
    """Paged/block-KV-cache decode (Llama and GPT families): the
    prefill packs each row's REAL tokens left-aligned into a varlen
    batch and one ``block_mha_p`` call per layer writes them straight
    into the block pool; each scan tick appends one token per row
    through the same program's decode branch. Cache memory is
    per-LOGICAL-token (pads never enter the pool), and the attention
    view is gathered through the block table exactly like the
    reference's serving kernel (block_multihead_attention.py:19). RoPE
    rides inside the block program (Llama); learned positions are added
    at the embedding by logical position (GPT).

    MEASURED (tools/paged_decode_probe.py + paged_kernel_probe.py,
    v5e): the block-table gather/scatter program is ~10x slower than
    the dense scan at 645M serving shapes, and even jax's official
    Pallas paged_attention kernel (numerically equivalent, 1.6x faster
    than the gather) remains ~6x the dense per-layer budget at short
    contexts — paged attention is overhead-bound there. Use paged for
    its cache semantics (ragged pools, pad-free memory, the reference
    serving interface); the dense scan is the throughput path."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..incubate.nn.functional import _rope_tables
    from ..incubate.nn.functional.inference_attention import _bmha_fwd

    if not hasattr(model, "llama") and not hasattr(model, "gpt"):
        raise NotImplementedError(
            "paged=True decode supports the Llama and GPT families; "
            "MoE models use the dense cache path")
    p, _dense_fwd = _decode_family(model)
    is_llama = hasattr(model, "llama")
    b, t0 = ids.shape
    nh, nkv, dh = p["nh"], p["nkv"], p["dh"]
    L = len(p["layers"])
    dtype = p["embed"].dtype
    eos = -1 if eos_token_id is None else int(eos_token_id)
    s_max = t0 + max_new_tokens
    static_cfg, arrays, cache = _prep_decode(model, p, t0, max_new_tokens)
    # loud pool-exhaustion contract (see _paged_block_tables): a capped
    # pool that cannot hold the batch fails HERE, not as a clamped
    # cross-row gather mid-decode
    tables_np, nb = _paged_block_tables(b, s_max, block_size, num_blocks)

    def _run(arrs, ids, pads, key):
        p = {**arrs, **static_cfg}
        tables = jnp.asarray(tables_np)
        enc = (jnp.full((b,), t0, jnp.int32) if pads is None
               else (t0 - pads).astype(jnp.int32))
        # pack real tokens left-aligned per row: row b's segment is
        # [b*t0, b*t0 + enc_b); the clipped tail duplicates are masked
        # out of the cache/attention by enc
        shift = (jnp.zeros((b, 1), jnp.int32) if pads is None
                 else pads[:, None])
        gather_cols = jnp.minimum(shift + jnp.arange(t0)[None, :], t0 - 1)
        packed = jnp.take_along_axis(ids, gather_cols, axis=1).reshape(-1)
        starts = jnp.arange(b, dtype=jnp.int32) * t0
        if is_llama:
            cos_full, sin_full = _rope_tables(s_max, dh, p["theta"], True,
                                              jnp.float32)
            # reference rope layout [2, B, S, 1, D]
            rope = jnp.stack([
                jnp.broadcast_to(cos_full[None, :, None, :],
                                 (b, s_max, 1, dh)),
                jnp.broadcast_to(sin_full[None, :, None, :],
                                 (b, s_max, 1, dh)),
            ]).astype(jnp.float32)
        else:
            rope = jnp.zeros((1,), jnp.float32)   # unused (use_rope=False)
        # packed-token logical positions: left-aligned row segments, so
        # slot j of every segment is position j (prefill); decode steps
        # pass each row's current length instead
        pos_prefill = jnp.tile(jnp.arange(t0, dtype=jnp.int32), b)

        def rms(h, g):
            return _rms(h, g, p["eps"], dtype)

        def ln(h, g, bb):
            return _ln(h, g, bb, p["eps"], dtype)

        def attn(qkv, kc, vc, enc_now, dec_now, cu, win_tables):
            return _bmha_fwd(
                qkv, kc, vc, enc_now, dec_now, cu, win_tables, rope,
                num_heads=nh, kv_num_heads=nkv, block_size=block_size,
                max_seq_len=s_max, use_neox=True, use_rope=is_llama)

        def stack_step(tokens_flat, caches, enc_now, dec_now, cu,
                       pos_tok, win_tables):
            """One forward through all layers on packed rows [T, H];
            returns (hidden rows [T, H], new caches). The norm/FFN math
            is the SHARED per-family helpers (_rms/_ln/_llama_ffn/
            _gpt_ffn) — same source as the dense path, so the two cache
            layouts can't drift."""
            x = jnp.take(p["embed"], tokens_flat, axis=0)
            if not is_llama:
                x = x + jnp.take(p["wpe"], pos_tok, axis=0)
            new_caches = []
            for lp, (kc, vc) in zip(p["layers"], caches):
                if is_llama:
                    h = rms(x, lp["ln1"])
                    qkv = jnp.concatenate(
                        [h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]],
                        axis=-1)
                else:
                    h = ln(x, lp["ln1_w"], lp["ln1_b"])
                    # fused qkv weight is already laid out q|k|v
                    qkv = h @ lp["wqkv"] + lp["bqkv"]
                ctx, _qkv, kc, vc = attn(qkv, kc, vc, enc_now, dec_now,
                                         cu, win_tables)
                new_caches.append((kc, vc))
                if is_llama:
                    x = x + ctx.astype(dtype) @ lp["wo"]
                    x = x + _llama_ffn(rms(x, lp["ln2"]), lp, dtype)
                else:
                    x = x + ctx.astype(dtype) @ lp["wo"] + lp["bo"]
                    x = x + _gpt_ffn(ln(x, lp["ln2_w"], lp["ln2_b"]),
                                     lp, dtype)
            if is_llama:
                return rms(x, p["norm"]), new_caches
            return ln(x, p["normf_w"], p["normf_b"]), new_caches

        caches = [(jnp.zeros((nb, nkv, block_size, dh), dtype),
                   jnp.zeros((nb, nkv, block_size, dh), dtype))
                  for _ in range(L)]
        zeros_b = jnp.zeros((b,), jnp.int32)
        # prefill attends through a PROMPT-SIZED view of the block table:
        # the full table's padded window would cost
        # (ceil(s_max/bs)/ceil(t0/bs))^2 x the live attention FLOPs; the
        # writes land in the same pool either way
        prompt_blocks = -(-t0 // block_size)
        hidden, caches = stack_step(packed, caches, enc, zeros_b, starts,
                                    pos_prefill,
                                    tables[:, :prompt_blocks])
        last_rows = starts + enc - 1
        logits0 = _head_logits(p, hidden[last_rows])
        key, sub = jax.random.split(key)
        tok0 = _sample_token(logits0, sub, do_sample=do_sample,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p)
        done0 = tok0 == eos
        flat = [c for pair in caches for c in pair]
        dec_starts = jnp.arange(b, dtype=jnp.int32)

        def step(carry, i):
            tok, done, key, *flat = carry
            caches_ = [(flat[2 * j], flat[2 * j + 1]) for j in range(L)]
            # the carried token is each row's element at logical
            # position enc + i - 1: its append slot and rope/wpe angle
            hidden, caches_ = stack_step(
                tok, caches_, zeros_b, enc + (i - 1), dec_starts,
                enc + (i - 1), tables)
            logits = _head_logits(p, hidden)
            key, sub = jax.random.split(key)
            nxt = _sample_token(logits, sub, do_sample=do_sample,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p)
            nxt = jnp.where(done, jnp.int32(eos), nxt)
            done = done | (nxt == eos)
            flat_ = [c for pair in caches_ for c in pair]
            return (nxt, done, key, *flat_), tok

        (last, _done, _key, *_rest), toks = lax.scan(
            step, (tok0, done0, key, *flat),
            jnp.arange(1, max_new_tokens))
        toks = jnp.concatenate([toks.swapaxes(0, 1), last[:, None]], axis=1)
        return jnp.concatenate([ids, toks], axis=1)

    ragged = pads_np is not None
    sig = ("paged", b, t0, max_new_tokens, do_sample, float(temperature),
           int(top_k), float(top_p), eos, ragged, int(block_size),
           int(nb), str(dtype), L)
    fn = cache.get(sig)
    if fn is None:
        fn = jax.jit(_run, static_argnums=() if ragged else (2,))
        cache[sig] = fn
    pads_arg = jnp.asarray(pads_np) if ragged else None
    out = fn(arrays, ids, pads_arg, jax.random.PRNGKey(seed))
    return Tensor._from_value(out)
