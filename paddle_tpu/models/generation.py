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

The layer math is ``models/decoder_stack.py``'s, the one stack
``ServeEngine`` runs too, over the view each family hands over
(``decode_view()``); ``_cached_forward`` adds what is this path's own,
the dense cache's write and its masked softmax. The test suite pins the
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
against its own full-prefix oracle in tests), and SPECULATIVE
draft-and-verify decoding (``generate_speculative``, output exactly
equal to the target's greedy by construction).

Every path here keeps a DENSE cache. Decoding over a paged block pool
is ``serve.ServeEngine``'s and nobody else's (the reference's serving op
``incubate.nn.functional.block_multihead_attention`` stays as the
public op it is).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ..core.tensor import Tensor
from .decoder_stack import embed, head_logits, specs_of, stack_layers

__all__ = ["generate", "generate_speculative"]


def _decode_family(model):
    """The decode view of a causal-LM family (its ``decode_view()``: the
    parameter arrays, the statics and one ``LayerSpec`` a layer)."""
    view = getattr(model, "decode_view", None)
    if view is None:
        raise TypeError(
            f"generate() supports the Llama, GPT and ERNIE-MoE families; "
            f"got {type(model).__name__}")
    p = view()
    specs_of(p)
    return p


def _cached_forward(p, tokens, caches, pos, s_max, pads=None,
                    return_all=False):
    """Forward ``tokens`` [B, T] through the stack at absolute positions
    ``pos..pos+T-1``, reading/updating the per-layer KV caches
    [B, S_max, kvh, dh]. Returns (last-position hidden [B, H], caches) —
    or every position's hidden [B, T, H] with ``return_all`` (the
    speculative verify pass needs all of them). Causal within the new
    tokens; full attention to everything cached before ``pos``.
    ``pads`` [B] (left-pad counts) offsets each row's rope/learned
    positions and blanks its pad slots out of the visibility mask — the
    ragged-prompt path. ``pos`` may be a traced scalar (speculative
    decoding advances it dynamically).

    A caller of ``decoder_stack.stack_layers`` on the flattened
    ``B * T`` rows: what is this path's own is the dense cache, written
    by one ``dynamic_update_slice`` at ``pos``, and the masked softmax
    over all of it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t = tokens.shape
    nh, nkv, dh = p["nh"], p["nkv"], p["dh"]
    n_rep = nh // nkv
    positions = pos + jnp.arange(t)                   # absolute [T]
    if pads is None:
        rel = jnp.broadcast_to(positions[None, :], (b, t))
        # query i (absolute pos+i) may see cache slot j iff j <= pos+i
        slot = jnp.arange(s_max)[None, :]             # [1, S_max]
        visible = (slot <= positions[:, None])[None]  # [1, T, S_max]
    else:
        # per-row logical positions: absolute minus this row's pad run
        rel = jnp.maximum(positions[None, :] - pads[:, None], 0)  # [B, T]
        slot = jnp.arange(s_max)[None, None, :]
        visible = (slot <= positions[None, :, None]) \
            & (slot >= pads[:, None, None])           # [B, T, S_max]
    x, rope = embed(p, tokens.reshape(-1), rel.reshape(-1), s_max)
    # pos may be traced int32 (speculative decode); literal indices must
    # match its dtype exactly under jax_enable_x64
    z = jnp.int32(0)
    pos_i = jnp.asarray(pos, jnp.int32)

    def write_kv(_i, _spec, ck, cv, k, v):
        return (lax.dynamic_update_slice(
                    ck, k.reshape(b, t, nkv, dh), (z, pos_i, z, z)),
                lax.dynamic_update_slice(
                    cv, v.reshape(b, t, nkv, dh), (z, pos_i, z, z)))

    def attn(_i, spec, q, _k, _v, ck, cv):
        if spec.window is not None:
            raise NotImplementedError(
                "generate(): a sliding-window layer wants a band in the "
                "dense mask; such a model is served through ServeEngine")
        kk = jnp.repeat(ck, n_rep, axis=2) if n_rep > 1 else ck
        vv = jnp.repeat(cv, n_rep, axis=2) if n_rep > 1 else cv
        logits = jnp.einsum("bthd,bshd->bhts", q.reshape(b, t, nh, dh), kk,
                            preferred_element_type=jnp.float32)
        logits = logits * (dh ** -0.5)
        # visible: [1 or B, T, S_max] — broadcast over heads
        logits = jnp.where(visible[:, None, :, :], logits,
                           jnp.float32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhts,bshd->bthd", probs, vv).reshape(b * t, -1)

    out, new_caches, _ = stack_layers(p, x, rope, caches, write_kv, attn)
    out = out.reshape(b, t, -1)
    return (out if return_all else out[:, -1, :]), new_caches


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
    """Shared decode-path setup (ONE copy for the greedy, beam and
    speculative drivers): validate the learned-position table can hold
    the target length, split params into STATIC scalars (shapes depend
    on them) vs jit-argument arrays, and return the per-model jit
    cache."""
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
             pad_token_id: Optional[int] = None, num_beams: int = 1,
             length_penalty: float = 0.0, repetition_penalty: float = 1.0,
             min_length: int = 0):
    """Decode ``max_new_tokens`` from a Llama- or GPT-family causal
    LM with a KV cache; the whole loop is ONE jitted scan. Returns
    ``[B, prompt_len + max_new_tokens]`` (prompt included); positions
    after an emitted ``eos_token_id`` are filled with eos.

    ``pad_token_id``: enables LEFT-padded mixed-length prompts (each
    row decodes at its own logical positions).
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
    if num_beams > 1:
        if do_sample:
            raise ValueError(
                "generate: num_beams > 1 is deterministic beam search; "
                "it does not compose with do_sample")
        if pads_np is not None:
            raise NotImplementedError(
                "generate: beam search runs on the dense same-length "
                "cache path (no ragged prompts)")
        if repetition_penalty != 1.0 or min_length:
            raise NotImplementedError(
                "generate: repetition_penalty/min_length apply to the "
                "greedy/sampling paths, not beam search")
        return _generate_beam(model, ids, max_new_tokens=max_new_tokens,
                              num_beams=num_beams,
                              eos_token_id=eos_token_id,
                              length_penalty=length_penalty)
    if min_length > 0 and eos_token_id is None:
        # the beam branch above already rejects min_length loudly;
        # on the greedy/sampling path it works by masking eos, so with no
        # eos it would be a silent no-op — refuse instead (the module's
        # no-silently-ignored-arguments posture)
        raise ValueError(
            "generate: min_length works by masking the eos token for the "
            "first min_length new tokens; it has no effect with "
            "eos_token_id=None — refusing to silently ignore it")
    p = _decode_family(model)
    if pads_np is not None and any(
            s.ffn == "capacity_moe" for s in p["specs"]):
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
        hidden, caches = _cached_forward(p, ids, caches, 0, s_max,
                                         pads=pads)
        logits0 = penalize(
            head_logits(p, hidden).astype(jnp.float32), presence0, 0)
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
            hidden, caches_ = _cached_forward(
                p, tok[:, None], caches_, t0 + i - 1, s_max, pads=pads)
            logits = penalize(
                head_logits(p, hidden).astype(jnp.float32), presence, i)
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

    p = _decode_family(model)
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
        hidden, caches = _cached_forward(p, ids, caches, 0, s_max)
        lp0 = jax.nn.log_softmax(
            head_logits(p, hidden).astype(jnp.float32), axis=-1)
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
            hidden, caches_ = _cached_forward(
                p, tok.reshape(b * K, 1), caches_, t0 + i - 1, s_max)
            lp = jax.nn.log_softmax(
                head_logits(p, hidden).astype(jnp.float32),
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
    pt = _decode_family(model)
    pd = _decode_family(draft_model)
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
        hid, ct = _cached_forward(pt, ids, ct, 0, s_max)
        pending = jnp.argmax(head_logits(pt, hid),
                             axis=-1).astype(jnp.int32)     # [1]
        _hd, cd = _cached_forward(pd, ids, cd, 0, s_max)
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
                hid, dc = _cached_forward(pd, tok[:, None], dc, P + i,
                                          s_max)
                nxt = jnp.argmax(head_logits(pd, hid),
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
            _hd, cd_ = _cached_forward(pd, last_d[:, None], cd_,
                                       P + gamma, s_max)

            # --- verify: ONE target forward over pending + drafts ---
            window = jnp.concatenate([pending, drafts])[None, :]
            hid_all, ct_ = _cached_forward(pt, window, ct_, P, s_max,
                                           return_all=True)
            t_preds = jnp.argmax(
                head_logits(pt, hid_all[0]), axis=-1
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
