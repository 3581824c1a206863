"""Plain reference of the Granite 4.0-H architecture (`model_type:
granitemoehybrid`, the published config of granite-4.0-h-micro): Mamba-2
state-space layers with an attention layer every tenth, a shared SwiGLU MLP
in every layer, no experts.

The forward pass, for token ids (the configuration's file gives the source
of each point that the published config does not carry):

- x = embedding[ids] * `embedding_multiplier`.
- every layer, with m = `residual_multiplier`: x = x + m * mixer(RMSNorm(x));
  x = x + m * MLP(RMSNorm(x)); MLP(h) = W_out (silu(gate) * up) with
  [gate | up] = h W_in (`shared_intermediate_size` each).
- `attention` layers: q, k, v = h W_q, h W_k, h W_v with no bias and NO
  position encoding (`position_embedding_type: nope`); causal softmax of
  q . k * `attention_multiplier` (1/64: not dh^-0.5), a query head reading
  key-value head h // (heads / kv heads); W_o.
- `mamba` layers (Mamba-2, one group): [z | xBC | dt] = h W_in with widths
  inner | inner + 2 n | heads, inner = `mamba_n_heads` x `mamba_d_head`, n =
  `mamba_d_state`; xBC_t = silu(sum_k w_k * xBC_(t - K + 1 + k) + b), a
  causal depthwise convolution of K = `mamba_d_conv` taps computed as K
  shifted adds; [x | B | C] = xBC; dt = softplus(dt + dt_bias) with no
  clamp (`time_step_limit` (0, inf)); A = -exp(A_log); per head p the state
  H (dh x n) moves H_t = exp(dt_t A) H_(t-1) + dt_t x_t (outer) B_t from
  H_(-1) = 0 and reads y_t = H_t C_t + D x_t: a `lax.scan` over the TOKENS,
  the state in float32, no chunks; out = (RMSNorm(y * silu(z)) * w) W_out,
  the gate before the norm and the norm over all `inner` channels.
- a final RMSNorm, then the tied head: logits = x embedding^T /
  `logits_scaling`.

Departures from the published modelling file
(`transformers/models/granitemoehybrid/modeling_granitemoehybrid.py` and
the `modeling_bamba.py` mixer it uses), each by design of a reference:

- float32 throughout with `highest` matmul precision, where the published
  file computes in the checkpoint's bfloat16 (its state update alone in
  float32);
- the recurrence is the scan above, where the published file has a chunked
  scan (`mamba_chunk_size`) for prompts and a one-token step over a cache:
  the three compute one function;
- the convolution's weight is [K, channels] (the benchmark's seeded leaf;
  the published depthwise weight [channels, 1, K] transposed);
- one row at a time, no cache, no batching; attention in query blocks of
  512 so that a long row's scores fit.

The weights stay in the type they were made in (bfloat16, so widening them
is exact) and are widened a layer at a time (one jitted call a layer): 3.2
billion parameters in float32 would not fit beside anything.

`precision="fp8"` is the control: both operands of every matrix product
rounded to float8 e4m3 under a per-tensor absmax scale, the nearest
precision below the configuration's bfloat16; the recurrence itself stays
in float32.

Imports nothing of the program and is handed only the benchmark's own
seeded weights.
"""
from __future__ import annotations

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
PRE = "model.layers."


def stack_params(weights: dict, cfg: dict) -> dict:
    """{"top": {...}, "layers": [{leaf: array}]} in the type the leaves
    were made in (nothing is stacked: the layers differ)."""
    top = {n: weights[n] for n in ("model.embed_tokens.weight",
                                   "model.norm.weight")}
    layers = []
    for l in range(cfg["num_hidden_layers"]):
        p = f"{PRE}{l}."
        layers.append({n[len(p):]: a for n, a in weights.items()
                       if n.startswith(p)})
    return {"top": top, "layers": layers}


def _fq(x, dtype=jnp.float8_e4m3fn):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _einsum(precision):
    exact = functools.partial(jnp.einsum, precision=HI)
    if precision == "f32":
        return exact
    if precision == "fp8":
        return lambda spec, a, b: exact(spec, _fq(a), _fq(b))
    raise ValueError(precision)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _attention(q, k, v, scale, es):
    """Causal attention, a block of queries at a time: q [S, nh, dh], k
    and v [S, kvh, dh] -> [S, nh * dh]."""
    s, nh, dh = q.shape
    rep = nh // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    cols = jnp.arange(s)[None, :]

    def block(args):
        qb, first = args
        rows = first + jnp.arange(blk)[:, None]
        sc = es("qnd,knd->nqk", qb, k) * scale
        pr = jax.nn.softmax(jnp.where((cols <= rows)[None], sc, -jnp.inf),
                            -1)
        return es("nqk,knd->qnd", pr, v)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, nh, dh),
                              jnp.arange(0, s, blk)))
    return out.reshape(s, nh * dh)


def _attn_mixer(h, lp, cfg, es):
    f32 = lambda a: a.astype(jnp.float32)
    s = h.shape[0]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // nh
    a = "self_attn."
    q = es("sh,hk->sk", h, f32(lp[a + "q_proj.weight"])).reshape(s, nh, dh)
    k = es("sh,hk->sk", h, f32(lp[a + "k_proj.weight"])).reshape(s, kvh, dh)
    v = es("sh,hk->sk", h, f32(lp[a + "v_proj.weight"])).reshape(s, kvh, dh)
    ctx = _attention(q, k, v, cfg["attention_multiplier"], es)
    return es("sk,kh->sh", ctx, f32(lp[a + "o_proj.weight"]))


def _mamba_mixer(h, lp, cfg, es):
    f32 = lambda a: a.astype(jnp.float32)
    s = h.shape[0]
    nh, dh = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, taps = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    inner = nh * dh
    m = "mamba."
    proj = es("sh,hk->sk", h, f32(lp[m + "in_proj.weight"]))
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + inner + 2 * n],
                  proj[:, inner + inner + 2 * n:])
    # the causal depthwise convolution as `taps` shifted adds: tap k
    # weighs the input taps - 1 - k tokens back, zeros before the row
    w = f32(lp[m + "conv1d.weight"])                        # [taps, C]
    back = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc], 0)
    conv = f32(lp[m + "conv1d.bias"])[None, :]
    for k in range(taps):
        conv = conv + w[k][None, :] * back[k:k + s]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, nh, dh)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + f32(lp[m + "dt_bias"])[None, :])   # [S, nh]
    a = -jnp.exp(f32(lp[m + "A_log"]))                           # [nh]

    def token(state, row):
        x_t, b_t, c_t, dt_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.sum(state * c_t[None, None, :], -1)

    _, y = jax.lax.scan(token, jnp.zeros((nh, dh, n), jnp.float32),
                        (x, b, c, dt))
    y = y + f32(lp[m + "D"])[None, :, None] * x
    gated = _rms(y.reshape(s, inner) * jax.nn.silu(z),
                 f32(lp[m + "norm.weight"]), cfg["rms_norm_eps"])
    return es("sk,kh->sh", gated, f32(lp[m + "out_proj.weight"]))


def _layer(x, lp, cfg, mamba, es):
    f32 = lambda a: a.astype(jnp.float32)
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = _rms(x, f32(lp["input_layernorm.weight"]), eps)
    x = x + m * (_mamba_mixer if mamba else _attn_mixer)(h, lp, cfg, es)
    h = _rms(x, f32(lp["post_attention_layernorm.weight"]), eps)
    i = cfg["shared_intermediate_size"]
    gate_up = es("sh,hi->si", h, f32(lp["shared_mlp.input_linear.weight"]))
    f = es("si,ih->sh", jax.nn.silu(gate_up[:, :i]) * gate_up[:, i:],
           f32(lp["shared_mlp.output_linear.weight"]))
    return x + m * f


@functools.lru_cache(maxsize=16)
def _fns(cfg_json: str, precision: str):
    """(a jitted layer by kind, the jitted embedding, the jitted head)."""
    cfg = json.loads(cfg_json)
    es = _einsum(precision)

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(x, lp, mamba):
        return _layer(x, lp, cfg, mamba, es)

    @jax.jit
    def embed(top, ids):
        return top["model.embed_tokens.weight"][ids].astype(jnp.float32) \
            * cfg["embedding_multiplier"]

    @jax.jit
    def head(top, x):
        f32 = lambda a: a.astype(jnp.float32)
        x = _rms(x, f32(top["model.norm.weight"]), cfg["rms_norm_eps"])
        return es("sh,vh->sv", x, f32(top["model.embed_tokens.weight"])) \
            / cfg["logits_scaling"]

    return layer, embed, head


def _key(cfg):
    keep = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "layer_types", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv",
            "shared_intermediate_size", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "rms_norm_eps")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def logits_of(params, ids, cfg, precision="f32"):
    """[S, vocab] float32 logits of one row of token ids [S]."""
    layer, embed, head = _fns(_key(cfg), precision)
    x = embed(params["top"], jnp.asarray(ids, jnp.int32))
    for l, lp in enumerate(params["layers"]):
        x = layer(x, lp, cfg["layer_types"][l] == "mamba")
    return head(params["top"], x)


def _bucket(n: int, pad_to: int) -> int:
    """Positions a row is padded to: the power of two that holds it (a
    few shapes whatever the lengths), `Q_BLOCK` at least, `pad_to` at most.
    The pad follows the row, so a causal pass never sees it."""
    return min(max(Q_BLOCK, 1 << (n - 1).bit_length()), max(pad_to, n))


def served_gaps(params, cfg, prompt, served, pad_to, control=None):
    """For one finished request: at each position that produced a served
    token, how far that token's logit lies below the reference's best
    ([n_served] float32, >= 0). No position is excused: this architecture
    has no routing cut, so a program in the stated precision moves a
    logit by its rounding and no further. With `control` set, the token
    that the control's precision puts first at that position is judged
    instead."""
    n, m = len(prompt), len(served)
    ids = np.zeros(_bucket(n + m, pad_to), np.int32)
    ids[:n + m] = np.concatenate([prompt, served])
    lg = logits_of(params, ids, cfg)
    if control:
        chosen = jnp.argmax(logits_of(params, ids, cfg, control), -1)
    else:
        chosen = np.zeros(len(ids), np.int32)
        chosen[:n + m - 1] = ids[1:n + m]   # position t chose token t+1
        chosen = jnp.asarray(chosen)
    got = jnp.take_along_axis(lg, chosen[:, None], 1)[:, 0]
    gaps = np.asarray(jnp.max(lg, -1) - got)[n - 1:n + m - 1].copy()
    sys.stderr.write(
        f"[reference] {m} positions ({control or 'served'}): largest gap "
        f"{gaps.max():.5f}, mean {gaps.mean():.5f}, over half the largest "
        f"at {int((gaps > gaps.max() / 2).sum())}\n")
    return gaps
