"""Plain reference of the EXAONE-MoE architecture (`model_type:
exaone_moe`, the published config of K-EXAONE-236B-A23B), as one chip of
its expert-parallel deployment computes it.

The layer, for input x (PERF.md §4 has the sources of each point):

- q = RMSNorm_dh(W_q x) per head, k = RMSNorm_dh(W_k x), v = W_v x; on a
  `sliding_attention` layer q and k are rotated (RoPE, half-split, theta
  from `rope_parameters`), on a `full_attention` layer they are not;
  causal attention, scale dh^-0.5, a query head reads key-value head
  h // (heads / kv heads); on a sliding layer key j is visible to query i
  iff 0 <= i - j < `sliding_window`; a = W_o attn.
- x = x + RMSNorm(a); x = x + RMSNorm(F(x)); a last RMSNorm, then the
  untied head.
- layers before `first_k_dense_replace`: F a SwiGLU MLP of
  `intermediate_size`. The others: s = sigmoid(W_r x) over all
  `num_experts_published` experts; the `num_experts_per_tok` largest of
  s + b are chosen; w = s[chosen] / (sum + 1e-20) * `routed_scaling_factor`;
  F(x) = sum_e w_e E_e(x) + S(x), E_e and the shared S SwiGLU of
  `moe_intermediate_size`.
- the share: the chip holds experts `experts_held` = [first, count) and
  `vocab_size` rows of the vocabulary; the sum runs over the chosen
  experts that are held, the others' terms are left out (as in the
  program), and logits are over the slice.

Straightforward `jax.numpy` in float32 with `highest` matmul precision:
no kernels, no cache, no batching, one row at a time. It fits beside
nothing else on a 16 GB chip only in pieces: the weights stay in the type
they were made in (bfloat16, so the cast to float32 is exact) and one
layer's are cast at a time (a jitted call a layer), a held expert's
inside a `lax.map` over the experts, and attention goes through query
blocks of 512. Every held expert is applied to every position and weighed
by 0 where it was not chosen: plain, and 16 times the work.

`precision="fp8"` is the control: both operands of every matrix product
(the router's too) rounded to float8 e4m3 under a per-tensor absmax
scale, the nearest precision below the configuration's bfloat16.

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
PRE = "exaone.layers."


def is_sparse(cfg, l):
    return l >= cfg["first_k_dense_replace"]


def stack_params(weights: dict, cfg: dict) -> dict:
    """{"top": {...}, "layers": [{leaf: array}]} in the type the leaves
    were made in (nothing is stacked: the layers differ)."""
    top = {n: weights[n] for n in ("exaone.embed_tokens.weight",
                                   "exaone.norm.weight", "lm_head.weight")}
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


def _rope(x, theta):
    """x [S, heads, dh] rotated by its position, half-split pairing."""
    s, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, window, es):
    """Causal (banded where `window`) attention, a block of queries at a
    time: q [S, nh, dh], k and v [S, kvh, dh] -> [S, nh * dh]."""
    s, nh, dh = q.shape
    rep = nh // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    cols = jnp.arange(s)[None, :]

    def block(args):
        qb, first = args
        rows = first + jnp.arange(blk)[:, None]
        seen = cols <= rows
        if window:
            seen &= rows - cols < window
        sc = es("qnd,knd->nqk", qb, k) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
        return es("nqk,knd->qnd", pr, v)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, nh, dh),
                              jnp.arange(0, s, blk)))
    return out.reshape(s, nh * dh)


def _swiglu(x, wg, wu, wd, es):
    return es("si,ih->sh", jax.nn.silu(es("sh,hi->si", x, wg))
              * es("sh,hi->si", x, wu), wd)


def _moe(x, lp, cfg, es):
    f32 = lambda a: a.astype(jnp.float32)
    first, count = cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(es("sh,he->se", x, f32(lp["mlp.gate.weight"])))
    _, chosen = jax.lax.top_k(
        scores + f32(lp["mlp.gate.e_score_correction_bias"]), k)
    w = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    # [S, count]: the weight of each held expert at each position, 0
    # where it was not chosen
    held = jnp.sum(jnp.where(
        chosen[:, :, None] == first + jnp.arange(count)[None, None, :],
        w[:, :, None], 0.0), 1)
    i = cfg["moe_intermediate_size"]

    def expert(args):
        gate_up, down, we = args
        gate_up = f32(gate_up)
        return we[:, None] * _swiglu(x, gate_up[:, :i], gate_up[:, i:],
                                     f32(down), es)

    routed = jnp.sum(jax.lax.map(expert, (
        lp["mlp.experts.gate_up_proj"], lp["mlp.experts.down_proj"],
        held.T)), 0)
    sh = "mlp.shared_experts."
    return routed + _swiglu(x, f32(lp[sh + "gate_proj.weight"]),
                            f32(lp[sh + "up_proj.weight"]),
                            f32(lp[sh + "down_proj.weight"]), es)


def _attn_half(x, lp, cfg, sliding, es):
    """x after the attention sub-layer and its norm: what the router and
    the FFN read."""
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    a = "self_attn."
    q = _rms(es("sh,hk->sk", x, f32(lp[a + "q_proj.weight"])).reshape(
        s, nh, dh), f32(lp[a + "q_norm.weight"]), eps)
    k = _rms(es("sh,hk->sk", x, f32(lp[a + "k_proj.weight"])).reshape(
        s, kvh, dh), f32(lp[a + "k_norm.weight"]), eps)
    v = es("sh,hk->sk", x, f32(lp[a + "v_proj.weight"])).reshape(s, kvh, dh)
    if sliding:
        theta = cfg["rope_parameters"]["rope_theta"]
        q, k = _rope(q, theta), _rope(k, theta)
    ctx = _attention(q, k, v, cfg["sliding_window"] if sliding else None, es)
    return x + _rms(es("sk,kh->sh", ctx, f32(lp[a + "o_proj.weight"])),
                    f32(lp["post_attention_layernorm.weight"]), eps)


def _layer(x, lp, cfg, sliding, sparse, es):
    f32 = lambda a: a.astype(jnp.float32)
    x = _attn_half(x, lp, cfg, sliding, es)
    if sparse:
        f = _moe(x, lp, cfg, es)
    else:
        f = _swiglu(x, f32(lp["mlp.gate_proj.weight"]),
                    f32(lp["mlp.up_proj.weight"]),
                    f32(lp["mlp.down_proj.weight"]), es)
    return x + _rms(f, f32(lp["post_feedforward_layernorm.weight"]),
                    cfg["rms_norm_eps"])


@functools.lru_cache(maxsize=16)
def _fns(cfg_json: str, precision: str):
    """(a jitted layer by (sliding, sparse), the jitted head)."""
    cfg = json.loads(cfg_json)
    es = _einsum(precision)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def layer(x, lp, sliding, sparse):
        return _layer(x, lp, cfg, sliding, sparse, es)

    @jax.jit
    def embed(top, ids):
        return top["exaone.embed_tokens.weight"][ids].astype(jnp.float32)

    @jax.jit
    def head(top, x):
        f32 = lambda a: a.astype(jnp.float32)
        x = _rms(x, f32(top["exaone.norm.weight"]), cfg["rms_norm_eps"])
        return es("sh,hv->sv", x, f32(top["lm_head.weight"]))

    return layer, embed, head


def _key(cfg):
    keep = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "layer_types", "experts_held",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "moe_intermediate_size", "rms_norm_eps", "rope_parameters")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def logits_of(params, ids, cfg, precision="f32"):
    """[S, vocab slice] float32 logits of one row of token ids [S]."""
    layer, embed, head = _fns(_key(cfg), precision)
    x = embed(params["top"], jnp.asarray(ids, jnp.int32))
    for l, lp in enumerate(params["layers"]):
        x = layer(x, lp, cfg["layer_types"][l] == "sliding_attention",
                  is_sparse(cfg, l))
    return head(params["top"], x)


def _bucket(n: int, pad_to: int) -> int:
    """Positions a row is padded to: the power of two that holds it (a
    few shapes whatever the lengths), `Q_BLOCK` at least, `pad_to` at most."""
    return min(max(Q_BLOCK, 1 << (n - 1).bit_length()), max(pad_to, n))


#: Routing is a cut: where the 8th and 9th of a token's 128 scores lie
#: closer than the rounding of a bfloat16 hidden state, a program in the
#: stated precision holds another expert than this float32 reference and
#: that token's output (and what attends to it) moves by a tenth of the
#: logits' spread. At the published widths that happens to 5-8% of
#: positions, 1% of positions then serve a token whose gap passes 0.09 and
#: the largest gap of a run reads 0.5-0.7 (PERF.md §6, PR 27), while a
#: program in float8 reads over 0.09 at 30% of positions. So the largest
#: `FLIP_SHARE` of a request's gaps are held to a limit `FLIP_ROOM`
#: times the cell's, by being divided by it, and every other position to
#: the limit itself. The share is 5%, not the 1-1.5% that pass 0.09,
#: because the shortest request a window finishes has some 150 positions:
#: with 7 of them excused, 8 moved tokens in one request are a chance of
#: one in thousands, where with 4 excused 5 would be one in thirty. Nothing is taken from the program: the reference
#: does not learn its choices, and a position is excused by rank alone.
FLIP_SHARE = 0.05
FLIP_ROOM = 20.0


def served_gaps(params, cfg, prompt, served, pad_to, control=None):
    """For one finished request: at each position that produced a served
    token, how far that token's logit lies below the reference's best
    ([n_served] float32, >= 0), the `FLIP_SHARE` largest divided by
    `FLIP_ROOM` (above). With `control` set, the token that the
    control's precision puts first at that position is judged instead."""
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
    excused = np.argsort(gaps)[m - int(FLIP_SHARE * m):]
    sys.stderr.write(
        f"[reference] {m} positions ({control or 'served'}): largest gap "
        f"{gaps.max():.4f}, largest outside the {len(excused)} excused "
        f"{np.delete(gaps, excused).max():.4f}, over 0.09 at "
        f"{int((gaps > 0.09).sum())}\n")
    gaps[excused] /= FLIP_ROOM
    return gaps


def router_margins(params, cfg, ids):
    """{sparse layer: [S] the reference's own margin at its cut}: the
    k-th largest of s + b less the (k+1)-th, over the sigmoid's slope
    s (1 - s) at the k-th, which is the distance between the two in the
    router's logits (to first order; exactly so for b = 0 and a small
    margin). Where this is small beside the rounding of the program's
    hidden state, the program may hold another expert."""
    layer, embed, _ = _fns(_key(cfg), "f32")
    es = _einsum("f32")
    k = cfg["num_experts_per_tok"]
    x = embed(params["top"], jnp.asarray(ids, jnp.int32))
    out = {}
    for l, lp in enumerate(params["layers"]):
        sliding = cfg["layer_types"][l] == "sliding_attention"
        if is_sparse(cfg, l):
            out[l] = np.asarray(_cut_margin(
                _attn_half(x, lp, cfg, sliding, es), lp, k, es))
        x = layer(x, lp, sliding, is_sparse(cfg, l))
    return out


def _cut_margin(mid, lp, k, es):
    f32 = lambda a: a.astype(jnp.float32)
    s = jax.nn.sigmoid(es("sh,he->se", mid, f32(lp["mlp.gate.weight"])))
    top, at = jax.lax.top_k(
        s + f32(lp["mlp.gate.e_score_correction_bias"]), k + 1)
    sk = jnp.take_along_axis(s, at[:, k - 1:k], -1)[:, 0]
    return (top[:, k - 1] - top[:, k]) / jnp.maximum(sk * (1 - sk), 1e-30)
