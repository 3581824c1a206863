"""Plain reference of the DeepSeek-V2 architecture (`model_type:
deepseek_v2`, the published config of deepseek-ai/DeepSeek-V2; the paper is
arXiv:2405.04434), as one chip of its expert-parallel deployment computes
it.

The layer, for input x [S, hidden] (pre-norm; the configuration's file
lists under `assumed` what the config does not carry, with sources):

- h = RMSNorm(x); c_q = RMSNorm(h W_qa); q = c_q W_qb as [heads, nope +
  rope] = [q_nope | q_pe]; [c_kv | k_pe] = h W_kva; c = RMSNorm(c_kv);
  [k_nope | v] a head = c W_kvb as [heads, nope + v]; q_pe and the ONE k_pe
  are rotated by their position (half-split pairing over the `rope` dims as
  the columns come; YaRN frequencies: pair i turns at theta^(-2i/rope),
  over `factor` for the pairs that make under `beta_slow` turns in
  `original_max_position_embeddings` positions, unchanged for those over
  `beta_fast`, a linear ramp between, its ends floor and ceil of the two
  pairs; cos and sin times yarn_get_mscale(factor, mscale) /
  yarn_get_mscale(factor, mscale_all_dim), which is 1 here); k = [k_nope |
  k_pe for every head]; causal softmax of q k^T (nope + rope)^-0.5
  mscale^2 with mscale = 0.1 mscale_all_dim ln(factor) + 1, in float32;
  a = (softmax v) W_o.
- x = x + a; x = x + F(RMSNorm(x)); a last RMSNorm, then the untied head.
- layers before `first_k_dense_replace`: F a SwiGLU MLP of
  `intermediate_size`. The others: s = softmax(h W_r) over all
  `n_routed_experts_published` experts; a group's score is the largest s
  of its experts (`n_group` groups side by side); the `topk_group` best
  groups are kept and s is zeroed elsewhere; the `num_experts_per_tok`
  largest of what is left are chosen; w = s[chosen] x
  `routed_scaling_factor` (`norm_topk_prob` false: not renormalised);
  F(h) = sum_e w_e E_e(h) + S(h), E_e SwiGLU of `moe_intermediate_size`, S
  ONE SwiGLU of `n_shared_experts` x that.
- the share: the chip holds experts `experts_held` = [first, count) and
  `vocab_size` rows of the vocabulary; the sum runs over the chosen experts
  that are held, the others' terms are left out (as in the program), and
  logits are over the slice.

Straightforward `jax.numpy` in float32 with `highest` matmul precision: no
kernels, no cache, NO ABSORPTION (every position's keys and values are
expanded through W_kvb, which is what the program's decode path never
does), no batching, one row at a time. It fits beside nothing else on a
16 GB chip only in pieces: the weights stay in the type they were made in
(bfloat16, so the cast to float32 is exact) and one layer's are cast at a
time (a jitted call a layer), a held expert's inside a `lax.map` over the
experts, and attention goes through query blocks of 256. Every held
expert is applied to every position and weighed by 0 where it was not
chosen.

`precision="fp8"` is the control: both operands of every matrix product
(the router's too) rounded to float8 e4m3 under a per-tensor absmax scale,
the nearest precision below the configuration's bfloat16.

Imports nothing of the program and is handed only the benchmark's own
seeded weights.
"""
from __future__ import annotations

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
PRE = "model.layers."


def is_sparse(cfg, l):
    return l >= cfg["first_k_dense_replace"]


def stack_params(weights: dict, cfg: dict) -> dict:
    """{"top": {...}, "layers": [{leaf: array}]} in the type the leaves
    were made in (nothing is stacked: the layers differ)."""
    top = {n: weights[n] for n in ("model.embed_tokens.weight",
                                   "model.norm.weight", "lm_head.weight")}
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


def yarn_get_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, rs):
    """[dim / 2] frequencies, written out pair by pair (numpy, float64
    until the last step): the direct formula the program's tables are
    tested against."""
    original = rs["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), dim - 1)
    out = np.zeros(dim // 2)
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out[i] = plain / rs["factor"] * ramp + plain * (1.0 - ramp)
    return out.astype(np.float32)


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, cfg):
    """x [S, heads, rope] rotated by its position, half-split pairing."""
    s, _, dim = x.shape
    rs = cfg.get("rope_scaling")
    if rs:
        inv = jnp.asarray(yarn_inv_freq(dim, cfg["rope_theta"], rs))
        m = yarn_get_mscale(rs["factor"], rs.get("mscale", 1)) \
            / yarn_get_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    else:
        inv = cfg["rope_theta"] ** (
            -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        m = 1.0
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * m
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, scale, es):
    """Causal attention, a block of queries at a time: q and k [S, nh,
    dqk], v [S, nh, dv] -> [S, nh * dv]."""
    s, nh, dqk = q.shape
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    cols = jnp.arange(s)[None, :]

    def block(args):
        qb, first = args
        rows = first + jnp.arange(blk)[:, None]
        sc = es("qnd,knd->nqk", qb, k) * scale
        pr = jax.nn.softmax(jnp.where((cols <= rows)[None], sc, -jnp.inf),
                            -1)
        return es("nqk,knd->qnd", pr, v)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, nh, dqk),
                              jnp.arange(0, s, blk)))
    return out.reshape(s, -1)


def _swiglu(x, wg, wu, wd, es):
    return es("si,ih->sh", jax.nn.silu(es("sh,hi->si", x, wg))
              * es("sh,hi->si", x, wu), wd)


def _scores(h, lp, es):
    return jax.nn.softmax(
        es("sh,he->se", h, lp["mlp.gate.weight"].astype(jnp.float32)), -1)


def _choose(scores, cfg):
    """(chosen [S, k], their weights [S, k], the kept groups [S, n_group]
    bool) of the group-limited router."""
    s, e = scores.shape
    g = cfg["n_group"]
    best = jnp.max(scores.reshape(s, g, e // g), -1)
    _, groups = jax.lax.top_k(best, cfg["topk_group"])
    kept = jnp.any(groups[:, :, None] == jnp.arange(g), 1)
    left = jnp.where(jnp.repeat(kept, e // g, 1), scores, 0.0)
    _, chosen = jax.lax.top_k(left, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"], kept


def _moe(h, lp, cfg, es):
    f32 = lambda a: a.astype(jnp.float32)
    first, count = cfg["experts_held"]
    chosen, w, _ = _choose(_scores(h, lp, es), cfg)
    # [S, count]: the weight of each held expert at each position, 0
    # where it was not chosen
    held = jnp.sum(jnp.where(
        chosen[:, :, None] == first + jnp.arange(count)[None, None, :],
        w[:, :, None], 0.0), 1)
    i = cfg["moe_intermediate_size"]

    def expert(args):
        gate_up, down, we = args
        gate_up = f32(gate_up)
        return we[:, None] * _swiglu(h, gate_up[:, :i], gate_up[:, i:],
                                     f32(down), es)

    routed = jnp.sum(jax.lax.map(expert, (
        lp["mlp.experts.gate_up_proj"], lp["mlp.experts.down_proj"],
        held.T)), 0)
    sh = "mlp.shared_experts."
    return routed + _swiglu(h, f32(lp[sh + "gate_proj.weight"]),
                            f32(lp[sh + "up_proj.weight"]),
                            f32(lp[sh + "down_proj.weight"]), es)


def _attn_half(x, lp, cfg, es):
    """x after the attention sub-layer: what the second norm, the router
    and the FFN read."""
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    a = "self_attn."
    h = _rms(x, f32(lp["input_layernorm.weight"]), eps)
    c_q = _rms(es("sh,hr->sr", h, f32(lp[a + "q_a_proj.weight"])),
               f32(lp[a + "q_a_layernorm.weight"]), eps)
    q = es("sr,rk->sk", c_q, f32(lp[a + "q_b_proj.weight"])).reshape(
        s, nh, nope + rope)
    ckv = es("sh,hr->sr", h, f32(lp[a + "kv_a_proj_with_mqa.weight"]))
    c = _rms(ckv[:, :rank], f32(lp[a + "kv_a_layernorm.weight"]), eps)
    kv = es("sr,rk->sk", c, f32(lp[a + "kv_b_proj.weight"])).reshape(
        s, nh, nope + dv)
    q_pe = _rope(q[:, :, nope:], cfg)
    k_pe = _rope(ckv[:, None, rank:], cfg)
    q = jnp.concatenate([q[:, :, :nope], q_pe], -1)
    k = jnp.concatenate([kv[:, :, :nope],
                         jnp.broadcast_to(k_pe, (s, nh, rope))], -1)
    ctx = _attention(q, k, kv[:, :, nope:], softmax_scale(cfg), es)
    return x + es("sk,kh->sh", ctx, f32(lp[a + "o_proj.weight"]))


def _layer(x, lp, cfg, sparse, es):
    f32 = lambda a: a.astype(jnp.float32)
    x = _attn_half(x, lp, cfg, es)
    h = _rms(x, f32(lp["post_attention_layernorm.weight"]),
             cfg["rms_norm_eps"])
    if sparse:
        return x + _moe(h, lp, cfg, es)
    return x + _swiglu(h, f32(lp["mlp.gate_proj.weight"]),
                       f32(lp["mlp.up_proj.weight"]),
                       f32(lp["mlp.down_proj.weight"]), es)


@functools.lru_cache(maxsize=16)
def _fns(cfg_json: str, precision: str):
    """(a jitted layer by sparse or not, embed, the jitted head)."""
    cfg = json.loads(cfg_json)
    es = _einsum(precision)

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(x, lp, sparse):
        return _layer(x, lp, cfg, sparse, es)

    @jax.jit
    def embed(top, ids):
        return top["model.embed_tokens.weight"][ids].astype(jnp.float32)

    @jax.jit
    def head(top, x):
        f32 = lambda a: a.astype(jnp.float32)
        x = _rms(x, f32(top["model.norm.weight"]), cfg["rms_norm_eps"])
        return es("sh,hv->sv", x, f32(top["lm_head.weight"]))

    return layer, embed, head


def _key(cfg):
    keep = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "experts_held", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "n_group", "topk_group",
            "moe_intermediate_size", "rms_norm_eps", "rope_theta",
            "rope_scaling")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def logits_of(params, ids, cfg, precision="f32"):
    """[S, vocab slice] float32 logits of one row of token ids [S]."""
    layer, embed, head = _fns(_key(cfg), precision)
    x = embed(params["top"], jnp.asarray(ids, jnp.int32))
    for l, lp in enumerate(params["layers"]):
        x = layer(x, lp, is_sparse(cfg, l))
    return head(params["top"], x)


def _bucket(n: int, pad_to: int) -> int:
    """Positions a row is padded to: the power of two that holds it (a
    few shapes whatever the lengths), `Q_BLOCK` at least, `pad_to` at most."""
    return min(max(Q_BLOCK, 1 << (n - 1).bit_length()), max(pad_to, n))


#: Routing is a cut, here two: the third and fourth of a token's 8 group
#: scores, and the sixth and seventh of the scores the kept groups leave.
#: Where either pair lies closer than the rounding of a bfloat16 hidden
#: state, a program in the stated precision holds another expert than this
#: float32 reference, and where one of the two experts is held here that
#: token's output (and, less, what attends to it) moves. A routed expert
#: weighs `s x 16`, not renormalised, and only the held experts' terms are
#: summed, so one expert more or less moves a token's logits by about their
#: own spread (1.43): at the published widths the largest gap of a request
#: reads 0.9-2.1 at a handful of positions while 95% of its positions stay
#: under 0.03 (PERF.md §6, PR 33). As `reference/exaone_moe.py` does for
#: its one cut, the largest `FLIP_SHARE` of a request's gaps are divided
#: by `FLIP_ROOM` and every other position is held to the limit itself.
#: The share is that file's 5% (the shortest request a window finishes here
#: has some 650 positions, 32 of them excused). The room is 100 where that
#: file's is 20: a gap cannot pass some 5 (the best logit lies three
#: spreads over a random token's), so an excused position reads under 0.05
#: and is in truth not held at all, which is said here rather than hidden
#: in a smaller number: what the limit holds is the other 95%, and a
#: program in float8 moves far more than 5% of positions (the control).
#: Nothing is taken from the program: the reference does not learn its
#: choices, and a position is excused by rank alone. `router_margins`
#: gives the reference's own margins at both cuts; at the published widths
#: they did not single the moved positions out (the program's router
#: logits differ by about the median margin: PERF.md §6, PR 33).
FLIP_SHARE = 0.05
FLIP_ROOM = 100.0


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
        f"{np.delete(gaps, excused).max():.4f}, over half the largest at "
        f"{int((gaps > 0.5 * gaps.max()).sum())}, spread of the logits "
        f"{float(jnp.std(lg[n - 1:n + m - 1])):.3f}\n")
    gaps[excused] /= FLIP_ROOM
    return gaps


def router_margins(params, cfg, ids):
    """{sparse layer: ([S] the reference's margin at the group cut, [S]
    at the expert cut)}, each the log of the ratio of the two scores on
    either side of the cut, which is their distance in the router's
    logits. Where one is small beside the rounding of the program's
    hidden state, the program may keep another group or expert."""
    layer, embed, _ = _fns(_key(cfg), "f32")
    es = _einsum("f32")
    x = embed(params["top"], jnp.asarray(ids, jnp.int32))
    out = {}
    for l, lp in enumerate(params["layers"]):
        if is_sparse(cfg, l):
            mid = _attn_half(x, lp, cfg, es)
            h = _rms(mid, lp["post_attention_layernorm.weight"].astype(
                jnp.float32), cfg["rms_norm_eps"])
            out[l] = tuple(np.asarray(m)
                           for m in _cut_margins(_scores(h, lp, es), cfg))
        x = layer(x, lp, is_sparse(cfg, l))
    return out


def _cut_margins(scores, cfg):
    s, e = scores.shape
    g, kg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    best = jnp.max(scores.reshape(s, g, e // g), -1)
    top_g, groups = jax.lax.top_k(best, min(kg + 1, g))
    kept = jnp.any(groups[:, :kg, None] == jnp.arange(g), 1)
    left = jnp.where(jnp.repeat(kept, e // g, 1), scores, 0.0)
    top_e, _ = jax.lax.top_k(left, k + 1)
    tiny = 1e-30
    group_cut = (jnp.log(top_g[:, kg - 1] / jnp.maximum(top_g[:, -1], tiny))
                 if kg < g else jnp.full(s, jnp.inf))
    return group_cut, jnp.log(top_e[:, k - 1] / jnp.maximum(top_e[:, k],
                                                            tiny))
