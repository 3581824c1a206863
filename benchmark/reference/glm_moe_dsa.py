"""Plain reference of the GLM-5 architecture (`model_type: glm_moe_dsa`,
the published config of zai-org/GLM-5), as one chip of its expert-parallel
deployment computes it.

The layer, for input x [S, hidden] (pre-norm; the configuration's file
lists under `assumed` what the config does not carry, with sources):

- MLA (DeepSeek-V2, arXiv:2405.04434 §2.1): h = RMSNorm(x); c_q =
  RMSNorm(h W_qa); q = c_q W_qb as [heads, nope + rope] = [q_nope | q_pe];
  [c_kv | k_pe] = h W_kva; c = RMSNorm(c_kv); [k_nope | v] a head = c W_kvb
  as [heads, nope + v]; q_pe and the ONE k_pe rotated by their position
  (half-split pairing over the `rope` dims as the columns come, theta
  `rope_parameters.rope_theta`, no scaling); k = [k_nope | k_pe for every
  head]; scores q k^T (nope + rope)^-0.5.
- the indexer (DeepSeek-V3.2-Exp's lightning indexer): q_I = c_q W_Iq as
  [index_n_heads, index_head_dim]; k_I = LayerNorm(h W_Ik) (weight and
  bias, `index_norm_eps`); the first `rope` dims of each q_I head and of
  k_I rotated as above; w = h W_Iw x index_n_heads^-0.5; I[t, s] =
  index_head_dim^-0.5 x sum_j w[t, j] ReLU(q_I[t, j] . k_I[s]); query t's
  softmax runs over the `index_topk` positions s <= t of largest I[t, s]
  (all of them where t < index_topk), ties to the earlier position, every
  other score -inf; a = (softmax v) W_o.
- x = x + a; x = x + F(RMSNorm(x)); a last RMSNorm, then the untied head.
- layers before `first_k_dense_replace`: F a SwiGLU MLP of
  `intermediate_size`. The others: s = sigmoid(h W_r) over all
  `n_routed_experts_published` experts; the `num_experts_per_tok` largest
  of s + b are chosen (b `e_score_correction_bias`); w = s[chosen] / sum x
  `routed_scaling_factor`; F(h) = sum_e w_e E_e(h) + S(h), E_e and the one
  shared S SwiGLU of `moe_intermediate_size`.
- the share: the chip holds experts `experts_held` = [first, count) and
  `vocab_size` rows of the vocabulary; the sum runs over the chosen experts
  that are held, the others' terms are left out (as in the program), and
  logits are over the slice.

Straightforward `jax.numpy` in float32 with `highest` matmul precision: no
kernels, no cache, NO ABSORPTION (every position's keys and values are
expanded through W_kvb), no batching, one row at a time. The selection is
written its own way: the indices `lax.top_k` gives and a scatter of them
(the program thresholds at the k-th score). It fits beside the weights on a
16 GB chip only in pieces: one layer's weights are cast at a time (a jitted
call a layer), the held experts one at a time in a scan, the indexer and
the selection in blocks of 256 queries, and attention a group of 16 heads
at a time in blocks of 256 queries.

Controls, each put in the program's place: `"fp8"`, both operands of every
matrix product (the router's and the indexer's too) rounded to float8 e4m3
under a per-tensor absmax scale, the nearest precision below the
configuration's bfloat16; `"dense"`, the selection left out (every causal
key attended), which is the mathematics without its sparse attention.

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
Q_BLOCK = 256
HEAD_GROUP = 16
PRE = "model.layers."


def is_sparse(cfg, l):
    return l >= cfg["first_k_dense_replace"]


def stack_params(weights: dict, cfg: dict) -> dict:
    """{"top": {...}, "layers": [{leaf: array}]} in the type the leaves
    were made in."""
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
    if precision in ("f32", "dense"):
        return exact
    if precision == "fp8":
        return lambda spec, a, b: exact(spec, _fq(a), _fq(b))
    raise ValueError(precision)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, cfg):
    """x [S, heads, dim] rotated by its position, half-split pairing."""
    s, _, dim = x.shape
    theta = cfg["rope_parameters"]["rope_theta"]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _chosen(qi, w, ki, cfg, es):
    """[S, S] bool: query t's selected keys, a block of `Q_BLOCK` queries
    at a time: the block's [block, S] index scores, the `index_topk`
    indices `lax.top_k` gives of its causal ones, scattered."""
    s, ih, idim = qi.shape
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    k = min(cfg["index_topk"], s)
    cols = jnp.arange(s)[None, :]

    def block(args):
        qb, wb, first = args
        rows = first + jnp.arange(blk)[:, None]
        causal = cols <= rows
        sc = jnp.sum(jax.nn.relu(es("qhd,kd->qhk", qb, ki))
                     * wb[:, :, None], 1) * idim ** -0.5
        _, idx = jax.lax.top_k(jnp.where(causal, sc, -jnp.inf), k)
        hit = jnp.zeros((blk, s), bool).at[
            jnp.arange(blk)[:, None], idx].set(True)
        return hit & causal

    out = jax.lax.map(block, (qi.reshape(s // blk, blk, ih, idim),
                              w.reshape(s // blk, blk, ih),
                              jnp.arange(0, s, blk)))
    return out.reshape(s, s)


def _attention(q, k, v, seen, scale, es):
    """Attention of one group of heads, a block of queries at a time: q
    and k [S, g, dqk], v [S, g, dv], seen [S, S] bool -> [S, g, dv]."""
    s, g, dqk = q.shape
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s

    def block(args):
        qb, sb = args
        sc = es("qnd,knd->nqk", qb, k) * scale
        pr = jax.nn.softmax(jnp.where(sb[None], sc, -jnp.inf), -1)
        return es("nqk,knd->qnd", pr, v)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, g, dqk),
                              seen.reshape(s // blk, blk, s)))
    return out.reshape(s, g, -1)


def _swiglu(x, wg, wu, wd, es):
    return es("si,ih->sh", jax.nn.silu(es("sh,hi->si", x, wg))
              * es("sh,hi->si", x, wu), wd)


def _attn_half(x, lp, cfg, es, dense):
    """x after the attention sub-layer."""
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    a, ix = "self_attn.", "self_attn.indexer."
    h = _rms(x, f32(lp["input_layernorm.weight"]), eps)
    c_q = _rms(es("sh,hr->sr", h, f32(lp[a + "q_a_proj.weight"])),
               f32(lp[a + "q_a_layernorm.weight"]), eps)
    ckv = es("sh,hr->sr", h, f32(lp[a + "kv_a_proj_with_mqa.weight"]))
    c = _rms(ckv[:, :rank], f32(lp[a + "kv_a_layernorm.weight"]), eps)
    k_pe = _rope(ckv[:, None, rank:], cfg)
    # the indexer and its selection
    if dense:
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    else:
        qi = es("sr,rk->sk", c_q, f32(lp[ix + "wq_b.weight"])).reshape(
            s, ih, idim)
        ki = _ln(es("sh,hk->sk", h, f32(lp[ix + "wk.weight"])),
                 f32(lp[ix + "k_norm.weight"]), f32(lp[ix + "k_norm.bias"]),
                 cfg["index_norm_eps"])
        qi = jnp.concatenate([_rope(qi[:, :, :rope], cfg), qi[:, :, rope:]],
                             -1)
        ki = jnp.concatenate([_rope(ki[:, None, :rope], cfg)[:, 0],
                              ki[:, rope:]], -1)
        w = es("sh,hj->sj", h, f32(lp[ix + "weights_proj.weight"])) \
            * ih ** -0.5
        seen = _chosen(qi, w, ki, cfg, es)
    # attention, a group of heads at a time
    g = HEAD_GROUP if nh % HEAD_GROUP == 0 else nh
    wqb = f32(lp[a + "q_b_proj.weight"]).reshape(-1, nh // g, g, nope + rope)
    wkvb = f32(lp[a + "kv_b_proj.weight"]).reshape(rank, nh // g, g,
                                                   nope + dv)
    wo = f32(lp[a + "o_proj.weight"]).reshape(nh // g, g * dv, -1)
    scale = (nope + rope) ** -0.5

    def group(acc, args):
        wq, wkv, wo_g = args
        q = es("sr,rgd->sgd", c_q, wq)
        kv = es("sr,rgd->sgd", c, wkv)
        q = jnp.concatenate([q[:, :, :nope], _rope(q[:, :, nope:], cfg)], -1)
        k = jnp.concatenate([kv[:, :, :nope],
                             jnp.broadcast_to(k_pe, (s, g, rope))], -1)
        ctx = _attention(q, k, kv[:, :, nope:], seen, scale, es)
        return acc + es("sk,kh->sh", ctx.reshape(s, -1), wo_g), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(x),
                          (jnp.moveaxis(wqb, 1, 0), jnp.moveaxis(wkvb, 1, 0),
                           wo))
    return x + out


def _moe(h, lp, cfg, es):
    f32 = lambda a: a.astype(jnp.float32)
    first, count = cfg["experts_held"]
    scores = jax.nn.sigmoid(es("sh,he->se", h, f32(lp["mlp.gate.weight"])))
    pick = scores + f32(lp["mlp.gate.e_score_correction_bias"])
    _, chosen = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
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

    def expert(acc, args):
        gate_up, down, we = args
        gate_up = f32(gate_up)
        return acc + we[:, None] * _swiglu(h, gate_up[:, :i], gate_up[:, i:],
                                           f32(down), es), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        lp["mlp.experts.gate_up_proj"], lp["mlp.experts.down_proj"],
        held.T))
    sh = "mlp.shared_experts."
    return routed + _swiglu(h, f32(lp[sh + "gate_proj.weight"]),
                            f32(lp[sh + "up_proj.weight"]),
                            f32(lp[sh + "down_proj.weight"]), es)


def _layer(x, lp, cfg, sparse, es, dense):
    f32 = lambda a: a.astype(jnp.float32)
    x = _attn_half(x, lp, cfg, es, dense)
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
    dense = precision == "dense"

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(x, lp, sparse):
        return _layer(x, lp, cfg, sparse, es, dense)

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
            "index_n_heads", "index_head_dim", "index_topk",
            "index_norm_eps", "experts_held", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor",
            "moe_intermediate_size", "rms_norm_eps", "rope_parameters")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def logits_of(params, ids, cfg, precision="f32"):
    """[S, vocab slice] float32 logits of one row of token ids [S];
    `precision` "f32", or a control: "fp8" or "dense"."""
    layer, embed, head = _fns(_key(cfg), precision)
    x = embed(params["top"], jnp.asarray(ids, jnp.int32))
    for l, lp in enumerate(params["layers"]):
        x = layer(x, lp, is_sparse(cfg, l))
    return head(params["top"], x)


def _bucket(n: int, pad_to: int) -> int:
    """Positions a row is padded to: the power of two that holds it (a
    few shapes whatever the lengths), `Q_BLOCK` at least, `pad_to` at most."""
    return min(max(Q_BLOCK, 1 << (n - 1).bit_length()), max(pad_to, n))


#: Routing is a cut: the eighth and ninth of a token's 256 scores plus
#: their bias. Where the two lie closer than the rounding of a bfloat16
#: hidden state, the program holds another expert than this float32
#: reference, and where one of them is held here that token's output moves
#: by about an expert's term: the router is K-EXAONE's (sigmoid, top 8,
#: renormalised, times 2.5), and so is the remedy, `reference/
#: exaone_moe.py`'s: the largest `FLIP_SHARE` of a request's gaps are
#: divided by `FLIP_ROOM` and every other position is held to the limit
#: itself. The selection of the indexer is a cut too (the 2,048th and
#: 2,049th index scores), but a key more or less among 2,048 moves a
#: softmax over near-even scores by a 2,048th: no room is given for it.
#: Nothing is taken from the program: a position is excused by rank alone.
FLIP_SHARE = 0.05
FLIP_ROOM = 20.0


def served_gaps(params, cfg, prompt, served, pad_to, control=None):
    """For one finished request: at each position that produced a served
    token, how far that token's logit lies below the reference's best
    ([n_served] float32, >= 0), the `FLIP_SHARE` largest divided by
    `FLIP_ROOM` (above). With `control` set ("fp8" or "dense"), the token
    that the control puts first at that position is judged instead."""
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
