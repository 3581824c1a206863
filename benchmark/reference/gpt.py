"""Plain reference of the GPT-2 architecture (Radford et al. 2019, as the
`gpt2` model type of the published config states it): learned positions,
pre-LayerNorm blocks, fused qkv projection, exact GELU, biases, tied head.
Straightforward `jax.numpy` in float32 with `highest` matmul precision: no
kernels, no cache, no batching (one row at a time, layers under
`jax.checkpoint` so that a 2048-token row of the 590M model fits beside
its AdamW state). Imports nothing of the program and is handed only the
benchmark's own seeded weights.

`precision="fp8"` is the control: the same mathematics with both operands
of every matrix product rounded to float8 (e4m3 forward, e5m2 for the
cotangents backward, per-tensor absmax scale), the nearest precision below
the bfloat16 that the configuration states.

The loss is the mean cross-entropy of every position against `labels`;
AdamW is Loshchilov & Hutter's decoupled form with bias correction,
`p <- p (1 - lr wd) - lr m^ / (sqrt(v^) + eps)`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("norm1.weight", "norm1.bias", "attn.qkv_proj.weight",
                "attn.qkv_proj.bias", "attn.out_proj.weight",
                "attn.out_proj.bias", "norm2.weight", "norm2.bias",
                "linear1.weight", "linear1.bias", "linear2.weight",
                "linear2.bias")
TOP_LEAVES = ("gpt.wte.weight", "gpt.wpe.weight", "gpt.norm_f.weight",
              "gpt.norm_f.bias")


def stack_params(weights: dict, cfg: dict) -> dict:
    """The benchmark's {leaf name: array} as float32, the layers' leaves
    stacked along a leading axis for `lax.scan`."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    top = {n: f32(weights[n]) for n in TOP_LEAVES}
    layers = {n: jnp.stack([f32(weights[f"gpt.layers.{l}.{n}"])
                            for l in range(cfg["n_layer"])])
              for n in LAYER_LEAVES}
    return {"top": top, "layers": layers}


def leaf_norms(tree: dict) -> dict:
    """{leaf name: Frobenius norm} of a tree shaped like `stack_params`'
    (a stacked leaf gives one norm per layer). The fused qkv projection
    counts as three leaves, `#q`, `#k` and `#v`: the key's bias has no
    gradient under softmax."""
    out = {n: float(jnp.sqrt(jnp.sum(jnp.square(a))))
           for n, a in tree["top"].items()}
    for n, a in tree["layers"].items():
        if n.startswith("attn.qkv_proj"):
            third = a.shape[-1] // 3
            pieces = [(f"{n}#{t}", a[..., i * third:(i + 1) * third])
                      for i, t in enumerate("qkv")]
        else:
            pieces = [(n, a)]
        for name, piece in pieces:
            per = np.asarray(jnp.sqrt(jnp.sum(
                jnp.square(piece), axis=tuple(range(1, piece.ndim)))))
            out.update({f"gpt.layers.{l}.{name}": float(x)
                        for l, x in enumerate(per)})
    return out


def _fq(x, dtype=jnp.float8_e4m3fn):
    """Round to float8 under a per-tensor absmax scale (the usual float8
    recipe: e4m3 for the operands of the forward products, e5m2 for the
    cotangents)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _fp8_einsum(spec, a, b):
    """`einsum(spec, a, b)` as a float8 step computes it: both operands
    rounded to e4m3, and in the backward pass the cotangent rounded to
    e5m2 and multiplied with the rounded operands."""
    exact = functools.partial(jnp.einsum, spec, precision=HI)

    @jax.custom_vjp
    def f(a, b):
        return exact(_fq(a), _fq(b))

    def fwd(a, b):
        qa, qb = _fq(a), _fq(b)
        return exact(qa, qb), (qa, qb)

    def bwd(res, g):
        return jax.vjp(exact, *res)[1](_fq(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f(a, b)


def _einsum(precision):
    if precision == "f32":
        return functools.partial(jnp.einsum, precision=HI)
    if precision == "fp8":
        return _fp8_einsum
    raise ValueError(precision)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _block(x, lp, cfg, es):
    s, h = x.shape
    nh = cfg["n_head"]
    dh = h // nh
    eps = cfg["layer_norm_epsilon"]
    a = _ln(x, lp["norm1.weight"], lp["norm1.bias"], eps)
    qkv = es("sh,hk->sk", a, lp["attn.qkv_proj.weight"]) \
        + lp["attn.qkv_proj.bias"]
    q, k, v = (qkv[:, i * h:(i + 1) * h].reshape(s, nh, dh)
               for i in range(3))
    scores = es("qnd,knd->nqk", q, k) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    ctx = es("nqk,knd->qnd", probs, v).reshape(s, h)
    x = x + es("sh,hk->sk", ctx, lp["attn.out_proj.weight"]) \
        + lp["attn.out_proj.bias"]
    a = _ln(x, lp["norm2.weight"], lp["norm2.bias"], eps)
    u = jax.nn.gelu(es("sh,hi->si", a, lp["linear1.weight"])
                    + lp["linear1.bias"], approximate=False)
    return x + es("si,ih->sh", u, lp["linear2.weight"]) + lp["linear2.bias"]


def logits_of(params, ids, cfg, precision="f32"):
    """[S, vocab] logits of one row of token ids [S]."""
    es = _einsum(precision)
    top = params["top"]
    x = top["gpt.wte.weight"][ids] + top["gpt.wpe.weight"][:ids.shape[0]]
    block = jax.checkpoint(lambda x, lp: (_block(x, lp, cfg, es), None))
    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _ln(x, top["gpt.norm_f.weight"], top["gpt.norm_f.bias"],
            cfg["layer_norm_epsilon"])
    return es("sh,vh->sv", x, top["gpt.wte.weight"])


def row_loss(params, ids, labels, cfg, precision="f32"):
    logp = jax.nn.log_softmax(logits_of(params, ids, cfg, precision), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


# --- serving ---------------------------------------------------------------
@functools.lru_cache(maxsize=8)
def _position_fns(cfg_items: tuple, precision: str, arch):
    cfg = dict(cfg_items)
    logits_of = arch.logits_of

    @jax.jit
    def stats(params, ids, chosen):
        """Per position: the best logit, and the logit of `chosen`."""
        lg = logits_of(params, ids, cfg, "f32")
        return (jnp.max(lg, -1),
                jnp.take_along_axis(lg, chosen[:, None], 1)[:, 0])

    @jax.jit
    def first(params, ids):
        """Per position: the token that `precision` puts first."""
        return jnp.argmax(logits_of(params, ids, cfg, precision), -1)

    return stats, first


def _hashable(cfg):
    return tuple((k, v) for k, v in sorted(cfg.items())
                 if isinstance(v, (int, float, str, bool)))


def served_gaps(params, cfg, prompt, served, pad_to, control=None,
                arch=None):
    """For one finished request: at each position that produced a served
    token, how far that token's logit lies below the reference's best
    ([n_served] float32, >= 0). With `control` set, the token that the
    control's precision puts first at that position is judged instead."""
    stats, first = _position_fns(_hashable(cfg), control or "f32",
                                 arch or _ARCH)
    n, m = len(prompt), len(served)
    ids = np.zeros(pad_to, np.int32)
    ids[:n + m] = np.concatenate([prompt, served])
    chosen = np.zeros(pad_to, np.int32)
    chosen[:n + m - 1] = ids[1:n + m]       # position t chose token t+1
    ids_j = jnp.asarray(ids)
    if control:
        chosen = first(params, ids_j)
    best, got = stats(params, ids_j, jnp.asarray(chosen))
    return np.asarray(best - got)[n - 1:n + m - 1]


# --- training --------------------------------------------------------------
@functools.lru_cache(maxsize=8)
def _train_fns(cfg_items: tuple, precision: str, opt_items: tuple, arch):
    cfg, opt = dict(cfg_items), dict(opt_items)
    row_loss = arch.row_loss
    lr, b1, b2 = opt["lr"], opt["beta1"], opt["beta2"]
    eps, wd = opt["eps"], opt["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def accumulate(params, gacc, ids, labels, weight):
        loss, g = jax.value_and_grad(row_loss)(params, ids, labels, cfg,
                                               precision)
        return loss, jax.tree.map(lambda a, b: a + weight * b, gacc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adamw(params, grads, m, v, t):
        def one(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            p = p * (1 - lr * wd) - lr * (m / (1 - b1 ** t)) / (
                jnp.sqrt(v / (1 - b2 ** t)) + eps)
            return p, m, v
        out = jax.tree.map(one, params, grads, m, v)
        pick = lambda i: jax.tree.map(lambda t3: t3[i], out,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    return accumulate, adamw


def train(make_weights, batches, cfg: dict, opt: dict, *,
          precision: str = "f32", fault: str | None = None,
          arch=None) -> dict:
    """Drive the reference from `make_weights()` (the benchmark's seeded
    {leaf name: array}, made anew when the initial weights are needed
    again, so that no second copy is held through the steps) through
    `batches` = [(ids[B,S], labels[B,S])] and return what the comparison reads: each step's loss, every leaf's
    first-gradient norm, and every leaf's norm of change after the last
    step. `fault` plants one of the faults a training cell can have in
    the reference put in the program's place: `half_batch` (the second
    half of the rows left out, the mean taken over the rest)."""
    opt_items = tuple(sorted((k, v) for k, v in opt.items()
                             if isinstance(v, (int, float))))
    arch = arch or _ARCH
    stack_params, leaf_norms = arch.stack_params, arch.leaf_norms
    accumulate, adamw = _train_fns(_hashable(cfg), precision, opt_items,
                                   arch)
    params = stack_params(make_weights(), cfg)
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    m, v = zeros(), zeros()
    losses, grad_norms = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        rows = range(len(ids) // 2 if fault == "half_batch" else len(ids))
        gacc, loss = zeros(), 0.0
        for r in rows:
            l, gacc = accumulate(params, gacc, jnp.asarray(ids[r], jnp.int32),
                                 jnp.asarray(labels[r], jnp.int32),
                                 jnp.float32(1.0 / len(rows)))
            loss += float(l) / len(rows)
        losses.append(loss)
        if t == 1:
            grad_norms = leaf_norms(gacc)
        params, m, v = adamw(params, gacc, m, v, jnp.float32(t))
        del gacc
    del m, v
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params,
                                     stack_params(make_weights(), cfg)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


class _Arch:
    """What a reference architecture gives the shared driver."""
    stack_params = staticmethod(stack_params)
    leaf_norms = staticmethod(leaf_norms)
    logits_of = staticmethod(logits_of)
    row_loss = staticmethod(row_loss)


_ARCH = _Arch()
