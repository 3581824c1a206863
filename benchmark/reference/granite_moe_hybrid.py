"""Plain reference of the Granite 4.0-H architecture with routed experts
(`model_type: granitemoehybrid`, the published config of
granite-4.0-h-small), as one chip of its expert-parallel deployment
computes it.

The layer, for input x (m = `residual_multiplier`; transformers'
`modeling_granitemoehybrid.py`: `GraniteMoeHybridDecoderLayer`,
`GraniteMoeHybridMoE`, `GraniteMoeHybridTopKGating`,
`GraniteMoeHybridParallelExperts`):

- x = x + m * mixer(RMSNorm_1(x)): the Mamba-2 mixer or the NoPE attention
  exactly as `reference/granite_hybrid.py` computes them (taken from it).
- u = RMSNorm_2(x); r = u W_router over all `num_experts_published`
  experts (no bias); S = the `num_experts_per_tok` largest of r; g =
  softmax(r[S]), over the chosen logits only.
- routed(u) = sum_{e in S} g_e W_out,e (silu(u W_gate,e) * (u W_up,e)),
  `input_linear` of an expert being [gate | up] (`intermediate_size`
  each); shared(u) the same SwiGLU with the one `shared_mlp`
  (`shared_intermediate_size`).
- x = x + m * (routed(u) + shared(u)).
- the share: the chip holds experts `experts_held` = [first, first +
  count) and `vocab_size` rows of the vocabulary; the routed sum runs over
  the chosen experts that are held, the gates stay normalised over all the
  chosen ones, the others' terms are left out (as in the program), and
  logits are over the slice.
- around the stack: embedding times `embedding_multiplier`, a final
  RMSNorm, the tied head, logits divided by `logits_scaling`.

Departures from the published modelling file, each by design of a
reference: float32 throughout with `highest` matmul precision (the router's
logits too, where the published file computes them in the checkpoint's
bfloat16 and takes the softmax in float32); the mixers' own departures, as
`reference/granite_hybrid.py` lists them (a scan over tokens, attention in
query blocks); one row at a time, no cache, no batching; every held expert
applied to every position and weighed by 0 where it was not chosen (plain,
and 36 / 10 times the work), inside a `lax.map` over the experts so that
one expert's matrices are widened at a time. The weights stay in the type
they were made in (bfloat16, so widening is exact), a layer at a time.

`precision` names what `logits_of` computes besides the reference
itself, the controls that the check must refuse: `"fp8"` (both operands
of every matrix product, the router's too, rounded to float8 e4m3 under a
per-tensor absmax scale: the nearest precision below the configuration's
bfloat16), `"router_bf16"` (the router's operands and logits rounded to
bfloat16, so that near-ties at the top-10 cut fall the other way) and
`"no_shared"` (the shared expert left out).

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

from benchmark.reference import granite_hybrid as _dense

CONTROLS = ("fp8", "router_bf16", "no_shared")

stack_params = _dense.stack_params
_bucket = _dense._bucket
_rms = _dense._rms


def _swiglu(u, gate_up, down, es):
    i = gate_up.shape[1] // 2
    a = es("sh,hi->si", u, gate_up)
    return es("si,ih->sh", jax.nn.silu(a[:, :i]) * a[:, i:], down)


def gates(u, router, k, es, bf16=False):
    """(g [S, k] float32, chosen [S, k]): the top `k` of the logits u
    W_router and a softmax over those `k` alone. `bf16`: operands and
    logits rounded to bfloat16 (a control)."""
    if bf16:
        r = (u.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)
             ).astype(jnp.float32)
    else:
        r = es("sh,he->se", u, router)
    top, chosen = jax.lax.top_k(r, k)
    return jax.nn.softmax(top, -1), chosen


def _moe(u, lp, cfg, es, control):
    f32 = lambda a: a.astype(jnp.float32)
    e = "block_sparse_moe."
    first, count = cfg["experts_held"]
    g, chosen = gates(u, f32(lp[e + "router.layer.weight"]),
                      cfg["num_experts_per_tok"], es,
                      bf16=control == "router_bf16")
    # [S, count]: the gate of each held expert at each position, 0 where
    # it was not chosen
    held = jnp.sum(jnp.where(
        chosen[:, :, None] == first + jnp.arange(count)[None, None, :],
        g[:, :, None], 0.0), 1)

    def expert(args):
        gate_up, down, ge = args
        return ge[:, None] * _swiglu(u, f32(gate_up), f32(down), es)

    out = jnp.sum(jax.lax.map(expert, (
        lp[e + "input_linear.weight"], lp[e + "output_linear.weight"],
        held.T)), 0)
    if control != "no_shared":
        out = out + _swiglu(u, f32(lp["shared_mlp.input_linear.weight"]),
                            f32(lp["shared_mlp.output_linear.weight"]), es)
    return out


def _layer(x, lp, cfg, mamba, es, control):
    f32 = lambda a: a.astype(jnp.float32)
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = _rms(x, f32(lp["input_layernorm.weight"]), eps)
    mixer = _dense._mamba_mixer if mamba else _dense._attn_mixer
    x = x + m * mixer(h, lp, cfg, es)
    u = _rms(x, f32(lp["post_attention_layernorm.weight"]), eps)
    return x + m * _moe(u, lp, cfg, es, control)


@functools.lru_cache(maxsize=16)
def _fns(cfg_json: str, precision: str):
    """(a jitted layer by kind, the jitted embedding, the jitted head)."""
    if precision not in ("f32",) + CONTROLS:
        raise ValueError(precision)
    cfg = json.loads(cfg_json)
    es = _dense._einsum("fp8" if precision == "fp8" else "f32")

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(x, lp, mamba):
        return _layer(x, lp, cfg, mamba, es, precision)

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
    keep = json.loads(_dense._key(cfg))
    keep.update({k: cfg[k] for k in ("intermediate_size", "experts_held",
                                     "num_experts_per_tok")})
    return json.dumps(keep, sort_keys=True)


def logits_of(params, ids, cfg, precision="f32"):
    """[S, vocab slice] float32 logits of one row of token ids [S]."""
    layer, embed, head = _fns(_key(cfg), precision)
    x = embed(params["top"], jnp.asarray(ids, jnp.int32))
    for l, lp in enumerate(params["layers"]):
        x = layer(x, lp, cfg["layer_types"][l] == "mamba")
    return head(params["top"], x)


#: Routing is a cut, as `reference/exaone_moe.py` sets out: where the 10th
#: and 11th of a token's 72 router logits lie closer than the rounding of
#: a bfloat16 hidden state, the program holds another expert than this
#: float32 reference, and that token's output (and what reads it later:
#: the recurrent state of every Mamba layer above it) moves by more than
#: rounding. So the largest `FLIP_SHARE` of a request's gaps are held to
#: `FLIP_ROOM` times the cell's limit, by being divided by it, and every
#: other position to the limit itself. The values are K-EXAONE's, kept
#: from the readings on the chip (PERF.md §6, Granite 4.0-H Small): the
#: program's largest gap of a request reads 0.0007-0.0020 and the largest
#: outside its 5% 0-0.0002 (24 requests); unexcused, the limit would have
#: to sit within 1.5 times of the one, excused it lies 12 times over the
#: other, and the float8 control still reads 3 times over it outside its
#: own 5%.
#: Nothing is taken from the program: a position is excused by rank alone.
FLIP_SHARE = 0.05
FLIP_ROOM = 20.0


def served_gaps(params, cfg, prompt, served, pad_to, control=None):
    """For one finished request: at each position that produced a served
    token, how far that token's logit lies below the reference's best
    ([n_served] float32, >= 0), the `FLIP_SHARE` largest divided by
    `FLIP_ROOM` (above). With `control` set, the token that the control
    puts first at that position is judged instead."""
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
        f"{np.delete(gaps, excused).max():.4f}, over 0.02 at "
        f"{int((gaps > 0.02).sum())}\n")
    gaps[excused] /= FLIP_ROOM
    return gaps
