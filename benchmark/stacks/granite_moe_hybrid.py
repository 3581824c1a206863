"""The Granite 4.0-H stack with routed experts as the program builds it:
`models/granite_hybrid.py` served by `serve.ServeEngine` (Mamba-2 and
attention layers as in `stacks/granite_hybrid.py`, and after the mixer of
every layer a sparse expert layer: a softmax top-k router over all the
published experts, the held experts through `ops/pallas/moe_experts`, the
fused SwiGLU shared expert beside them). One chip's share of an
expert-parallel deployment: the configuration's `experts_held` of
`num_experts_published` routed experts and `vocab_size` rows of the
vocabulary. This file and `stacks/granite_hybrid.py`, whose leaves it
extends, are the only places where the benchmark knows the program's
parameter names and this architecture's dimensions. Serving only: no cell
trains it."""
from __future__ import annotations

import math

from benchmark.stacks import granite_hybrid as _dense

REFERENCE = "granite_moe_hybrid"

#: the configuration's keys that `GraniteHybridConfig` takes as they are
_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "shared_intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
         "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
         "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
         "mamba_proj_bias", "attention_bias", "attention_multiplier",
         "embedding_multiplier", "residual_multiplier", "logits_scaling",
         "position_embedding_type", "num_experts_per_tok", "rms_norm_eps",
         "max_position_embeddings", "tie_word_embeddings", "dtype")


#: the seeded embedding's std over the other leaves' (the configuration's
#: `assumed.seeded_embedding`: 0.005 beside 0.02). The head is tied, so a
#: position's own token scores 12 |E_t|^2 / rms(x) against the others'
#: random products: at 0.02 (and at 0.01) that wins at every position of
#: a reduced probe of ten layers, greedy text repeats its prompt's last
#: token from the first step, and every reading of the check, the controls'
#: too, is 0 (PERF.md §6, Granite 4.0-H Small: the first chip run). At
#: 0.005 a token's own row takes 9% of the argmaxes and the controls read
#: far above the program.
EMBED_SCALE = 0.25


def leaf_specs(cfg: dict):
    """[(program leaf name, shape, init, scale)]: the dense stack's leaves
    (the embedding's std times `EMBED_SCALE`), then each layer's router
    over all the published experts and the held experts' two matrices,
    normal(0, initializer_range)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    held = cfg["experts_held"][1]
    std = cfg.get("initializer_range", 0.02)
    specs = [(n, s, init, scale * EMBED_SCALE
              if n == "model.embed_tokens.weight" else scale)
             for n, s, init, scale in _dense.leaf_specs(cfg)]
    for l in range(cfg["num_hidden_layers"]):
        e = f"model.layers.{l}.block_sparse_moe."
        specs += [
            (e + "router.layer.weight", (h, cfg["num_experts_published"]),
             "normal", std),
            (e + "input_linear.weight", (held, h, 2 * i), "normal", std),
            (e + "output_linear.weight", (held, i, h), "normal", std)]
    return specs


def build_model(cfg: dict, recompute: bool = False):
    """The program's model at the configuration's sizes, in its `dtype`,
    its leaves left for the seeded weights to fill (`deferred_init`)."""
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)

    if cfg["hidden_act"] != "silu" or cfg["normalization_function"] \
            != "rmsnorm":
        raise ValueError("the Granite hybrid stack runs SwiGLU and RMSNorm")
    config = GraniteHybridConfig(
        **{k: cfg[k] for k in _KEYS}, layer_types=tuple(cfg["layer_types"]),
        num_local_experts=cfg["num_experts_published"],
        experts_held=tuple(cfg["experts_held"]), deferred_init=True)
    return GraniteHybridForCausalLM(config)


def parts(name: str, shape):
    """No fused leaf is read apart here (no cell trains this stack)."""
    return [("", (Ellipsis,))]


def vocab_size(cfg: dict) -> int:
    """The slice: ids, logits and sampling are over it."""
    return cfg["vocab_size"]


def dims(cfg: dict) -> dict:
    """The dense stack's dimensions and the expert layer's.
    `matmul_params`: what the dense stack counts (the mixers' projections,
    the shared expert, the head's slice) plus, in every layer, the router
    and `top_k x held / published` routed experts (the expectation under
    seeded weights: 5 here)."""
    d = _dense.dims(cfg)
    h, im = cfg["hidden_size"], cfg["intermediate_size"]
    held, published = cfg["experts_held"][1], cfg["num_experts_published"]
    k = cfg["num_experts_per_tok"]
    expert = 3 * h * im
    layers = cfg["num_hidden_layers"]
    d.update(
        sparse_layers=layers, experts_held=held, top_k=k,
        experts_published=published, expert_params=expert, expert_width=im,
        parameters=sum(math.prod(s) for _, s, _, _ in leaf_specs(cfg)),
        matmul_params=d["matmul_params"]
        + layers * (h * published + k * held / published * expert))
    return d


def forward_flops(cfg: dict, new: int, ctx_before: int = 0) -> float:
    """Forward FLOPs of `new` tokens of one sequence that already holds
    `ctx_before`, the head on every position."""
    d = dims(cfg)
    seen = sum(ctx_before + j + 1 for j in range(new))
    return 2 * d["matmul_params"] * new + _dense._attention_flops(d, seen) \
        + _dense._recurrence_flops(d, new)


def prefill_flops(cfg: dict, n: int) -> float:
    """Forward FLOPs of a prompt of `n` tokens as a server needs them: the
    head is applied to the last position only."""
    d = dims(cfg)
    return forward_flops(cfg, n) - 2 * d["vocab"] * d["width"] * (n - 1)


def decode_flops(cfg: dict, rows: int, sum_ctx: int) -> float:
    """Forward FLOPs of one new token for each of `rows` streams whose
    contexts, the new token included, sum to `sum_ctx`: an attention layer
    reads the whole context, a Mamba layer its state."""
    d = dims(cfg)
    return 2 * d["matmul_params"] * rows \
        + _dense._attention_flops(d, sum_ctx) \
        + _dense._recurrence_flops(d, rows)
