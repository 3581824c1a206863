"""The Granite 4.0-H stack as the program builds it:
`models/granite_hybrid.py` served by `serve.ServeEngine` (Mamba-2 layers
whose state and convolution tail the engine keeps by slot, attention layers
over the paged pool, the fused SwiGLU MLP in every layer). The whole model,
uncut. This file is the only place where the benchmark knows the program's
parameter names and this architecture's dimensions. Serving only: there is
no training count because no cell trains it (the scan has no backward in
the program)."""
from __future__ import annotations

import math

REFERENCE = "granite_hybrid"

#: the seeded values of a Mamba layer's three small leaves, which
#: `harness/weights.py` can draw only as normal(0, scale), ones or zeros
#: (the published initialisation draws A in [1, 16] and dt in [0.001,
#: 0.1]; the configuration's `assumed` says what these come to). A = 1 in
#: every head, the slow end of the published range, and the spread of the
#: decays comes from dt alone. `A_log` was normal(0, 2) at first: a few
#: heads a layer then had A near 0.001 with dt near 2, carried most of the
#: mixer's output (a head's share goes as dt / 2A) and remembered 500
#: tokens; seeded weights at temperature 0 repeat one token for hundreds
#: of steps, and on a constant input a state stored in bfloat16 stops
#: moving once a step changes it by under half a unit in its last place,
#: 26-38% short of where the float32 state settles in such a layer
#: (PERF.md §6, PR 31: the check's refusal on seed 629899549)
SSM_LEAVES = {"dt_bias": ("normal", 1.0), "A_log": ("zeros", 0),
              "D": ("ones", 0)}


def _mamba(cfg, l):
    return cfg["layer_types"][l] == "mamba"


def _sizes(cfg):
    nh, dh = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner = nh * dh
    return inner, inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def leaf_specs(cfg: dict):
    """[(program leaf name, shape, init, scale)]: normal(0,
    initializer_range) matrices (the convolution's taps and bias too),
    norms one, `SSM_LEAVES` as above."""
    h = cfg["hidden_size"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = h // nh
    i = cfg["shared_intermediate_size"]
    inner, conv = _sizes(cfg)
    mh = cfg["mamba_n_heads"]
    std = cfg.get("initializer_range", 0.02)
    w = lambda name, *shape: (name, shape, "normal", std)
    one = lambda name, n: (name, (n,), "ones", 0)
    specs = [w("model.embed_tokens.weight", cfg["vocab_size"], h)]
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        if _mamba(cfg, l):
            m = p + "mamba."
            specs += [(m + n, (mh,)) + SSM_LEAVES[n]
                      for n in ("dt_bias", "A_log", "D")]
            specs += [w(m + "in_proj.weight", h, inner + conv + mh),
                      w(m + "conv1d.weight", cfg["mamba_d_conv"], conv),
                      w(m + "conv1d.bias", conv),
                      one(m + "norm.weight", inner),
                      w(m + "out_proj.weight", inner, h)]
        else:
            a = p + "self_attn."
            specs += [w(a + "q_proj.weight", h, nh * dh),
                      w(a + "k_proj.weight", h, kvh * dh),
                      w(a + "v_proj.weight", h, kvh * dh),
                      w(a + "o_proj.weight", nh * dh, h)]
        specs += [w(p + "shared_mlp.input_linear.weight", h, 2 * i),
                  w(p + "shared_mlp.output_linear.weight", i, h),
                  one(p + "input_layernorm.weight", h),
                  one(p + "post_attention_layernorm.weight", h)]
    specs += [one("model.norm.weight", h)]
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
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        layer_types=tuple(cfg["layer_types"]),
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_expand=cfg["mamba_expand"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        mamba_conv_bias=cfg["mamba_conv_bias"],
        mamba_proj_bias=cfg["mamba_proj_bias"],
        attention_bias=cfg["attention_bias"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        position_embedding_type=cfg["position_embedding_type"],
        num_local_experts=cfg["num_local_experts"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["dtype"], deferred_init=True)
    return GraniteHybridForCausalLM(config)


def parts(name: str, shape):
    """No fused leaf is read apart here (no cell trains this stack)."""
    return [("", (Ellipsis,))]


def vocab_size(cfg: dict) -> int:
    return cfg["vocab_size"]


def dims(cfg: dict) -> dict:
    """What the counts below and the files under `work/` count from.
    `matmul_params`: the parameters of the matrix products ONE token passes
    through: a Mamba layer's two projections or attention's four, the MLP's
    two, and the tied head."""
    h = cfg["hidden_size"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = h // nh
    inner, conv = _sizes(cfg)
    mh = cfg["mamba_n_heads"]
    n_mamba = sum(_mamba(cfg, l) for l in range(cfg["num_hidden_layers"]))
    n_attn = cfg["num_hidden_layers"] - n_mamba
    attn = 2 * h * nh * dh + 2 * h * kvh * dh
    mamba = h * (inner + conv + mh) + inner * h
    mlp = 3 * h * cfg["shared_intermediate_size"]
    state = mh * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return {
        "layers": cfg["num_hidden_layers"], "heads": nh, "kv_heads": kvh,
        "head_dim": dh, "width": h, "vocab": cfg["vocab_size"],
        "window": 0, "sliding_layers": 0, "full_layers": n_attn,
        "ssm_layers": n_mamba, "ssm_heads": mh,
        "ssm_head_dim": cfg["mamba_d_head"],
        "ssm_state": cfg["mamba_d_state"], "ssm_state_elems": state,
        "ssm_conv_dim": conv, "ssm_taps": cfg["mamba_d_conv"],
        "state_itemsize": 2 if cfg["dtype"] == "bfloat16" else 4,
        "parameters": sum(math.prod(s) for _, s, _, _ in leaf_specs(cfg)),
        "matmul_params": n_mamba * mamba + n_attn * attn
        + cfg["num_hidden_layers"] * mlp + h * cfg["vocab_size"]}


def _attention_flops(d, pairs):
    """q . k and p . v over `pairs` (query, key) pairs a head, the
    attention layers only."""
    return d["heads"] * 4 * d["head_dim"] * d["full_layers"] * pairs


def _recurrence_flops(d, tokens):
    """The state update and its read-out a token and Mamba layer: decay
    times H, plus dt x (outer) B, and H C, 2 FLOPs each an element of the
    state."""
    return 6 * d["ssm_state_elems"] * d["ssm_layers"] * tokens


def forward_flops(cfg: dict, new: int, ctx_before: int = 0) -> float:
    """Forward FLOPs of `new` tokens of one sequence that already holds
    `ctx_before`, the head on every position."""
    d = dims(cfg)
    seen = sum(ctx_before + j + 1 for j in range(new))
    return 2 * d["matmul_params"] * new + _attention_flops(d, seen) \
        + _recurrence_flops(d, new)


def prefill_flops(cfg: dict, n: int) -> float:
    """Forward FLOPs of a prompt of `n` tokens as a server needs them: the
    head is applied to the last position only. The recurrence is counted
    as the recurrence, not as the chunked scan's matrix products, which
    are more."""
    d = dims(cfg)
    return forward_flops(cfg, n) - 2 * d["vocab"] * d["width"] * (n - 1)


def decode_flops(cfg: dict, rows: int, sum_ctx: int) -> float:
    """Forward FLOPs of one new token for each of `rows` streams whose
    contexts, the new token included, sum to `sum_ctx`: an attention layer
    reads the whole context, a Mamba layer its state."""
    d = dims(cfg)
    return 2 * d["matmul_params"] * rows + _attention_flops(d, sum_ctx) \
        + _recurrence_flops(d, rows)
