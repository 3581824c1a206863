"""The EXAONE-MoE stack as the program builds it:
`models/exaone_moe.py` served by `serve.ServeEngine` (window and full
attention layers over two kinds of cache, a dense first layer, sparse
layers whose held experts run through `ops/pallas/moe_experts`). One
chip's share of an expert-parallel deployment: the configuration's
`experts_held` of `num_experts_published` routed experts and `vocab_size`
rows of the vocabulary. This file is the only place where the benchmark
knows the program's parameter names and this architecture's dimensions.
Serving only: there is no training count because no cell trains it."""
from __future__ import annotations

import math

REFERENCE = "exaone_moe"


def _kinds(cfg):
    """[(sliding, sparse)] of the layers that are run."""
    return [(cfg["layer_types"][l] == "sliding_attention",
             l >= cfg["first_k_dense_replace"])
            for l in range(cfg["num_hidden_layers"])]


def leaf_specs(cfg: dict):
    """[(program leaf name, shape, init, scale)]: normal(0,
    initializer_range) matrices, norms one, the selection bias zero."""
    h, dh = cfg["hidden_size"], cfg["head_dim"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    i, im = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    std = cfg.get("initializer_range", 0.02)
    w = lambda name, *shape: (name, shape, "normal", std)
    one = lambda name, n: (name, (n,), "ones", 0)
    specs = [w("exaone.embed_tokens.weight", cfg["vocab_size"], h)]
    for l, (_, sparse) in enumerate(_kinds(cfg)):
        p = f"exaone.layers.{l}."
        specs += [w(p + "self_attn.q_proj.weight", h, nh * dh),
                  w(p + "self_attn.k_proj.weight", h, kvh * dh),
                  w(p + "self_attn.v_proj.weight", h, kvh * dh),
                  w(p + "self_attn.o_proj.weight", nh * dh, h),
                  one(p + "self_attn.q_norm.weight", dh),
                  one(p + "self_attn.k_norm.weight", dh)]
        if sparse:
            s = im * cfg["num_shared_experts"]
            specs += [
                w(p + "mlp.gate.weight", h, cfg["num_experts_published"]),
                (p + "mlp.gate.e_score_correction_bias",
                 (cfg["num_experts_published"],), "zeros", 0),
                w(p + "mlp.experts.gate_up_proj", held, h, 2 * im),
                w(p + "mlp.experts.down_proj", held, im, h),
                w(p + "mlp.shared_experts.gate_proj.weight", h, s),
                w(p + "mlp.shared_experts.up_proj.weight", h, s),
                w(p + "mlp.shared_experts.down_proj.weight", s, h)]
        else:
            specs += [w(p + "mlp.gate_proj.weight", h, i),
                      w(p + "mlp.up_proj.weight", h, i),
                      w(p + "mlp.down_proj.weight", i, h)]
        specs += [one(p + "post_attention_layernorm.weight", h),
                  one(p + "post_feedforward_layernorm.weight", h)]
    specs += [one("exaone.norm.weight", h),
              w("lm_head.weight", h, cfg["vocab_size"])]
    return specs


def build_model(cfg: dict, recompute: bool = False):
    """The program's model at the configuration's sizes, in bf16, its
    leaves left for the seeded weights to fill (`deferred_init`: a model
    of this size is not initialised twice)."""
    from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                              ExaoneMoeForCausalLM)

    if cfg["hidden_act"] != "silu":
        raise ValueError("the EXAONE-MoE stack runs SwiGLU")
    config = ExaoneMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], layer_types=tuple(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        scoring_func=cfg["scoring_func"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        experts_held=tuple(cfg["experts_held"]), dtype=cfg["dtype"],
        deferred_init=True)
    return ExaoneMoeForCausalLM(config)


def parts(name: str, shape):
    """No fused leaf is read apart here (no cell trains this stack)."""
    return [("", (Ellipsis,))]


def vocab_size(cfg: dict) -> int:
    """The slice: ids, logits and sampling are over it."""
    return cfg["vocab_size"]


def dims(cfg: dict) -> dict:
    """What the counts below and the files under `work/` count from.
    `matmul_params`: the parameters of the matrix products ONE token
    passes through on this chip: attention's four projections, the dense
    MLP or the router, the shared expert and `top_k x held / published`
    routed experts (the expectation under seeded weights: 1 here), and
    the head's slice."""
    h, dh = cfg["hidden_size"], cfg["head_dim"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * h * nh * dh + 2 * h * kvh * dh
    expert = 3 * h * cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    per_token = cfg["num_experts_per_tok"] * held / cfg[
        "num_experts_published"]
    kinds = _kinds(cfg)
    n_sparse = sum(sparse for _, sparse in kinds)
    dense = 3 * h * cfg["intermediate_size"]
    outside = attn + cfg["num_shared_experts"] * expert \
        + h * cfg["num_experts_published"]
    return {
        "layers": len(kinds), "heads": nh, "kv_heads": kvh, "head_dim": dh,
        "width": h, "vocab": cfg["vocab_size"],
        "window": cfg["sliding_window"],
        "sliding_layers": sum(s for s, _ in kinds),
        "full_layers": sum(not s for s, _ in kinds),
        "sparse_layers": n_sparse, "experts_held": held,
        "top_k": cfg["num_experts_per_tok"],
        "experts_published": cfg["num_experts_published"],
        "expert_params": expert,
        "expert_width": cfg["moe_intermediate_size"],
        "parameters": sum(math.prod(s)
                          for _, s, _, _ in leaf_specs(cfg)),
        "matmul_params": (len(kinds) - n_sparse) * (attn + dense)
        + n_sparse * (outside + per_token * expert)
        + h * cfg["vocab_size"]}


def _attention_flops(d, full_pairs, sliding_pairs):
    return d["heads"] * 4 * d["head_dim"] * (
        d["full_layers"] * full_pairs + d["sliding_layers"] * sliding_pairs)


def forward_flops(cfg: dict, new: int, ctx_before: int = 0) -> float:
    """Forward FLOPs of `new` tokens of one sequence that already holds
    `ctx_before`, the head on every position."""
    d = dims(cfg)
    w = d["window"]
    seen = [ctx_before + j + 1 for j in range(new)]
    return 2 * d["matmul_params"] * new + _attention_flops(
        d, sum(seen), sum(min(s, w) for s in seen))


def prefill_flops(cfg: dict, n: int) -> float:
    """Forward FLOPs of a prompt of `n` tokens as a server needs them: the
    head is applied to the last position only."""
    d = dims(cfg)
    return forward_flops(cfg, n) - 2 * d["vocab"] * d["width"] * (n - 1)


def decode_flops(cfg: dict, rows: int, sum_ctx: int) -> float:
    """Forward FLOPs of one new token for each of `rows` streams whose
    contexts, the new token included, sum to `sum_ctx`: a full layer
    reads the whole context, a sliding layer its window (every decoded
    stream's context is at least a window long in this stack's cells)."""
    d = dims(cfg)
    return 2 * d["matmul_params"] * rows + _attention_flops(
        d, sum_ctx, rows * d["window"])
