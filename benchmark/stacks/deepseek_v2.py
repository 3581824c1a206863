"""The DeepSeek-V2 stack as the program builds it:
`models/deepseek_v2.py` served by `serve.ServeEngine` (latent attention in
every layer over one pool of rows a layer, a dense first layer, sparse
layers whose router is group-limited and whose held experts run through
`ops/pallas/moe_experts`). One chip's share of an expert-parallel
deployment: the configuration's `experts_held` (one routing group) of
`n_routed_experts_published` routed experts and `vocab_size` rows of the
vocabulary. This file is the only place where the benchmark knows the
program's parameter names and this architecture's dimensions.
Serving only: there is no training count because no cell trains it."""
from __future__ import annotations

import math

REFERENCE = "deepseek_v2"


def _sparse(cfg):
    return [l >= cfg["first_k_dense_replace"]
            for l in range(cfg["num_hidden_layers"])]


def leaf_specs(cfg: dict):
    """[(program leaf name, shape, init, scale)]: normal(0,
    initializer_range) matrices, norms one."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank, qr = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    i, im = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    std = cfg.get("initializer_range", 0.02)
    w = lambda name, *shape: (name, shape, "normal", std)
    one = lambda name, n: (name, (n,), "ones", 0)
    specs = [w("model.embed_tokens.weight", cfg["vocab_size"], h)]
    for l, sparse in enumerate(_sparse(cfg)):
        p = f"model.layers.{l}."
        a = p + "self_attn."
        specs += [w(a + "q_a_proj.weight", h, qr),
                  one(a + "q_a_layernorm.weight", qr),
                  w(a + "q_b_proj.weight", qr, nh * (nope + rope)),
                  w(a + "kv_a_proj_with_mqa.weight", h, rank + rope),
                  one(a + "kv_a_layernorm.weight", rank),
                  w(a + "kv_b_proj.weight", rank, nh * (nope + dv)),
                  w(a + "o_proj.weight", nh * dv, h)]
        if sparse:
            s = im * cfg["n_shared_experts"]
            specs += [
                w(p + "mlp.gate.weight", h, cfg["n_routed_experts_published"]),
                w(p + "mlp.experts.gate_up_proj", held, h, 2 * im),
                w(p + "mlp.experts.down_proj", held, im, h),
                w(p + "mlp.shared_experts.gate_proj.weight", h, s),
                w(p + "mlp.shared_experts.up_proj.weight", h, s),
                w(p + "mlp.shared_experts.down_proj.weight", s, h)]
        else:
            specs += [w(p + "mlp.gate_proj.weight", h, i),
                      w(p + "mlp.up_proj.weight", h, i),
                      w(p + "mlp.down_proj.weight", i, h)]
        specs += [one(p + "input_layernorm.weight", h),
                  one(p + "post_attention_layernorm.weight", h)]
    specs += [one("model.norm.weight", h),
              w("lm_head.weight", h, cfg["vocab_size"])]
    return specs


def build_model(cfg: dict, recompute: bool = False):
    """The program's model at the configuration's sizes, in its `dtype`,
    its leaves left for the seeded weights to fill (`deferred_init`: a
    model of this size is not initialised twice)."""
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)

    if cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("the DeepSeek-V2 stack runs SwiGLU and bias-free "
                         "attention")
    if cfg["moe_layer_freq"] != 1:
        raise ValueError("every layer after the dense ones is sparse")
    config = DeepseekV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["n_routed_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        scoring_func=cfg["scoring_func"], topk_method=cfg["topk_method"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        experts_held=tuple(cfg["experts_held"]), dtype=cfg["dtype"],
        deferred_init=True)
    return DeepseekV2ForCausalLM(config)


def parts(name: str, shape):
    """No fused leaf is read apart here (no cell trains this stack)."""
    return [("", (Ellipsis,))]


def vocab_size(cfg: dict) -> int:
    """The slice: ids, logits and sampling are over it."""
    return cfg["vocab_size"]


def dims(cfg: dict) -> dict:
    """What the counts below and the files under `work/` count from.
    `matmul_params`: the parameters of the matrix products ONE token
    passes through on this chip with the expansion ABSORBED (a decode
    step): the two query products, the joint compression, the expansion
    once (its key half into the query, its value half out of the result:
    `kv_b` whole a token), the output projection, the dense MLP or the
    router, the shared experts and `top_k x held / published` routed
    experts (the expectation under seeded weights: 0.75 here), and the
    head's slice."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank, qr = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    kv_b = rank * nh * (nope + dv)
    attn = h * qr + qr * nh * (nope + rope) + h * (rank + rope) + kv_b \
        + nh * dv * h
    expert = 3 * h * cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    per_token = cfg["num_experts_per_tok"] * held / cfg[
        "n_routed_experts_published"]
    sparse = _sparse(cfg)
    n_sparse = sum(sparse)
    dense = 3 * h * cfg["intermediate_size"]
    outside = attn + cfg["n_shared_experts"] * expert \
        + h * cfg["n_routed_experts_published"]
    return {
        "layers": len(sparse), "heads": nh, "width": h,
        "vocab": cfg["vocab_size"], "latent_rank": rank, "rope_dim": rope,
        "nope_dim": nope, "v_dim": dv, "kv_b_params": kv_b,
        "sparse_layers": n_sparse, "experts_held": held,
        "top_k": cfg["num_experts_per_tok"],
        "experts_published": cfg["n_routed_experts_published"],
        "expert_params": expert,
        "expert_width": cfg["moe_intermediate_size"],
        "parameters": sum(math.prod(s)
                          for _, s, _, _ in leaf_specs(cfg)),
        "matmul_params": (len(sparse) - n_sparse) * (attn + dense)
        + n_sparse * (outside + per_token * expert)
        + h * cfg["vocab_size"]}


def _absorbed_pair_flops(d) -> int:
    """A head's score against a cached row and its value's share of the
    result, absorbed: `rank + rope` and `rank` multiply-adds."""
    return d["heads"] * (2 * d["latent_rank"] + d["rope_dim"]) * 2


def _expanded_pair_flops(d) -> int:
    """The same within a prompt, expanded: a query-key head of `nope +
    rope` and a value head of `v`."""
    return d["heads"] * (d["nope_dim"] + d["rope_dim"] + d["v_dim"]) * 2


def forward_flops(cfg: dict, new: int, ctx_before: int = 0) -> float:
    """Forward FLOPs of `new` tokens of one sequence that already holds
    `ctx_before`, the head on every position: the new tokens attend among
    themselves expanded and to what is cached absorbed."""
    d = dims(cfg)
    return 2 * d["matmul_params"] * new + d["layers"] * (
        _expanded_pair_flops(d) * (new * (new + 1) // 2)
        + _absorbed_pair_flops(d) * new * ctx_before)


def prefill_flops(cfg: dict, n: int) -> float:
    """Forward FLOPs of a prompt of `n` tokens as a server needs them:
    every row expanded through `kv_b` once (which `matmul_params` counts a
    token), causal attention at 192 and 128, the head on the last position
    only."""
    d = dims(cfg)
    return forward_flops(cfg, n) - 2 * d["vocab"] * d["width"] * (n - 1)


def decode_flops(cfg: dict, rows: int, sum_ctx: int) -> float:
    """Forward FLOPs of one new token for each of `rows` streams whose
    contexts, the new token included, sum to `sum_ctx`, with the
    expansion absorbed: 128 x (576 + 512) x 2 a context token and layer at
    the published sizes. A decode path that expanded its cached tokens
    would do a hundred times the work and read a hundredth of the
    share."""
    d = dims(cfg)
    return 2 * d["matmul_params"] * rows \
        + d["layers"] * _absorbed_pair_flops(d) * sum_ctx
