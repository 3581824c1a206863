"""The GLM-5 stack as the program builds it: `models/glm_moe_dsa.py`
served by `serve.ServeEngine` (latent attention in every layer with
DeepSeek sparse attention over it: a lightning indexer whose keys the
engine caches beside the latent rows, the `index_topk` best rows attended;
a dense first layer; sparse layers with a sigmoid router whose held experts
run through `ops/pallas/moe_experts`). One chip's share of an
expert-parallel deployment: the configuration's `experts_held` of
`n_routed_experts_published` routed experts and `vocab_size` rows of the
vocabulary. This file is the only place where the benchmark knows the
program's parameter names and this architecture's dimensions. Serving
only: no cell trains it."""
from __future__ import annotations

import math

REFERENCE = "glm_moe_dsa"


def _sparse(cfg):
    return [l >= cfg["first_k_dense_replace"]
            for l in range(cfg["num_hidden_layers"])]


def leaf_specs(cfg: dict):
    """[(program leaf name, shape, init, scale)]: normal(0,
    initializer_range) matrices, norms one, the index key's norm bias and
    the router's selection bias zero."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank, qr = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    i, im = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    std = cfg.get("initializer_range", 0.02)
    w = lambda name, *shape: (name, shape, "normal", std)
    one = lambda name, n: (name, (n,), "ones", 0)
    zero = lambda name, n: (name, (n,), "zeros", 0)
    specs = [w("model.embed_tokens.weight", cfg["vocab_size"], h)]
    for l, sparse in enumerate(_sparse(cfg)):
        p = f"model.layers.{l}."
        a = p + "self_attn."
        x = a + "indexer."
        specs += [w(a + "q_a_proj.weight", h, qr),
                  one(a + "q_a_layernorm.weight", qr),
                  w(a + "q_b_proj.weight", qr, nh * (nope + rope)),
                  w(a + "kv_a_proj_with_mqa.weight", h, rank + rope),
                  one(a + "kv_a_layernorm.weight", rank),
                  w(a + "kv_b_proj.weight", rank, nh * (nope + dv)),
                  w(a + "o_proj.weight", nh * dv, h),
                  w(x + "wq_b.weight", qr, ih * idim),
                  w(x + "wk.weight", h, idim),
                  one(x + "k_norm.weight", idim),
                  zero(x + "k_norm.bias", idim),
                  w(x + "weights_proj.weight", h, ih)]
        if sparse:
            s = im * cfg["n_shared_experts"]
            e = cfg["n_routed_experts_published"]
            specs += [
                w(p + "mlp.gate.weight", h, e),
                zero(p + "mlp.gate.e_score_correction_bias", e),
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
    its leaves left for the seeded weights to fill (`deferred_init`)."""
    from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                               GlmMoeDsaForCausalLM)

    if cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("the GLM-5 stack runs SwiGLU and bias-free "
                         "attention")
    if cfg["moe_layer_freq"] != 1 or cfg["topk_method"] != "noaux_tc":
        raise ValueError("every layer after the dense ones is sparse, "
                         "routed by noaux_tc")
    if cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] != \
            cfg["qk_head_dim"]:
        raise ValueError("qk_head_dim is nope + rope")
    config = GlmMoeDsaConfig(
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
        scoring_func=cfg["scoring_func"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        index_norm_eps=cfg["index_norm_eps"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        experts_held=tuple(cfg["experts_held"]), dtype=cfg["dtype"],
        deferred_init=True)
    return GlmMoeDsaForCausalLM(config)


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
    once (`kv_b` whole a token), the output projection, the indexer's three
    products, the dense MLP or the router, the shared expert and `top_k x
    held / published` routed experts (the expectation under seeded
    weights: 0.5 here), and the head's slice."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank, qr = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    kv_b = rank * nh * (nope + dv)
    attn = h * qr + qr * nh * (nope + rope) + h * (rank + rope) + kv_b \
        + nh * dv * h
    indexer = qr * ih * idim + h * idim + h * ih
    expert = 3 * h * cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    published = cfg["n_routed_experts_published"]
    per_token = cfg["num_experts_per_tok"] * held / published
    sparse = _sparse(cfg)
    n_sparse = sum(sparse)
    dense = 3 * h * cfg["intermediate_size"]
    outside = attn + indexer + cfg["n_shared_experts"] * expert \
        + h * published
    return {
        "layers": len(sparse), "heads": nh, "width": h,
        "vocab": cfg["vocab_size"], "latent_rank": rank, "rope_dim": rope,
        "nope_dim": nope, "v_dim": dv, "kv_b_params": kv_b,
        "index_heads": ih, "index_dim": idim,
        "index_topk": cfg["index_topk"],
        "sparse_layers": n_sparse, "experts_held": held,
        "top_k": cfg["num_experts_per_tok"],
        "experts_published": published,
        "expert_params": expert,
        "expert_width": cfg["moe_intermediate_size"],
        "parameters": sum(math.prod(s)
                          for _, s, _, _ in leaf_specs(cfg)),
        "matmul_params": (len(sparse) - n_sparse) * (attn + indexer + dense)
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


def _index_pair_flops(d) -> int:
    """The indexer's score of one (query, key) pair: a product of `dim`
    a head."""
    return d["index_heads"] * d["index_dim"] * 2


def _selected(n: int, topk: int) -> int:
    """Pairs a prompt of `n` attends: query `t` sees `min(t + 1, topk)`."""
    full = min(n, topk)
    return full * (full + 1) // 2 + (n - full) * topk


def prefill_flops(cfg: dict, n: int) -> float:
    """Forward FLOPs of a prompt of `n` tokens as a server needs them:
    every row expanded through `kv_b` once (which `matmul_params` counts a
    token), attention at 256 and 256 over each query's selected keys (every
    causal key up to `index_topk`, then `index_topk` a query), the
    indexer's `n (n + 1) / 2` causal pairs, the head on the last position
    only."""
    d = dims(cfg)
    return 2 * d["matmul_params"] * n + d["layers"] * (
        _expanded_pair_flops(d) * _selected(n, d["index_topk"])
        + _index_pair_flops(d) * (n * (n + 1) // 2)) \
        - 2 * d["vocab"] * d["width"] * (n - 1)


def decode_flops(cfg: dict, rows: int, sum_ctx: int) -> float:
    """Forward FLOPs of one new token for each of `rows` streams whose
    contexts, the new token included, sum to `sum_ctx`: the indexer over
    every context token and, absorbed, attention over `min(rows x
    index_topk, sum_ctx)` rows. That is exact where every stream holds
    `index_topk` rows or more (the cell's shortest prompt is 3,072) and an
    upper bound elsewhere, where a stream shorter than `index_topk`
    attends all of its own."""
    d = dims(cfg)
    return 2 * d["matmul_params"] * rows + d["layers"] * (
        _index_pair_flops(d) * sum_ctx
        + _absorbed_pair_flops(d) * min(rows * d["index_topk"], sum_ctx))
