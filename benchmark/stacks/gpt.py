"""The GPT stack as the program builds it: `models/gpt.py` behind
`paddle.jit.to_static` for training and `serve.ServeEngine`'s GPT branch
for serving. This file is the only place where the benchmark knows the
program's parameter names and this architecture's dimensions: a new
architecture is a new file here, with `REFERENCE`, `leaf_specs`,
`build_model`, `parts`, `vocab_size`, `dims` and the three counts of the
model's FLOPs (`forward_flops`, `prefill_flops`, `decode_flops`)."""
from __future__ import annotations

from benchmark.harness import counts

REFERENCE = "gpt"


def leaf_specs(cfg: dict):
    """[(program leaf name, shape, init, scale)]: GPT-2's initialisation,
    normal(0, initializer_range) with the two projections into the residual
    stream scaled by 1/sqrt(2 n_layer); norms one, biases zero."""
    h, i, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    std = cfg.get("initializer_range", 0.02)
    res = std / (2 * L) ** 0.5
    specs = [("gpt.wte.weight", (cfg["vocab_size"], h), "normal", std),
             ("gpt.wpe.weight", (cfg["n_positions"], h), "normal", std)]
    for l in range(L):
        p = f"gpt.layers.{l}."
        specs += [
            (p + "norm1.weight", (h,), "ones", 0), (p + "norm1.bias", (h,), "zeros", 0),
            (p + "attn.qkv_proj.weight", (h, 3 * h), "normal", std),
            (p + "attn.qkv_proj.bias", (3 * h,), "zeros", 0),
            (p + "attn.out_proj.weight", (h, h), "normal", res),
            (p + "attn.out_proj.bias", (h,), "zeros", 0),
            (p + "norm2.weight", (h,), "ones", 0), (p + "norm2.bias", (h,), "zeros", 0),
            (p + "linear1.weight", (h, i), "normal", std),
            (p + "linear1.bias", (i,), "zeros", 0),
            (p + "linear2.weight", (i, h), "normal", res),
            (p + "linear2.bias", (h,), "zeros", 0)]
    specs += [("gpt.norm_f.weight", (h,), "ones", 0),
              ("gpt.norm_f.bias", (h,), "zeros", 0)]
    return specs


def build_model(cfg: dict, recompute: bool = False):
    """The program's model at the configuration's sizes, in bf16."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if cfg["activation_function"] != "gelu" or cfg["dtype"] != "bfloat16":
        raise ValueError("the GPT stack runs exact GELU in bfloat16 only")
    config = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_hidden_layers=cfg["n_layer"], num_attention_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"],
        max_position_embeddings=cfg["n_positions"],
        hidden_dropout_prob=cfg["resid_pdrop"],
        attention_probs_dropout_prob=cfg["attn_pdrop"],
        layer_norm_eps=cfg["layer_norm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"], recompute=recompute)
    model = GPTForCausalLM(config)
    model.bfloat16()
    return model


def parts(name: str, shape):
    """[(suffix, index)] of the pieces of a leaf that the comparison reads
    apart: the fused qkv projection is three leaves to the mathematics
    (the key's bias has no gradient under softmax; fused, it would hide in
    the query's and the value's)."""
    if name.endswith("attn.qkv_proj.bias") or name.endswith(
            "attn.qkv_proj.weight"):
        h = shape[-1] // 3
        return [(f"#{t}", (Ellipsis, slice(i * h, (i + 1) * h)))
                for i, t in enumerate("qkv")]
    return [("", (Ellipsis,))]


def vocab_size(cfg: dict) -> int:
    return cfg["vocab_size"]


def dims(cfg: dict) -> dict:
    """What the counts below and the files under `work/` count from.
    `matmul_params`: the parameters of the matrix products a token passes
    through, the layers' four projections and the (tied) head; the
    embeddings are lookups."""
    h, i = cfg["n_embd"], cfg["n_inner"]
    return {"layers": cfg["n_layer"], "heads": cfg["n_head"],
            "kv_heads": cfg["n_head"], "head_dim": h // cfg["n_head"],
            "width": h, "vocab": cfg["vocab_size"],
            "matmul_params": cfg["n_layer"] * (h * 3 * h + h * h + 2 * h * i)
            + h * cfg["vocab_size"]}


def forward_flops(cfg: dict, new: int, ctx_before: int = 0) -> float:
    """Forward FLOPs of `new` tokens of one sequence that already holds
    `ctx_before`, the head on every position (training's forward)."""
    return counts.dense_forward_flops(dims(cfg), new, ctx_before)


def prefill_flops(cfg: dict, n: int) -> float:
    """Forward FLOPs of a prompt of `n` tokens as a server needs them."""
    return counts.dense_prefill_flops(dims(cfg), n)


def decode_flops(cfg: dict, rows: int, sum_ctx: int) -> float:
    """Forward FLOPs of one new token for each of `rows` streams whose
    contexts, the new token included, sum to `sum_ctx`."""
    return counts.dense_decode_flops(dims(cfg), rows, sum_ctx)
