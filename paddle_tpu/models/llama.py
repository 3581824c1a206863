"""Llama model family — the flagship LLM.

Reference: test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py
(LlamaAttentionAuto :94, LlamaMLPAuto :305, LlamaRMSNorm, LlamaForCausalLMAuto
:809) — the reference's own fixture for exercising dp/mp/pp combos.

TPU design highlights:
- bf16-friendly: params fp32 (or bf16 with master weights), RMSNorm/softmax
  accumulate fp32.
- attention through scaled_dot_product_attention → Pallas flash kernel on
  TPU, XLA composition elsewhere; GQA via num_key_value_heads.
- RoPE via incubate.fused_rotary_position_embedding.
- ``llama_shard_plan(model, mesh)`` applies the Megatron TP layout +
  sequence-parallel activations over a (dp, mp) mesh — matching the
  placements the reference fixture assigns via shard_tensor.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import paddle_tpu as paddle
from .. import nn
from ..core.tensor import Tensor
from ..incubate.nn.functional import fused_rotary_position_embedding, swiglu
from ..nn import functional as F


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    use_flash_attention: bool = True
    tie_word_embeddings: bool = False
    recompute: bool = False  # activation checkpointing per decoder layer
    # chunked fused lm_head+cross_entropy: never materializes the fp32
    # [tokens, vocab] logits (the single biggest activation at bs*seq*32k —
    # see incubate/nn/functional/fused_linear_ce.py). Only affects the
    # labels-given training path; generation still returns full logits.
    fused_lm_head_ce: bool = True
    # compute-time q|k|v weight concat: one [h, h+2*kv] projection
    # instead of three narrow ones. Parameters stay SEPARATE (shard
    # plans, checkpoints, parity untouched). MEASURED NULL on the 645M
    # bench geometry (v5e, 2026-07-31): fused 0.676 MFU vs separate
    # 0.697 — XLA already co-schedules same-input matmuls, and the
    # per-step weight concat adds HBM traffic the width-curve gain
    # doesn't repay. Kept as an option for genuinely narrow models;
    # off by default.
    fused_qkv: bool = False
    dtype: str = "float32"
    # context parallelism: "ring" | "ulysses" | None. When set, attention
    # runs over the sequence sharded on cp_mesh_axis (fleet.context_parallel
    # — capability the reference lacks, SURVEY §5.7). Sequences longer than
    # one chip's HBM shard across the sep axis of the active mesh.
    context_parallel: Optional[str] = None
    cp_mesh_axis: str = "sep"

    def __post_init__(self):
        if self.context_parallel not in (None, "ring", "ulysses"):
            raise ValueError(
                f"context_parallel must be None, 'ring' or 'ulysses', "
                f"got {self.context_parallel!r}")

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=8192,
            rope_theta=500000.0,
        )

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
        )
        base.update(kw)
        return LlamaConfig(**base)


class LlamaRMSNorm(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.weight = self.create_parameter(
            [config.hidden_size],
            default_initializer=nn.initializer.Constant(1.0),
        )
        self.eps = config.rms_norm_eps
        self.kernel_partition = None    # set by llama_shard_plan

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.eps,
                          partition=self.kernel_partition)


def fused_qkv_linear(x, projs):
    """One wide GEMM against the CONCATENATED weights of ``projs``
    (nn.Linear layers sharing input ``x``), returning per-proj slices.
    Bias is concatenated when every proj has one. Parameters stay
    separate tensors — this is a compute-time fusion only (see
    LlamaConfig.fused_qkv for the measured effect)."""
    from ..ops.manipulation import concat

    w = concat([p.weight for p in projs], axis=1)
    biases = [getattr(p, "bias", None) for p in projs]
    if all(bb is not None for bb in biases):
        b = concat(biases, axis=0)
    elif any(bb is not None for bb in biases):
        raise ValueError(
            "fused_qkv_linear: projections mix bias and bias-free "
            "layers; fuse only uniform projections (or disable "
            "fused_qkv for this model)")
    else:
        b = None
    out = F.linear(x, w, b)
    widths = [p.weight.shape[1] for p in projs]
    slices, off = [], 0
    for wd in widths:
        slices.append(out[..., off:off + wd])
        off += wd
    return slices


class LlamaAttention(nn.Layer):
    """GQA attention (reference fixture LlamaAttentionAuto:94)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = nn.Linear(h, h, bias_attr=False)
        self.k_proj = nn.Linear(h, kv, bias_attr=False)
        self.v_proj = nn.Linear(h, kv, bias_attr=False)
        self.o_proj = nn.Linear(h, h, bias_attr=False)
        self.kernel_partition = None    # set by llama_shard_plan

    def forward(self, hidden_states, position_ids=None, attention_mask=None):
        b, s, h = hidden_states.shape
        if self.config.fused_qkv:
            q, k, v = fused_qkv_linear(
                hidden_states, (self.q_proj, self.k_proj, self.v_proj))
            q = q.reshape([b, s, self.num_heads, self.head_dim])
            k = k.reshape([b, s, self.num_kv_heads, self.head_dim])
            v = v.reshape([b, s, self.num_kv_heads, self.head_dim])
        else:
            q = self.q_proj(hidden_states).reshape(
                [b, s, self.num_heads, self.head_dim])
            k = self.k_proj(hidden_states).reshape(
                [b, s, self.num_kv_heads, self.head_dim])
            v = self.v_proj(hidden_states).reshape(
                [b, s, self.num_kv_heads, self.head_dim])
        q, k, v = fused_rotary_position_embedding(
            q, k, v, position_ids=position_ids,
            use_neox_rotary_style=True, rotary_emb_base=self.config.rope_theta,
        )
        if self.config.context_parallel:
            if attention_mask is not None:
                raise NotImplementedError(
                    "context_parallel attention is causal-only; custom "
                    "attention_mask is not supported under ring/ulysses")
            out = self._cp_attention(q, k, v)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attention_mask,
                is_causal=attention_mask is None,
                partition=self.kernel_partition,
            )
        return self.o_proj(out.reshape([b, s, h]))

    def _cp_attention(self, q, k, v):
        """Ring/Ulysses attention over the sequence-sharded sep axis."""
        from ..distributed.fleet.context_parallel import (
            ring_attention, ulysses_attention,
        )
        from ..ops.manipulation import repeat_interleave

        if self.num_kv_heads != self.num_heads:  # GQA: expand kv heads
            rep = self.num_heads // self.num_kv_heads
            k = repeat_interleave(k, rep, axis=2)
            v = repeat_interleave(v, rep, axis=2)
        fn = {"ring": ring_attention, "ulysses": ulysses_attention}[
            self.config.context_parallel]
        return fn(q, k, v, axis=self.config.cp_mesh_axis, causal=True)


class LlamaMLP(nn.Layer):
    """SwiGLU MLP (reference fixture LlamaMLPAuto:305)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.gate_proj = nn.Linear(config.hidden_size, config.intermediate_size,
                                   bias_attr=False)
        self.up_proj = nn.Linear(config.hidden_size, config.intermediate_size,
                                 bias_attr=False)
        self.down_proj = nn.Linear(config.intermediate_size, config.hidden_size,
                                   bias_attr=False)

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)

    def forward(self, hidden_states, position_ids=None, attention_mask=None):
        residual = hidden_states
        hidden_states = self.input_layernorm(hidden_states)
        hidden_states = self.self_attn(hidden_states, position_ids, attention_mask)
        hidden_states = residual + hidden_states
        residual = hidden_states
        hidden_states = self.post_attention_layernorm(hidden_states)
        hidden_states = self.mlp(hidden_states)
        return residual + hidden_states


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        )
        self.norm = LlamaRMSNorm(config)

    def forward(self, input_ids, position_ids=None, attention_mask=None):
        hidden_states = self.embed_tokens(input_ids)
        if self.config.recompute:
            from ..distributed.fleet.utils import recompute

            for layer in self.layers:
                hidden_states = recompute(
                    layer, hidden_states, position_ids, attention_mask
                )
        else:
            for layer in self.layers:
                hidden_states = layer(hidden_states, position_ids, attention_mask)
        return self.norm(hidden_states)


class LlamaForCausalLM(nn.Layer):
    """Reference fixture LlamaForCausalLMAuto:809."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                labels=None):
        hidden_states = self.llama(input_ids, position_ids, attention_mask)
        if labels is not None and self.config.fused_lm_head_ce:
            from ..incubate.nn.functional.fused_linear_ce import (
                fused_linear_cross_entropy,
            )

            loss = fused_linear_cross_entropy(
                hidden_states.reshape([-1, self.config.hidden_size]),
                self.lm_head.weight,
                labels.reshape([-1]),
                ignore_index=-100,
            )
            return loss, None
        logits = self.lm_head(hidden_states)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]),
                ignore_index=-100,
            )
            return loss, logits
        return logits

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())

    def decode_view(self):
        """The parameter view ``models/decoder_stack.py`` runs over a KV
        cache: the arrays by the stack's names, the statics, and one
        ``LayerSpec`` a layer (the defaults are this family's)."""
        from .decoder_stack import LayerSpec

        cfg = self.config
        layers = []
        for layer in self.llama.layers:
            a, m = layer.self_attn, layer.mlp
            layers.append(dict(
                ln1=layer.input_layernorm.weight._value,
                wq=a.q_proj.weight._value, wk=a.k_proj.weight._value,
                wv=a.v_proj.weight._value, wo=a.o_proj.weight._value,
                ln2=layer.post_attention_layernorm.weight._value,
                wg=m.gate_proj.weight._value, wu=m.up_proj.weight._value,
                wd=m.down_proj.weight._value,
            ))
        return dict(
            embed=self.llama.embed_tokens.weight._value,
            norm=self.llama.norm.weight._value,
            head=self.lm_head.weight._value,
            layers=layers,
            nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
            dh=cfg.hidden_size // cfg.num_attention_heads,
            eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
            specs=(LayerSpec(),) * len(layers),
        )

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id=None, seed: int = 0, pad_token_id=None,
                 num_beams: int = 1, length_penalty: float = 0.0,
                 repetition_penalty: float = 1.0, min_length: int = 0):
        """KV-cache incremental decoding: the whole loop is one jitted
        lax.scan (models/generation.py). Greedy by default; sampling
        via do_sample + temperature/top_k/top_p; ``pad_token_id``
        enables left-padded ragged prompts. Returns
        [B, prompt + max_new_tokens] including the prompt. (Paged
        decoding is ``serve.ServeEngine``'s.)"""
        from .generation import generate as _generate

        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         do_sample=do_sample, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         eos_token_id=eos_token_id, seed=seed,
                         pad_token_id=pad_token_id,
                         num_beams=num_beams,
                         length_penalty=length_penalty,
                         repetition_penalty=repetition_penalty,
                         min_length=min_length)


# ---------------------------------------------------------------------------
# Sharding plan — the semi-auto placements the reference fixture assigns
# (semi_auto_parallel_llama_model.py shard_tensor calls), expressed once.
# ---------------------------------------------------------------------------
def llama_shard_plan(model: LlamaForCausalLM, mesh, dp_axis="dp", mp_axis="mp"):
    """Apply Megatron TP + replicated-DP layout over ``mesh``:

    - embed_tokens.weight:    Shard(0) on mp (vocab parallel)
    - q/k/v/gate/up:          Shard(1) on mp (column parallel)
    - o_proj/down_proj:       Shard(0) on mp (row parallel)
    - lm_head.weight:         Shard(1) on mp
    - norms:                  replicated

    and records on every attention and norm layer where the activations
    live (batch over ``dp_axis``, heads over ``mp_axis``), so the Pallas
    kernels run per shard instead of asking XLA to partition them.
    """
    import paddle_tpu.distributed as dist
    from ..ops.kernel_partition import KernelPartition

    mp = mesh.dim_names.index(mp_axis)
    partition = KernelPartition(
        mesh, batch=dp_axis if dp_axis in mesh.dim_names else None,
        heads=mp_axis)
    for layer in model.sublayers():
        if isinstance(layer, (LlamaAttention, LlamaRMSNorm)):
            layer.kernel_partition = partition

    def place(p, tensor_dim=None):
        placements = [dist.Replicate() for _ in range(mesh.ndim)]
        if tensor_dim is not None:
            placements[mp] = dist.Shard(tensor_dim)
        dist.shard_tensor(p, mesh, placements)

    place(model.llama.embed_tokens.weight, 0)
    for layer in model.llama.layers:
        place(layer.self_attn.q_proj.weight, 1)
        place(layer.self_attn.k_proj.weight, 1)
        place(layer.self_attn.v_proj.weight, 1)
        place(layer.self_attn.o_proj.weight, 0)
        place(layer.mlp.gate_proj.weight, 1)
        place(layer.mlp.up_proj.weight, 1)
        place(layer.mlp.down_proj.weight, 0)
        place(layer.input_layernorm.weight)
        place(layer.post_attention_layernorm.weight)
    place(model.llama.norm.weight)
    place(model.lm_head.weight, 1)
    return model
