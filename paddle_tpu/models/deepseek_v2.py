"""DeepSeek-V2 family (``model_type: deepseek_v2``): multi-head latent
attention (MLA) in every layer, a dense first layer and sparse layers
after it whose router is group-limited.

Source of the shapes: the published ``config.json`` of
deepseek-ai/DeepSeek-V2 and the paper (arXiv:2405.04434, §2.1 for the
attention, §2.2 for the experts and their device-limited routing). What
the config does not carry follows the model repository's
``modeling_deepseek.py`` / ``transformers``' ``models/deepseek_v2`` (from
memory; the benchmark's configuration lists each point under
``assumed``): an RMSNorm on the query's and on the key-value latent, RoPE
on ``qk_rope_head_dim`` of a head's query-key dims only, YaRN frequencies
with the softmax scale times ``mscale ** 2``.

The layer, for input ``x`` (pre-norm, ``h = RMSNorm(x)``):

- ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb`` as ``[heads, nope + rope]``
  ``= [q_nope | q_pe]``; ``[c_kv | k_pe] = h W_kva``, ``c =
  RMSNorm(c_kv)``; ``[k_nope | v]`` a head ``= c W_kvb``; ``q_pe`` and the
  ONE ``k_pe`` rotated (half-split pairing over the ``rope`` dims as the
  weights' columns come: the published checkpoint interleaves them, which
  for a loader is a fixed permutation of ``W_qb``'s and ``W_kva``'s rope
  columns); ``k = [k_nope | k_pe]``; causal softmax of ``q k^T x
  (nope + rope)^-0.5 x mscale^2``; ``a = (softmax v) W_o``.
- ``x = x + a``; ``x = x + F(RMSNorm(x))``.
- dense layers: ``F`` a SwiGLU MLP; sparse layers: ``s = softmax(h W_r)``
  in float32 over all ``n_routed_experts``, a group of ``n_routed_experts
  / n_group`` experts scored by its best, the ``topk_group`` best groups
  kept, the ``num_experts_per_tok`` largest of what is left chosen, ``w =
  s[chosen] x routed_scaling_factor`` (not renormalised), ``F(h) = sum_e
  w_e E_e(h) + S(h)`` with ``S`` ONE SwiGLU of ``n_shared_experts x
  moe_intermediate_size``.

**One chip's share** (``experts_held = (first, count)``), as
``models/exaone_moe.py`` has it: the expert layer stores only the held
experts' matrices, routes over all of them, adds only its own experts'
terms and the shared expert's. The paper's device-limited routing is the
deployment this names: ``n_group`` devices hold a group each.

Serving goes through ``serve.ServeEngine`` over :meth:`decode_view`
(``LayerSpec.mixer = "mla"``): the cache is ONE row a token and layer
(``[c | k_pe]``, ``kv_lora_rank + qk_rope_head_dim`` numbers), a prompt
attends with its own rows expanded and a decode step with the expansion
absorbed into the query (``ops/mla.py``, ``ops/pallas/mla_decode.py``).
The ``forward`` here is the plain whole-sequence pass the tests hold
against the reference. Training of this family is not claimed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

from .. import nn
from ..core.tensor import Tensor
from .decoder_stack import LayerSpec
from .exaone_moe import _Linear, _MLP, _Norm, _init, attention_mask

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM", "DeepseekV2Model",
           "yarn_mscale"]


def yarn_mscale(factor: float, mscale: float) -> float:
    """``yarn_get_mscale``: ``0.1 x mscale x ln(factor) + 1`` past a
    factor of 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn():
    return dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                mscale=0.707, mscale_all_dim=0.707,
                original_max_position_embeddings=4096)


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    n_routed_experts: int = 160
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    moe_intermediate_size: int = 1536
    scoring_func: str = "softmax"
    topk_method: str = "group_limited_greedy"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    n_group: int = 8
    topk_group: int = 3
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: the config's ``rope_scaling`` (YaRN), or None for plain RoPE
    rope_scaling: Optional[dict] = field(default_factory=_yarn)
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False
    #: (first, count) of the routed experts this chip holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"
    #: leaves are created as shapes only (``jax.ShapeDtypeStruct``) for a
    #: loader to fill: a model of this size is never initialised twice
    deferred_init: bool = False

    def __post_init__(self):
        if self.scoring_func != "softmax" \
                or self.topk_method != "group_limited_greedy":
            raise ValueError("deepseek_v2 routes by softmax scores, "
                             "group-limited")
        if self.n_routed_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.n_routed_experts} experts in {self.n_group} groups, "
                f"{self.topk_group} kept")
        if self.rope_scaling is not None \
                and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling: {self.rope_scaling}")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts}")
        self.experts_held = (int(first), int(count))

    def is_sparse(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def layer_spec(self, layer: int) -> LayerSpec:
        return LayerSpec(mixer="mla",
                         ffn="moe" if self.is_sparse(layer) else "swiglu")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        """``qk_head_dim ** -0.5``, times ``mscale ** 2`` under YaRN."""
        scale = self.qk_head_dim ** -0.5
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim"):
            scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return scale

    def rope_statics(self) -> Optional[dict]:
        """``decoder_stack.yarn_tables``' keywords, or None."""
        rs = self.rope_scaling
        if not rs:
            return None
        return dict(
            factor=rs["factor"], beta_fast=rs["beta_fast"],
            beta_slow=rs["beta_slow"],
            original=rs["original_max_position_embeddings"],
            mscale=yarn_mscale(rs["factor"], rs.get("mscale", 1))
            / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))

    @staticmethod
    def tiny(**kw) -> "DeepseekV2Config":
        base = dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=16, num_experts_per_tok=3,
            n_shared_experts=2, moe_intermediate_size=32, n_group=8,
            topk_group=3, max_position_embeddings=512,
            rope_scaling=dict(_yarn(), factor=4,
                              original_max_position_embeddings=32))
        base.update(kw)
        return DeepseekV2Config(**base)


# --- the mathematics, on arrays -------------------------------------------------
def forward_logits(p, ids):
    """[T, vocab] float32 logits of one row of token ids [T]: the plain
    whole-sequence pass, ``decoder_stack.stack_layers`` under a closure
    that keeps no cache (every row expanded, a dense masked softmax)."""
    import jax
    import jax.numpy as jnp

    from ..ops import mla as _mla
    from .decoder_stack import embed, head_logits, stack_layers

    t = ids.shape[0]
    x, rope = embed(p, ids, jnp.arange(t), t)

    def mla(_i, _spec, lp, q_nope, q_pe, latent, cache):
        k, v = _mla.expand(lp, p["mla"], latent)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * p["attn_scale"]
        s = jnp.where(attention_mask(t)[None], s, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                         v.astype(jnp.float32))
        return ctx.reshape(t, -1), cache

    out, _, _ = stack_layers(p, x, rope, [None] * len(p["layers"]), None,
                             None, mla=mla)
    return head_logits(p, out).astype(jnp.float32)


# --- the layers -------------------------------------------------------------------
class DeepseekV2Attention(nn.Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        h, nh = config.hidden_size, config.num_attention_heads
        rank = config.kv_lora_rank
        self.q_a_proj = _Linear(config, h, config.q_lora_rank)
        self.q_a_layernorm = _Norm(config, config.q_lora_rank)
        self.q_b_proj = _Linear(config, config.q_lora_rank,
                                nh * config.qk_head_dim)
        self.kv_a_proj_with_mqa = _Linear(
            config, h, rank + config.qk_rope_head_dim)
        self.kv_a_layernorm = _Norm(config, rank)
        self.kv_b_proj = _Linear(
            config, rank,
            nh * (config.qk_nope_head_dim + config.v_head_dim))
        self.o_proj = _Linear(config, nh * config.v_head_dim, h)


class DeepseekV2Router(nn.Layer):
    """The whole router (no selection bias): every chip routes over all
    the experts."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.weight = self.create_parameter(
            [config.hidden_size, config.n_routed_experts],
            dtype=config.dtype, default_initializer=_init(config))


class DeepseekV2Experts(nn.Layer):
    """The held experts' matrices and nothing of the others': gate and
    up side by side ``[count, H, 2 I]``, down ``[count, I, H]``."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        count = config.experts_held[1]
        h, i = config.hidden_size, config.moe_intermediate_size
        self.gate_up_proj = self.create_parameter(
            [count, h, 2 * i], dtype=config.dtype,
            default_initializer=_init(config))
        self.down_proj = self.create_parameter(
            [count, i, h], dtype=config.dtype,
            default_initializer=_init(config))


class DeepseekV2SparseBlock(nn.Layer):
    """Router, held experts and the shared experts (one SwiGLU of their
    joint width) of one sparse layer."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.gate = DeepseekV2Router(config)
        self.experts = DeepseekV2Experts(config)
        self.shared_experts = _MLP(
            config, config.moe_intermediate_size * config.n_shared_experts)


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, config: DeepseekV2Config, layer: int):
        super().__init__()
        self.self_attn = DeepseekV2Attention(config)
        self.mlp = (DeepseekV2SparseBlock(config) if config.is_sparse(layer)
                    else _MLP(config, config.intermediate_size))
        self.input_layernorm = _Norm(config, config.hidden_size)
        self.post_attention_layernorm = _Norm(config, config.hidden_size)


class DeepseekV2Model(nn.Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = _Linear(config, config.vocab_size,
                                    config.hidden_size)
        self.layers = nn.LayerList([
            DeepseekV2DecoderLayer(config, l)
            for l in range(config.num_hidden_layers)])
        self.norm = _Norm(config, config.hidden_size)


class DeepseekV2ForCausalLM(nn.Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        if config.tie_word_embeddings:
            raise ValueError("deepseek_v2 has an untied head")
        self.config = config
        self.model = DeepseekV2Model(config)
        self.lm_head = _Linear(config, config.hidden_size, config.vocab_size)

    def decode_view(self):
        """The parameter view ``models/decoder_stack.py`` runs over a
        cache: the Llama view's names where the leaves mean the same,
        the latent attention's own (``wqa``, ``qan``, ``wqb``, ``wkva``,
        ``kvan``, ``wkvb``), ``specs`` (every layer a ``mla`` mixer, a
        dense or an expert FFN), the statics ``mla`` (the head's sizes
        and the latent's rank), ``rope_dim`` / ``rope_scaling`` (YaRN
        over the rotated dims), ``attn_scale``, ``moe`` (the router's
        statics and the held experts) and ``prefill="flash"``."""
        cfg = self.config
        layers = []
        for l, layer in enumerate(self.model.layers):
            a, m = layer.self_attn, layer.mlp
            lp = dict(
                wqa=a.q_a_proj.weight._value,
                qan=a.q_a_layernorm.weight._value,
                wqb=a.q_b_proj.weight._value,
                wkva=a.kv_a_proj_with_mqa.weight._value,
                kvan=a.kv_a_layernorm.weight._value,
                wkvb=a.kv_b_proj.weight._value, wo=a.o_proj.weight._value,
                ln1=layer.input_layernorm.weight._value,
                ln2=layer.post_attention_layernorm.weight._value)
            if cfg.is_sparse(l):
                lp.update(router=m.gate.weight._value,
                          gate_up=m.experts.gate_up_proj._value,
                          down=m.experts.down_proj._value)
                m = m.shared_experts
            lp.update(wg=m.gate_proj.weight._value,
                      wu=m.up_proj.weight._value,
                      wd=m.down_proj.weight._value)
            layers.append(lp)
        view = dict(
            embed=self.model.embed_tokens.weight._value,
            norm=self.model.norm.weight._value,
            head=self.lm_head.weight._value,
            layers=layers,
            nh=cfg.num_attention_heads, nkv=cfg.num_attention_heads,
            dh=cfg.qk_head_dim, eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
            rope_dim=cfg.qk_rope_head_dim, attn_scale=cfg.attn_scale,
            mla=dict(nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                     v=cfg.v_head_dim, rank=cfg.kv_lora_rank),
            specs=tuple(cfg.layer_spec(l)
                        for l in range(cfg.num_hidden_layers)),
            prefill="flash",
            moe=dict(top_k=cfg.num_experts_per_tok,
                     scale=cfg.routed_scaling_factor,
                     norm_topk=cfg.norm_topk_prob, first=cfg.experts_held[0],
                     count=cfg.experts_held[1],
                     num_experts=cfg.n_routed_experts, scoring="softmax",
                     n_group=cfg.n_group, topk_group=cfg.topk_group),
        )
        if cfg.rope_scaling:
            view["rope_scaling"] = cfg.rope_statics()
        return view

    def forward(self, input_ids):
        """[B, T, vocab] float32 logits (inference only: no graph)."""
        import jax.numpy as jnp

        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(np.asarray(input_ids))
        p = self.decode_view()
        return Tensor(jnp.stack([forward_logits(p, row) for row in ids]))

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())
