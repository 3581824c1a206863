"""GLM-5 family (``model_type: glm_moe_dsa``): multi-head latent attention
(MLA) in every layer with DeepSeek sparse attention (DSA) over it, dense
leading layers and sparse layers after them whose router is DeepSeek-V3's.

Source of the shapes: the published ``config.json`` of zai-org/GLM-5. The
attention is ``models/deepseek_v2.py``'s at other widths and without YaRN;
the indexer is DeepSeek-V3.2-Exp's ``Indexer`` and the router
DeepSeek-V3's (``transformers``' ``models/deepseek_v3``, sigmoid scores and
a selection bias); what the config does not carry is written from memory
and the benchmark's configuration lists each point under ``assumed``.

The layer, for input ``x`` (pre-norm, ``h = RMSNorm(x)``):

- **MLA**: ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb`` as ``[heads, nope +
  rope] = [q_nope | q_pe]``; ``[c_kv | k_pe] = h W_kva``, ``c =
  RMSNorm(c_kv)``; ``[k_nope | v]`` a head ``= c W_kvb``; RoPE (theta
  ``rope_theta``, no scaling) on ``q_pe`` and the ONE ``k_pe``, half-split
  over the columns as they come (``rope_interleave`` is a fixed
  permutation of ``W_qb``'s and ``W_kva``'s rope columns for a loader);
  softmax scale ``(nope + rope)^-0.5``.
- **Indexer** (``ops/dsa.py``): ``q_I = c_q W_Iq`` (``index_n_heads`` of
  ``index_head_dim``), ``k_I = LayerNorm(h W_Ik)`` (a weight and a bias),
  RoPE on the first ``qk_rope_head_dim`` dims of each, ``w = h W_Iw x
  index_n_heads^-0.5``; ``I[t, s] = index_head_dim^-0.5 x sum_j w[t, j]
  ReLU(q_I[t, j] . k_I[s])`` for ``s <= t`` in float32, and attention's
  softmax runs over the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, s]`` (ties to the earlier position), every other score ``-inf``.
  Left out: the float8 quantisation of ``q_I`` and ``k_I`` and the
  Hadamard rotation that serves it (orthogonal: in exact arithmetic it
  moves no ``I[t, s]``).
- ``x = x + (softmax v) W_o``; ``x = x + F(RMSNorm(x))``.
- layers before ``first_k_dense_replace``: ``F`` a SwiGLU MLP; the others:
  ``s = sigmoid(h W_r)`` in float32 over all ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` the
  ``e_score_correction_bias``, which picks but does not weigh), ``w =
  s[chosen] / sum x routed_scaling_factor``, ``F(h) = sum_e w_e E_e(h) +
  S(h)``, ``S`` one SwiGLU of ``n_shared_experts x moe_intermediate_size``.
- an untied head. The config's ``head_dim`` (64) equals
  ``qk_rope_head_dim`` and is read by nothing here.

**One chip's share** (``experts_held = (first, count)``), as
``models/exaone_moe.py`` has it. The multi-token-prediction module
(``num_nextn_predict_layers``) never enters the main model's logits and is
not built.

Serving goes through ``serve.ServeEngine`` over :meth:`decode_view`
(``LayerSpec.mixer = "mla"`` and the view's ``dsa`` statics): a layer
caches its latent row AND its index key a token, under one block table; a
prompt attends with its own rows expanded through the flash forward under
its selection, a decode step scores the cached index keys
(``ops/pallas/dsa_index``) and attends, absorbed, over the selected latent
rows only (``ops/pallas/mla_sparse_decode``). The ``forward`` here is the
plain whole-sequence pass the tests hold against the reference. Training
of this family is not claimed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from .. import nn
from ..core.tensor import Tensor
from .decoder_stack import LayerSpec
from .deepseek_v2 import DeepseekV2Attention, DeepseekV2Experts
from .exaone_moe import _Linear, _MLP, _Norm, _init, attention_mask

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaForCausalLM", "GlmMoeDsaModel"]


@dataclass
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    first_k_dense_replace: int = 3
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    #: the index key's LayerNorm (DeepSeek-V3.2-Exp's ``LayerNorm``)
    index_norm_eps: float = 1e-6
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 202752
    tie_word_embeddings: bool = False
    #: (first, count) of the routed experts this chip holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"
    #: leaves are created as shapes only (``jax.ShapeDtypeStruct``) for a
    #: loader to fill: a model of this size is never initialised twice
    deferred_init: bool = False

    def __post_init__(self):
        if self.scoring_func != "sigmoid" or self.n_group != 1 \
                or self.topk_group != 1:
            raise ValueError("glm_moe_dsa routes by sigmoid scores in one "
                             "group")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                f"the indexer rotates its first {self.qk_rope_head_dim} "
                f"dims of {self.index_head_dim}")
        if self.index_topk < 1:
            raise ValueError(f"index_topk {self.index_topk}")
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

    @staticmethod
    def tiny(**kw) -> "GlmMoeDsaConfig":
        base = dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, first_k_dense_replace=1, n_routed_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=32,
            index_n_heads=4, index_head_dim=16, index_topk=8,
            max_position_embeddings=512)
        base.update(kw)
        return GlmMoeDsaConfig(**base)


# --- the mathematics, on arrays -------------------------------------------------
def forward_logits(p, ids):
    """[T, vocab] float32 logits of one row of token ids [T]: the plain
    whole-sequence pass, ``decoder_stack.stack_layers`` under a closure
    that keeps no cache (every row expanded, the indexer's ``[T, T]``
    scores whole, a dense softmax masked to each query's selection)."""
    import jax
    import jax.numpy as jnp

    from ..ops import dsa as _dsa
    from ..ops import mla as _mla
    from .decoder_stack import embed, head_logits, stack_layers

    t = ids.shape[0]
    x, rope = embed(p, ids, jnp.arange(t), t)
    st = p["dsa"]

    def mla(_i, _spec, lp, q_nope, q_pe, latent, cache, index):
        k, v = _mla.expand(lp, p["mla"], latent)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * p["attn_scale"]
        q_i, k_i, w = index
        chosen = _dsa.top_mask(
            _dsa.index_scores(q_i, w, k_i, st["dim"] ** -0.5),
            attention_mask(t), st["topk"])
        s = jnp.where(chosen[None], s, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                         v.astype(jnp.float32))
        return ctx.reshape(t, -1), cache

    out, _, _ = stack_layers(p, x, rope, [None] * len(p["layers"]), None,
                             None, mla=mla)
    return head_logits(p, out).astype(jnp.float32)


# --- the layers -------------------------------------------------------------------
class _LayerNorm(nn.Layer):
    def __init__(self, config, size):
        super().__init__()
        self.weight = self.create_parameter(
            [size], dtype=config.dtype,
            default_initializer=nn.initializer.Constant(1.0))
        self.bias = self.create_parameter(
            [size], dtype=config.dtype,
            default_initializer=nn.initializer.Constant(0.0))


class GlmMoeDsaIndexer(nn.Layer):
    """The lightning indexer's leaves (DeepSeek-V3.2-Exp's names)."""

    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        heads, dim = config.index_n_heads, config.index_head_dim
        self.wq_b = _Linear(config, config.q_lora_rank, heads * dim)
        self.wk = _Linear(config, config.hidden_size, dim)
        self.k_norm = _LayerNorm(config, dim)
        self.weights_proj = _Linear(config, config.hidden_size, heads)


class GlmMoeDsaAttention(DeepseekV2Attention):
    """DeepSeek-V2's latent attention leaves and the indexer."""

    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__(config)
        self.indexer = GlmMoeDsaIndexer(config)


class GlmMoeDsaRouter(nn.Layer):
    """The whole router and its selection bias: every chip routes over
    all the experts."""

    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        self.weight = self.create_parameter(
            [config.hidden_size, config.n_routed_experts],
            dtype=config.dtype, default_initializer=_init(config))
        self.e_score_correction_bias = self.create_parameter(
            [config.n_routed_experts], dtype="float32",
            default_initializer=nn.initializer.Constant(0.0))


class GlmMoeDsaSparseBlock(nn.Layer):
    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        self.gate = GlmMoeDsaRouter(config)
        self.experts = DeepseekV2Experts(config)
        self.shared_experts = _MLP(
            config, config.moe_intermediate_size * config.n_shared_experts)


class GlmMoeDsaDecoderLayer(nn.Layer):
    def __init__(self, config: GlmMoeDsaConfig, layer: int):
        super().__init__()
        self.self_attn = GlmMoeDsaAttention(config)
        self.mlp = (GlmMoeDsaSparseBlock(config) if config.is_sparse(layer)
                    else _MLP(config, config.intermediate_size))
        self.input_layernorm = _Norm(config, config.hidden_size)
        self.post_attention_layernorm = _Norm(config, config.hidden_size)


class GlmMoeDsaModel(nn.Layer):
    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _Linear(config, config.vocab_size,
                                    config.hidden_size)
        self.layers = nn.LayerList([
            GlmMoeDsaDecoderLayer(config, l)
            for l in range(config.num_hidden_layers)])
        self.norm = _Norm(config, config.hidden_size)


class GlmMoeDsaForCausalLM(nn.Layer):
    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        if config.tie_word_embeddings:
            raise ValueError("glm_moe_dsa has an untied head")
        self.config = config
        self.model = GlmMoeDsaModel(config)
        self.lm_head = _Linear(config, config.hidden_size, config.vocab_size)

    def decode_view(self):
        """The parameter view ``models/decoder_stack.py`` runs over a
        cache: DeepSeek-V2's (``models/deepseek_v2.py``: the latent
        attention's leaves, ``specs``, the statics ``mla``, ``rope_dim``,
        ``attn_scale``, ``moe``, ``prefill="flash"``) with the router's
        selection bias, the indexer's leaves (``iwq``, ``iwk``,
        ``ikn_w``/``ikn_b``, ``iww``) and its statics ``dsa`` (``heads``,
        ``dim``, ``rope``, ``topk``, ``eps``)."""
        cfg = self.config
        layers = []
        for l, layer in enumerate(self.model.layers):
            a, m = layer.self_attn, layer.mlp
            ix = a.indexer
            lp = dict(
                wqa=a.q_a_proj.weight._value,
                qan=a.q_a_layernorm.weight._value,
                wqb=a.q_b_proj.weight._value,
                wkva=a.kv_a_proj_with_mqa.weight._value,
                kvan=a.kv_a_layernorm.weight._value,
                wkvb=a.kv_b_proj.weight._value, wo=a.o_proj.weight._value,
                iwq=ix.wq_b.weight._value, iwk=ix.wk.weight._value,
                ikn_w=ix.k_norm.weight._value, ikn_b=ix.k_norm.bias._value,
                iww=ix.weights_proj.weight._value,
                ln1=layer.input_layernorm.weight._value,
                ln2=layer.post_attention_layernorm.weight._value)
            if cfg.is_sparse(l):
                lp.update(router=m.gate.weight._value,
                          router_bias=m.gate.e_score_correction_bias._value,
                          gate_up=m.experts.gate_up_proj._value,
                          down=m.experts.down_proj._value)
                m = m.shared_experts
            lp.update(wg=m.gate_proj.weight._value,
                      wu=m.up_proj.weight._value,
                      wd=m.down_proj.weight._value)
            layers.append(lp)
        return dict(
            embed=self.model.embed_tokens.weight._value,
            norm=self.model.norm.weight._value,
            head=self.lm_head.weight._value,
            layers=layers,
            nh=cfg.num_attention_heads, nkv=cfg.num_attention_heads,
            dh=cfg.qk_head_dim, eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
            rope_dim=cfg.qk_rope_head_dim,
            attn_scale=cfg.qk_head_dim ** -0.5,
            mla=dict(nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                     v=cfg.v_head_dim, rank=cfg.kv_lora_rank),
            dsa=dict(heads=cfg.index_n_heads, dim=cfg.index_head_dim,
                     rope=cfg.qk_rope_head_dim, topk=cfg.index_topk,
                     eps=cfg.index_norm_eps),
            specs=tuple(cfg.layer_spec(l)
                        for l in range(cfg.num_hidden_layers)),
            prefill="flash",
            moe=dict(top_k=cfg.num_experts_per_tok,
                     scale=cfg.routed_scaling_factor,
                     norm_topk=cfg.norm_topk_prob, first=cfg.experts_held[0],
                     count=cfg.experts_held[1],
                     num_experts=cfg.n_routed_experts),
        )

    def forward(self, input_ids):
        """[B, T, vocab] float32 logits (inference only: no graph)."""
        import jax.numpy as jnp

        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(np.asarray(input_ids))
        p = self.decode_view()
        return Tensor(jnp.stack([forward_logits(p, row) for row in ids]))

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())
