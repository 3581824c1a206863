"""ERNIE-4.5-MoE-style mixture-of-experts causal LM.

BASELINE.json config 4 ("ERNIE-4.5 MoE — expert-parallel all_to_all over
ICI, fused_moe kernel"). The decoder reuses the Llama attention/RMSNorm
blocks; FFNs alternate between a dense MLP and a FusedMoELayer whose
routing dispatch is the einsum the EP sharding turns into the all-to-all
(incubate/distributed/models/moe). The training loss adds the gates'
load-balancing aux loss, and ``ernie_moe_shard_plan`` lays out Megatron TP
for attention + expert-dim sharding for the expert banks over a dp×mp×ep
mesh.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from .. import nn
from ..nn import functional as F
from ..incubate.distributed.models.moe import FusedMoELayer
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, LlamaRMSNorm

__all__ = ["ErnieMoeConfig", "ErnieMoeForCausalLM", "ErnieMoeModel",
           "ernie_moe_shard_plan"]


@dataclass
class ErnieMoeConfig(LlamaConfig):
    num_experts: int = 8
    moe_top_k: int = 2
    moe_layer_interval: int = 2      # every k-th decoder layer is MoE
    moe_intermediate_size: Optional[int] = None
    aux_loss_weight: float = 0.01
    gate_type: str = "gshard"
    # "swiglu" = ERNIE-4.5's expert form with gate+up CONCATENATED into
    # one [d, 2H] projection (one wide GEMM instead of two narrow ones —
    # see ExpertsFFN); "gelu" keeps the classic 2-GEMM FFN expert
    moe_activation: str = "gelu"

    @staticmethod
    def tiny(**kw) -> "ErnieMoeConfig":
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            num_experts=4, moe_top_k=2, moe_layer_interval=1,
        )
        base.update(kw)
        return ErnieMoeConfig(**base)

    def is_moe_layer(self, idx: int) -> bool:
        return (idx + 1) % self.moe_layer_interval == 0


class ErnieMoeDecoderLayer(nn.Layer):
    def __init__(self, config: ErnieMoeConfig, layer_idx: int,
                 moe_group=None):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)
        self.is_moe = config.is_moe_layer(layer_idx)
        if self.is_moe:
            self.mlp = FusedMoELayer(
                config.hidden_size,
                config.moe_intermediate_size or config.intermediate_size,
                config.num_experts,
                gate={"type": config.gate_type, "topk": config.moe_top_k},
                activation=config.moe_activation,
                moe_group=moe_group,
            )
        else:
            self.mlp = LlamaMLP(config)

    def forward(self, hidden_states, position_ids=None, attention_mask=None):
        residual = hidden_states
        hidden_states = self.input_layernorm(hidden_states)
        hidden_states = self.self_attn(hidden_states, position_ids, attention_mask)
        hidden_states = residual + hidden_states
        residual = hidden_states
        hidden_states = self.post_attention_layernorm(hidden_states)
        hidden_states = self.mlp(hidden_states)
        return residual + hidden_states


class ErnieMoeModel(nn.Layer):
    def __init__(self, config: ErnieMoeConfig, moe_group=None):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([
            ErnieMoeDecoderLayer(config, i, moe_group=moe_group)
            for i in range(config.num_hidden_layers)
        ])
        self.norm = LlamaRMSNorm(config)

    def forward(self, input_ids, position_ids=None, attention_mask=None):
        hidden_states = self.embed_tokens(input_ids)
        if self.config.recompute:
            from ..distributed.fleet.utils import recompute

            for layer in self.layers:
                if layer.is_moe:
                    # MoE layers run un-checkpointed: recompute's no_grad
                    # forward would detach the gate's load-balancing aux
                    # loss, silently un-training the router
                    hidden_states = layer(
                        hidden_states, position_ids, attention_mask
                    )
                else:
                    hidden_states = recompute(
                        layer, hidden_states, position_ids, attention_mask
                    )
        else:
            for layer in self.layers:
                hidden_states = layer(hidden_states, position_ids, attention_mask)
        return self.norm(hidden_states)


def capacity_moe_ffn(h, lp, statics, dtype):
    """Routed expert FFN of the decode view (``decoder_stack``'s
    ``"capacity_moe"`` kind): EVAL GShard/naive routing (top-k softmax
    gate, deterministic) through the same index-dispatch program the
    model's own forward uses (moe_layer._moe_idx_ffn_fwd), so decode and
    full-prefix forward route identically."""
    import jax
    import jax.numpy as jnp

    from ..incubate.distributed.models.moe.gate import _capacity
    from ..incubate.distributed.models.moe.moe_layer import _moe_idx_ffn_fwd

    topk, factor, activation, normalize = statics
    m = lp["moe"]
    shape = h.shape
    x = h.reshape(-1, shape[-1])
    n, e = x.shape[0], m["gw"].shape[1]
    probs = jax.nn.softmax(
        (x @ m["gw"] + m["gb"]).astype(jnp.float32), axis=-1)
    # the SHARED capacity rule (gate._capacity) over THIS call's tokens
    cap = _capacity(n, e, topk, factor)
    out = _moe_idx_ffn_fwd(
        probs, x, m["w0"], m["b0"], m["w1"], m["b1"],
        jax.random.PRNGKey(0), k=topk, capacity=cap,
        activation=activation, normalize=normalize, random2=False)
    return out.astype(dtype).reshape(shape)


class ErnieMoeForCausalLM(nn.Layer):
    def __init__(self, config: ErnieMoeConfig, moe_group=None):
        super().__init__()
        self.config = config
        self.model = ErnieMoeModel(config, moe_group=moe_group)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def moe_aux_loss(self):
        """Sum the gates' pending load-balancing losses (clears them)."""
        total = None
        for layer in self.model.layers:
            gate = getattr(layer.mlp, "gate", None)
            if gate is not None and hasattr(gate, "get_loss"):
                l = gate.get_loss(clear=True)
                if l is not None:
                    total = l if total is None else total + l
        return total

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                labels=None):
        hidden_states = self.model(input_ids, position_ids, attention_mask)
        logits = self.lm_head(hidden_states)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]),
                ignore_index=-100,
            )
            aux = self.moe_aux_loss()
            if aux is not None:
                loss = loss + self.config.aux_loss_weight * aux
            return loss, logits
        return logits

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())

    def decode_view(self):
        """The parameter view ``models/decoder_stack.py`` runs over a KV
        cache: Llama-style attention/norms, per-layer FFN either the
        dense SwiGLU or a routed expert bank (``"capacity_moe"``, its
        routing statics under ``moe_statics``). Generation runs the
        gate's current-mode routing (eval: deterministic top-k, eval
        capacity factor). Expert CAPACITY is computed over the tokens of
        each decode call (prefill: B*prompt_len; steps: B) with the same
        shared formula as the training forward — so decode matches the
        model's full-prefix forward whenever no expert saturates (the
        oracle-pinned regime); when capacity binds, drop behavior is
        per-call, mirroring the reference's step-wise serving ops
        (masked/block MHA process only the step's tokens too)."""
        from .decoder_stack import LayerSpec

        cfg = self.config
        layers, specs, moe_statics = [], [], []
        for layer in self.model.layers:
            a = layer.self_attn
            entry = dict(
                ln1=layer.input_layernorm.weight._value,
                wq=a.q_proj.weight._value, wk=a.k_proj.weight._value,
                wv=a.v_proj.weight._value, wo=a.o_proj.weight._value,
                ln2=layer.post_attention_layernorm.weight._value,
            )
            if layer.is_moe:
                gate, ex = layer.mlp.gate, layer.mlp.experts
                entry["moe"] = dict(
                    gw=gate.weight._value, gb=gate.bias._value,
                    w0=ex.w0._value, b0=ex.b0._value,
                    w1=ex.w1._value, b1=ex.b1._value,
                )
                # routing statics live OUTSIDE the layer dict: the layers
                # list rides as a jit ARGUMENT, and a string inside it
                # would break tracing. _train_factor() already respects
                # gate.training (GShard: capacity[0] train / [1] eval;
                # Naive: flat factor).
                moe_statics.append((int(gate.topk),
                                    float(gate._train_factor()),
                                    ex.activation, bool(gate._normalize)))
            else:
                m = layer.mlp
                entry.update(wg=m.gate_proj.weight._value,
                             wu=m.up_proj.weight._value,
                             wd=m.down_proj.weight._value)
                moe_statics.append(None)
            specs.append(LayerSpec(
                ffn="capacity_moe" if layer.is_moe else "swiglu"))
            layers.append(entry)
        return dict(
            embed=self.model.embed_tokens.weight._value,
            norm=self.model.norm.weight._value,
            head=self.lm_head.weight._value,
            layers=layers,
            moe_statics=tuple(moe_statics),   # hashable -> static_cfg
            nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
            dh=cfg.hidden_size // cfg.num_attention_heads,
            eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
            specs=tuple(specs),
        )

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id=None, seed: int = 0,
                 num_beams: int = 1, length_penalty: float = 0.0,
                 repetition_penalty: float = 1.0, min_length: int = 0):
        """KV-cache incremental decoding for the MoE family — the same
        single-jit scan as Llama (models/generation.py) with the
        routed-expert FFN run per step through the index-dispatch
        program (EVAL routing: deterministic top-k, eval capacity)."""
        from .generation import generate as _generate

        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         do_sample=do_sample, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         eos_token_id=eos_token_id, seed=seed,
                         num_beams=num_beams,
                         length_penalty=length_penalty,
                         repetition_penalty=repetition_penalty,
                         min_length=min_length)


def ernie_moe_shard_plan(model: ErnieMoeForCausalLM, mesh, mp_axis="mp",
                         ep_axis="ep"):
    """mp×ep layout: Megatron TP on attention/dense-MLP/vocab (when
    ``mp_axis`` exists in the mesh), expert-dim sharding on the fused expert
    banks (GSPMD turns the routing einsums into the all_to_all the reference
    issues via global_scatter/global_gather). Data parallelism needs no
    parameter placement — it comes from sharding the batch inputs."""
    import paddle_tpu.distributed as dist

    mp = mesh.dim_names.index(mp_axis) if mp_axis in mesh.dim_names else None
    ep = mesh.dim_names.index(ep_axis) if ep_axis in mesh.dim_names else None

    def place(p, dim=None, axis_idx=None):
        placements = [dist.Replicate() for _ in range(mesh.ndim)]
        target = mp if axis_idx is None else axis_idx
        if dim is not None and target is not None:
            placements[target] = dist.Shard(dim)
        dist.shard_tensor(p, mesh, placements)

    place(model.model.embed_tokens.weight, 0)
    place(model.lm_head.weight, 1)
    for layer in model.model.layers:
        place(layer.self_attn.q_proj.weight, 1)
        place(layer.self_attn.k_proj.weight, 1)
        place(layer.self_attn.v_proj.weight, 1)
        place(layer.self_attn.o_proj.weight, 0)
        if layer.is_moe:
            experts = layer.mlp.experts
            for w in (experts.w0, experts.b0, experts.w1, experts.b1):
                if ep is not None:
                    place(w, 0, axis_idx=ep)   # expert dim
                else:
                    place(w)
            if hasattr(layer.mlp.gate, "weight"):
                place(layer.mlp.gate.weight)
        else:
            place(layer.mlp.gate_proj.weight, 1)
            place(layer.mlp.up_proj.weight, 1)
            place(layer.mlp.down_proj.weight, 0)
    return model
