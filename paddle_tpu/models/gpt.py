"""GPT model family (GPT-2/3 style decoder).

Reference: the reference's GPT workloads run through
fleet/meta_parallel + the fused_multi_transformer big-op
(fluid/operators/fused/fused_multi_transformer_op.cu); the architecture
here is the standard pre-LN causal decoder with learned positions, laid
out for the MXU (attention via scaled_dot_product_attention → Pallas
flash on TPU) with XLA doing the fused_multi_transformer-style fusion.
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "GPTDecoderLayer",
           "gpt_shard_plan"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    # GPT-2's attn_pdrop; runs inside the Pallas flash kernel (causal +
    # dropout compose in-kernel, ops/pallas/flash_attention.py)
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    recompute: bool = False

    @staticmethod
    def gpt2() -> "GPTConfig":
        return GPTConfig()

    @staticmethod
    def gpt2_medium() -> "GPTConfig":
        return GPTConfig(hidden_size=1024, num_hidden_layers=24,
                         num_attention_heads=16, intermediate_size=4096)

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=128,
                    max_position_embeddings=64)
        base.update(kw)
        return GPTConfig(**base)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        self.qkv_proj = nn.Linear(h, 3 * h)
        self.out_proj = nn.Linear(h, h)

    def forward(self, x):
        b, s, h = x.shape
        qkv = self.qkv_proj(x).reshape([b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = F.scaled_dot_product_attention(
            q, k, v, dropout_p=self.config.attention_probs_dropout_prob,
            is_causal=True, training=self.training)
        return self.out_proj(out.reshape([b, s, h]))


class GPTDecoderLayer(nn.Layer):
    """Pre-LN block: x + attn(ln(x)); x + mlp(ln(x))."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.norm1 = nn.LayerNorm(config.hidden_size,
                                  epsilon=config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.norm2 = nn.LayerNorm(config.hidden_size,
                                  epsilon=config.layer_norm_eps)
        self.linear1 = nn.Linear(config.hidden_size, config.intermediate_size)
        self.linear2 = nn.Linear(config.intermediate_size, config.hidden_size)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, x):
        x = x + self.attn(self.norm1(x))
        return x + self.dropout(self.linear2(F.gelu(self.linear1(self.norm2(x)))))


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        )
        self.norm_f = nn.LayerNorm(config.hidden_size,
                                   epsilon=config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None):
        import paddle_tpu as paddle

        b, s = input_ids.shape
        if position_ids is None:
            position_ids = paddle.arange(s, dtype="int64").unsqueeze(0)
        x = self.drop(self.wte(input_ids) + self.wpe(position_ids))
        if self.config.recompute:
            from ..distributed.fleet.utils import recompute

            for layer in self.layers:
                x = recompute(layer, x)
        else:
            for layer in self.layers:
                x = layer(x)
        return self.norm_f(x)

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())


class GPTForCausalLM(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def decode_view(self):
        """The parameter view ``models/decoder_stack.py`` runs over a KV
        cache: learned positions, pre-LN, fused qkv, GELU
        (``GPT_LAYER``)."""
        from .decoder_stack import GPT_LAYER

        cfg = self.config
        layers = []
        for layer in self.gpt.layers:
            a = layer.attn
            layers.append(dict(
                ln1_w=layer.norm1.weight._value,
                ln1_b=layer.norm1.bias._value,
                wqkv=a.qkv_proj.weight._value, bqkv=a.qkv_proj.bias._value,
                wo=a.out_proj.weight._value, bo=a.out_proj.bias._value,
                ln2_w=layer.norm2.weight._value,
                ln2_b=layer.norm2.bias._value,
                w1=layer.linear1.weight._value, b1=layer.linear1.bias._value,
                w2=layer.linear2.weight._value, b2=layer.linear2.bias._value,
            ))
        out = dict(
            embed=self.gpt.wte.weight._value,
            wpe=self.gpt.wpe.weight._value,
            normf_w=self.gpt.norm_f.weight._value,
            normf_b=self.gpt.norm_f.bias._value,
            layers=layers,
            nh=cfg.num_attention_heads, nkv=cfg.num_attention_heads,
            dh=cfg.hidden_size // cfg.num_attention_heads,
            eps=cfg.layer_norm_eps,
            # tied head: logits = hidden @ embed.T computed in-graph (a
            # materialized transpose would duplicate [V, H] on device)
            tied_head=bool(cfg.tie_word_embeddings),
            max_positions=int(cfg.max_position_embeddings),
            specs=(GPT_LAYER,) * len(layers),
        )
        if not cfg.tie_word_embeddings:
            out["head"] = self.lm_head.weight._value
        return out

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id=None, seed: int = 0, pad_token_id=None,
                 num_beams: int = 1, length_penalty: float = 0.0,
                 repetition_penalty: float = 1.0, min_length: int = 0):
        """KV-cache incremental decoding — one jitted lax.scan over a
        dense cache (models/generation.py, same driver as Llama);
        ``pad_token_id`` enables left-padded ragged prompts."""
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

    def forward(self, input_ids, position_ids=None, labels=None):
        import paddle_tpu as paddle

        hidden = self.gpt(input_ids, position_ids)
        if self.config.tie_word_embeddings:
            logits = paddle.matmul(hidden, self.gpt.wte.weight,
                                   transpose_y=True)
        else:
            logits = self.lm_head(hidden)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]), ignore_index=-100)
            return loss, logits
        return logits

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())


def gpt_shard_plan(model: GPTForCausalLM, mesh, dp_axis="dp", mp_axis="mp"):
    """Megatron TP layout: qkv/linear1 column-parallel, out/linear2
    row-parallel, token embeddings vocab-parallel."""
    import paddle_tpu.distributed as dist

    mp = mesh.dim_names.index(mp_axis)

    def place(p, tensor_dim=None):
        placements = [dist.Replicate() for _ in range(mesh.ndim)]
        if tensor_dim is not None:
            placements[mp] = dist.Shard(tensor_dim)
        dist.shard_tensor(p, mesh, placements)

    place(model.gpt.wte.weight, 0)
    for layer in model.gpt.layers:
        place(layer.attn.qkv_proj.weight, 1)
        place(layer.attn.qkv_proj.bias, 0)
        place(layer.attn.out_proj.weight, 0)
        place(layer.linear1.weight, 1)
        place(layer.linear1.bias, 0)
        place(layer.linear2.weight, 0)
    if not model.config.tie_word_embeddings:
        place(model.lm_head.weight, 1)
    return model
