"""Granite 4.0-H family (``model_type: granitemoehybrid``): Mamba-2
state-space layers and a few attention layers in one stack, a shared
SwiGLU MLP in every layer, four scalar multipliers.

Source of the shapes: the published ``config.json`` of
ibm-granite/granite-4.0-h-micro (no experts) and of granite-4.0-h-small
(72 routed experts, top 10). What those files do not carry follows
``transformers``' ``modeling_granitemoehybrid.py`` and the
``modeling_bamba.py`` it takes its mixer from: the gate is applied BEFORE
the mixer's norm (``MambaRMSNormGated``: ``RMSNorm(y * silu(z)) * w``), the
norm runs over all the inner channels as one group (``mamba_n_groups``
1), ``time_step_limit`` is (0, inf), i.e. no clamp.

The layer, for input ``x`` (``m`` = ``residual_multiplier``):

- ``x = x + m * mixer(RMSNorm(x))``; ``x = x + m * F(RMSNorm(x))``; the
  shared MLP is ``output_linear(silu(gate) * up)`` with ``[gate | up] =
  input_linear(h)``. Without experts (``num_local_experts`` 0) ``F`` is
  that MLP; with them ``F(u) = sum_{e in S} g_e E_e(u) + MLP(u)``: ``S``
  the ``num_experts_per_tok`` largest router logits ``u W_router`` (no
  bias), ``g = softmax(logits[S])`` over the chosen ones alone, each
  expert ``E_e`` a SwiGLU whose ``input_linear`` is ``[gate | up]`` too
  (``GraniteMoeHybridMoE`` / ``GraniteMoeHybridTopKGating``).
- ``attention`` layers: GQA without bias and WITHOUT any position
  encoding (``position_embedding_type: nope``), scores multiplied by
  ``attention_multiplier`` (1/64 at head size 64: not ``dh ** -0.5``),
  causal softmax.
- ``mamba`` layers: ``[z | xBC | dt] = h W_in`` with widths ``inner |
  inner + 2 n | heads`` (``inner = mamba_n_heads * mamba_d_head``);
  ``xBC = silu(conv1d(xBC) + b)``, causal and depthwise over
  ``mamba_d_conv`` taps; ``[x | B | C] = xBC``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` per head; ``H_t = exp(dt_t A) H_(t-1) +
  dt_t x_t (outer) B_t``, ``y_t = H_t C_t + D x_t`` per head; ``out =
  (RMSNorm(y * silu(z)) * w) W_out``. ``ops/ssm.py`` computes it.
- embedding rows times ``embedding_multiplier``; a final RMSNorm; the
  tied head's logits divided by ``logits_scaling``.

**One chip's share** (``experts_held = (first, count)``): the expert
layer stores only the held experts' matrices, routes over all
``num_local_experts``, keeps the gates normalised over all the chosen
experts, and adds only its own experts' terms and the shared MLP's
(``models/exaone_moe.moe_ffn``); what the absent experts would add is left
out. Nothing here stands in for the other chips.

Serving goes through ``serve.ServeEngine``, which runs
``models/decoder_stack.py`` over :meth:`GraniteHybridForCausalLM.
decode_view` and keeps each Mamba layer's state and convolution tail by
slot beside the paged K/V of the attention layers; ``forward`` here is
the plain whole-sequence pass (the chunked scan from a zero state, a
masked softmax) the tests hold against the reference. ``generate()``'s
dense cache keeps no recurrent state and refuses the family by name.
Training is not claimed: ``forward`` records no graph and the scan has no
backward here.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from .. import nn
from ..core.tensor import Tensor
from .decoder_stack import LayerSpec
from .exaone_moe import _init, _Linear, _Norm, attention_mask

__all__ = ["GraniteHybridConfig", "GraniteHybridForCausalLM",
           "GraniteHybridModel", "GraniteMamba2Mixer", "GraniteMoE"]


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    #: "mamba" | "attention" by layer; None: the published pattern,
    #: attention at layers 5, 15, 25, ... and Mamba-2 elsewhere
    layer_types: Optional[Tuple[str, ...]] = None
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    position_embedding_type: str = "nope"
    #: routed experts of a layer (0: the shared MLP alone); each is
    #: ``intermediate_size`` wide
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    #: (first, count) of the routed experts this chip holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    dtype: str = "float32"
    #: leaves are created as shapes only, for a loader to fill
    deferred_init: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "attention" if l % 10 == 5 else "mamba"
                for l in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types: {self.layer_types}")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")
        for key, want in (("mamba_n_groups", 1),
                          ("position_embedding_type", "nope"),
                          ("mamba_conv_bias", True),
                          ("mamba_proj_bias", False),
                          ("attention_bias", False),
                          ("tie_word_embeddings", True)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"granite_hybrid runs {key}={want!r}, got "
                    f"{getattr(self, key)!r}")
        if self.num_local_experts:
            if not 1 <= self.num_experts_per_tok <= self.num_local_experts:
                raise ValueError(
                    f"num_experts_per_tok {self.num_experts_per_tok} of "
                    f"{self.num_local_experts} experts")
            first, count = self.experts_held or (0, self.num_local_experts)
            if not (0 <= first and count >= 1
                    and first + count <= self.num_local_experts):
                raise ValueError(f"experts_held {self.experts_held} of "
                                 f"{self.num_local_experts}")
            self.experts_held = (int(first), int(count))
        elif self.experts_held is not None:
            raise ValueError("experts_held names experts of a model that "
                             "has none (num_local_experts 0)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def layer_spec(self, layer: int) -> LayerSpec:
        return LayerSpec(
            rope=False, ffn="moe" if self.num_local_experts
            else "swiglu_fused",
            mixer="mamba2" if self.layer_types[layer] == "mamba"
            else "attention")

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        base = dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            shared_intermediate_size=96, num_hidden_layers=4,
            layer_types=("mamba", "attention", "mamba", "mamba"),
            num_attention_heads=4, num_key_value_heads=2,
            attention_multiplier=1 / 16, mamba_n_heads=4, mamba_d_head=32,
            mamba_d_state=16, mamba_chunk_size=8,
            max_position_embeddings=512)
        base.update(kw)
        return GraniteHybridConfig(**base)


# --- the layers ---------------------------------------------------------------
def _drawn(config, draw):
    """An initializer ``draw(key, shape)`` fed from the framework's seed,
    or the shape alone under ``deferred_init``."""
    if config.deferred_init:
        return _init(config)

    def init(shape, dtype):
        from ..core import generator
        from ..core.dtype import convert_dtype

        return draw(generator.next_key(), tuple(shape)).astype(
            convert_dtype(dtype))
    return init


def _constant(config, value):
    return _init(config) if config.deferred_init \
        else nn.initializer.Constant(value)


class _Conv1d(nn.Layer):
    """A causal depthwise convolution's leaves, ``[taps, channels]`` (the
    published ``[channels, 1, taps]`` transposed, so that the channels lie
    along the lanes) and its bias."""

    def __init__(self, config, taps, channels):
        super().__init__()
        import jax

        self.weight = self.create_parameter(
            [taps, channels], dtype=config.dtype,
            default_initializer=_drawn(config, lambda k, s: jax.random.uniform(
                k, s, minval=-1.0, maxval=1.0) / math.sqrt(taps)))
        self.bias = self.create_parameter(
            [channels], dtype=config.dtype,
            default_initializer=_constant(config, 0.0))


class GraniteMamba2Mixer(nn.Layer):
    """A Mamba-2 mixer's leaves. ``A`` is drawn in [1, 16] and ``dt`` in
    [0.001, 0.1] (log-uniform) with ``dt_bias`` its inverse softplus, as
    the published initialisation has them."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        import jax
        import jax.numpy as jnp

        h, nh = config.hidden_size, config.mamba_n_heads
        inner, conv = config.mamba_inner, config.conv_dim
        self.in_proj = _Linear(config, h, inner + conv + nh)
        self.conv1d = _Conv1d(config, config.mamba_d_conv, conv)

        def dt_bias(k, s):
            dt = jnp.exp(jax.random.uniform(
                k, s, minval=math.log(1e-3), maxval=math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus

        self.dt_bias = self.create_parameter(
            [nh], dtype=config.dtype,
            default_initializer=_drawn(config, dt_bias))
        self.A_log = self.create_parameter(
            [nh], dtype=config.dtype, default_initializer=_drawn(
                config, lambda k, s: jnp.log(jax.random.uniform(
                    k, s, minval=1.0, maxval=16.0))))
        self.D = self.create_parameter(
            [nh], dtype=config.dtype,
            default_initializer=_constant(config, 1.0))
        self.norm = _Norm(config, inner)
        self.out_proj = _Linear(config, inner, h)


class GraniteAttention(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        h, dh = config.hidden_size, config.head_dim
        self.q_proj = _Linear(config, h, config.num_attention_heads * dh)
        self.k_proj = _Linear(config, h, config.num_key_value_heads * dh)
        self.v_proj = _Linear(config, h, config.num_key_value_heads * dh)
        self.o_proj = _Linear(config, config.num_attention_heads * dh, h)


class GraniteSharedMLP(nn.Layer):
    """``input_linear`` holds gate and up side by side ``[H, 2 I]``."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        h, i = config.hidden_size, config.shared_intermediate_size
        self.input_linear = _Linear(config, h, 2 * i)
        self.output_linear = _Linear(config, i, h)


class _Experts(nn.Layer):
    """The held experts' matrices of one projection, ``[count, in,
    out]``."""

    def __init__(self, config, n_in, n_out):
        super().__init__()
        self.weight = self.create_parameter(
            [config.experts_held[1], n_in, n_out], dtype=config.dtype,
            default_initializer=_init(config))


class _Router(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.layer = _Linear(config, config.hidden_size,
                             config.num_local_experts)


class GraniteMoE(nn.Layer):
    """The whole router (``router.layer`` ``[H, experts]``: every chip
    routes over all of them) and the held experts: ``input_linear``
    ``[count, H, 2 I]`` (gate and up side by side), ``output_linear``
    ``[count, I, H]``."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.router = _Router(config)
        self.input_linear = _Experts(config, h, 2 * i)
        self.output_linear = _Experts(config, i, h)


class GraniteHybridDecoderLayer(nn.Layer):
    def __init__(self, config: GraniteHybridConfig, layer: int):
        super().__init__()
        if config.layer_types[layer] == "mamba":
            self.mamba = GraniteMamba2Mixer(config)
        else:
            self.self_attn = GraniteAttention(config)
        if config.num_local_experts:
            self.block_sparse_moe = GraniteMoE(config)
        self.shared_mlp = GraniteSharedMLP(config)
        self.input_layernorm = _Norm(config, config.hidden_size)
        self.post_attention_layernorm = _Norm(config, config.hidden_size)


class GraniteHybridModel(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _Linear(config, config.vocab_size,
                                    config.hidden_size)
        self.layers = nn.LayerList([
            GraniteHybridDecoderLayer(config, l)
            for l in range(config.num_hidden_layers)])
        self.norm = _Norm(config, config.hidden_size)


class GraniteHybridForCausalLM(nn.Layer):
    """The tied head reuses ``model.embed_tokens``."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.model = GraniteHybridModel(config)

    def decode_view(self):
        """The parameter view ``models/decoder_stack.py`` runs over a
        cache: one ``LayerSpec`` a layer (the mixer's kind, no RoPE, the
        fused SwiGLU or the expert layer), the attention layers' leaves
        under the Llama view's names, a Mamba layer's under
        ``ops/ssm.py``'s, ``ssm`` (the mixer's sizes), the four scalings
        and, with experts, the expert layer's leaves under ``moe_ffn``'s
        names (the shared MLP as the fused ``w_in``) and ``moe``, its
        router's statics."""
        cfg = self.config
        layers = []
        for l, layer in enumerate(self.model.layers):
            m = layer.shared_mlp
            lp = dict(ln1=layer.input_layernorm.weight._value,
                      ln2=layer.post_attention_layernorm.weight._value,
                      w_in=m.input_linear.weight._value,
                      wd=m.output_linear.weight._value)
            if cfg.layer_types[l] == "mamba":
                s = layer.mamba
                lp.update(in_proj=s.in_proj.weight._value,
                          conv_w=s.conv1d.weight._value,
                          conv_b=s.conv1d.bias._value,
                          dt_bias=s.dt_bias._value, A_log=s.A_log._value,
                          D=s.D._value, gate_norm=s.norm.weight._value,
                          out_proj=s.out_proj.weight._value)
            else:
                a = layer.self_attn
                lp.update(wq=a.q_proj.weight._value,
                          wk=a.k_proj.weight._value,
                          wv=a.v_proj.weight._value,
                          wo=a.o_proj.weight._value)
            if cfg.num_local_experts:
                e = layer.block_sparse_moe
                lp.update(router=e.router.layer.weight._value,
                          gate_up=e.input_linear.weight._value,
                          down=e.output_linear.weight._value)
            layers.append(lp)
        moe = {} if not cfg.num_local_experts else dict(moe=dict(
            top_k=cfg.num_experts_per_tok, scale=1.0, norm_topk=True,
            scoring="softmax", first=cfg.experts_held[0],
            count=cfg.experts_held[1], num_experts=cfg.num_local_experts))
        return dict(
            embed=self.model.embed_tokens.weight._value,
            norm=self.model.norm.weight._value, tied_head=True,
            layers=layers,
            nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
            dh=cfg.head_dim, eps=cfg.rms_norm_eps,
            specs=tuple(cfg.layer_spec(l)
                        for l in range(cfg.num_hidden_layers)),
            prefill="flash",
            ssm=dict(heads=cfg.mamba_n_heads, dh=cfg.mamba_d_head,
                     n=cfg.mamba_d_state, taps=cfg.mamba_d_conv,
                     channels=cfg.conv_dim, chunk=cfg.mamba_chunk_size),
            embedding_multiplier=cfg.embedding_multiplier,
            residual_multiplier=cfg.residual_multiplier,
            logits_scaling=cfg.logits_scaling,
            attn_scale=cfg.attention_multiplier, **moe,
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


def forward_logits(p, ids):
    """[T, vocab] float32 logits of one row of token ids [T]: the stack
    over the plainest caches there are (a layer's cache is its own rows; a
    Mamba layer's scan starts from a zero state and keeps nothing)."""
    import jax
    import jax.numpy as jnp

    from ..ops.ssm import mamba2_prefill
    from .decoder_stack import embed, head_logits, stack_layers

    t = ids.shape[0]
    chunk = p["ssm"]["chunk"]
    rows = t if t <= chunk else -(-t // chunk) * chunk
    padded = jnp.zeros(rows, ids.dtype).at[:t].set(ids)
    nh, kvh, dh = p["nh"], p["nkv"], p["dh"]
    x, rope = embed(p, padded, jnp.arange(rows), rows)
    seen = attention_mask(rows)

    def attn(_i, _spec, q, k, v, _kc, _vc):
        k, v = (jnp.repeat(a, nh // kvh, axis=1) for a in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * p["attn_scale"]
        pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", pr,
                          v.astype(jnp.float32)).reshape(rows, nh * dh)

    def ssm(i, _spec, lp, xbc, dt, cache):
        y, _, _ = mamba2_prefill(lp, p["ssm"], xbc, dt, t,
                                 scope=f"layer{i}/ssm")
        return y, cache

    out, _, _ = stack_layers(
        p, x, rope, [(None, None)] * len(p["layers"]),
        lambda _i, _spec, kc, vc, _k, _v: (kc, vc), attn, ssm=ssm)
    return head_logits(p, out[:t]).astype(jnp.float32)
