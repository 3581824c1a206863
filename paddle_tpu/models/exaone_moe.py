"""EXAONE-MoE family (``model_type: exaone_moe``): window and full
attention layers mixed, a dense first layer and sparse layers after it.

Source of the shapes: the published ``config.json`` of
LGAI-EXAONE/K-EXAONE-236B-A23B. What that file does not carry follows
the family's convention as ``transformers`` 4.57 has it on disk for its
sibling (``models/exaone4/modeling_exaone4.py``: RMSNorm on q and k per
head, RoPE on the sliding layers only, the norms AFTER each sub-layer)
and, for the router, ``models/deepseek_v3/modeling_deepseek_v3.py``,
whose keys this config uses (sigmoid scores, a selection bias that picks
but does not weigh, top-k renormalised and scaled).

The layer, for input ``x``:

- ``q = RMSNorm_dh(W_q x)`` per head, ``k = RMSNorm_dh(W_k x)``,
  ``v = W_v x``; on ``sliding_attention`` layers q and k are rotated
  (half-split RoPE), on ``full_attention`` layers they are not; causal
  GQA attention, on a sliding layer key ``j`` visible to query ``i`` iff
  ``0 <= i - j < sliding_window``; ``a = W_o attn``.
- ``x = x + RMSNorm(a)``; ``x = x + RMSNorm(F(x))``.
- dense layers: ``F`` a SwiGLU MLP; sparse layers: ``s = sigmoid(W_r x)``
  in float32, the ``top_k`` of ``s + b`` chosen, ``w = s[chosen] /
  (sum + 1e-20) * routed_scaling_factor``, ``F(x) = sum_e w_e E_e(x) +
  S(x)`` with no capacity and no dropped token.

**One chip's share** (``experts_held = (first, count)``): the expert
layer stores only the held experts' matrices, routes over all
``num_experts``, and adds only its own experts' terms and the shared
expert's; what the absent experts would add is left out and the partial
sum goes on. Nothing here stands in for the other chips.

The expert products run through ``ops/pallas/moe_experts`` (grouped by
expert under fixed shapes, the group sizes being data). Serving goes
through ``serve.ServeEngine``, which runs ``models/decoder_stack.py``
over ``ExaoneMoeForCausalLM.decode_view()`` (the parameter view and one
``LayerSpec`` a layer); the ``forward`` here is the plain whole-sequence
pass the tests hold against the reference.
Training of this family is not claimed: ``forward`` records no graph.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from .. import nn
from ..core.tensor import Tensor
from .decoder_stack import LayerSpec

__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM", "ExaoneMoeModel",
           "ExaoneMoeExperts", "ExaoneMoeSparseBlock", "LayerSpec"]

#: tokens a sparse FFN takes at once: a longer prompt goes through in
#: pieces, so that the grouped buffers stay a piece's size
MOE_CHUNK = 2048


@dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: "sliding_attention" | "full_attention" by layer; None: the
    #: published pattern, three sliding layers and then a full one
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 128
    first_k_dense_replace: int = 1
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    #: (first, count) of the routed experts this chip holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"
    #: leaves are created as shapes only (``jax.ShapeDtypeStruct``) for a
    #: loader to fill: a model of this size is never initialised twice
    deferred_init: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "full_attention" if l % 4 == 3 else "sliding_attention"
                for l in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"sliding_attention", "full_attention"}:
            raise ValueError(f"layer_types: {self.layer_types}")
        if self.scoring_func != "sigmoid" or self.n_group != 1 \
                or self.topk_group != 1:
            raise ValueError("exaone_moe routes by sigmoid scores in one "
                             "group")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        self.experts_held = (int(first), int(count))

    def is_sparse(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def layer_spec(self, layer: int) -> LayerSpec:
        sliding = self.layer_types[layer] == "sliding_attention"
        return LayerSpec(
            placement="post", rope=sliding, qk_norm=True,
            window=self.sliding_window if sliding else None,
            ffn="moe" if self.is_sparse(layer) else "swiglu")

    @staticmethod
    def tiny(**kw) -> "ExaoneMoeConfig":
        base = dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window=8,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            max_position_embeddings=512)
        base.update(kw)
        return ExaoneMoeConfig(**base)


# --- the mathematics, on arrays (model forward and serving stack alike) ----
def route(h, router_w, bias, *, top_k, scale, norm_topk=True,
          scoring="sigmoid", n_group=1, topk_group=1):
    """(weights [T, top_k] float32, experts [T, top_k] int32) of the
    router, in float32 whatever ``h`` is. ``scoring``: ``"sigmoid"``
    scores (this family) or a ``"softmax"`` over the experts
    (``models/deepseek_v2.py``); ``bias`` (or None) picks but does not
    weigh. With ``n_group > 1`` the selection is group-limited: the
    experts lie in ``n_group`` groups side by side, a group scores as
    its best expert, the ``topk_group`` best groups are kept, the others'
    scores are zeroed, and the ``top_k`` are chosen of what is left."""
    return _route(h, router_w, bias, top_k=top_k, scale=scale,
                  norm_topk=norm_topk, scoring=scoring, n_group=n_group,
                  topk_group=topk_group)[:2]


def _route(h, router_w, bias, *, top_k, scale, norm_topk, scoring, n_group,
           topk_group):
    """:func:`route` and, third, the groups a token keeps ``[T, n_group]``
    bool (None for a router with one group)."""
    import jax
    import jax.numpy as jnp

    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"route: unknown scoring {scoring!r}")
    logits = jnp.dot(
        h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    pick = s if bias is None else s + bias.astype(jnp.float32)
    kept = None
    if n_group > 1:
        t, e = pick.shape
        best = jnp.max(pick.reshape(t, n_group, e // n_group), axis=-1)
        _, groups = jax.lax.top_k(best, topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
        pick = jnp.where(jnp.repeat(kept, e // n_group, axis=1), pick, 0.0)
    _, experts = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, experts, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, experts.astype(jnp.int32), kept


def moe_ffn(h, lp, st, dtype, *, valid=None, backend="auto", scope=None):
    """The sparse FFN on ``h`` ``[T, H]`` for one chip's share: the held
    experts' part of the routed sum and the shared expert. ``lp`` holds
    ``router`` [H, E], ``router_bias`` [E], ``gate_up`` [count, H, 2 I],
    ``down`` [count, I, H] and the shared expert's ``wg``/``wu``/``wd``;
    ``st`` the statics (``top_k``, ``scale``, ``norm_topk``, ``first``
    and, where they are not this family's, ``scoring``, ``n_group``,
    ``topk_group``); a router without ``router_bias`` has none.
    Rows where ``valid`` is false (idle slots, a bucket's padding) are
    routed nowhere. Returns (``[T, H]``, held group sizes ``[count]``); a
    group-limited router's sizes are one number longer: the tokens whose
    kept groups include one that holds a held expert."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.moe_experts import experts_ffn, scoped
    from .decoder_stack import swiglu_ffn

    t = h.shape[0]
    if t > MOE_CHUNK and t % MOE_CHUNK == 0:
        n = t // MOE_CHUNK
        ok = jnp.ones(t, bool) if valid is None else valid
        out, sizes = jax.lax.map(
            lambda a: moe_ffn(a[0], lp, st, dtype, valid=a[1],
                              backend=backend, scope=scope),
            (h.reshape(n, MOE_CHUNK, -1), ok.reshape(n, MOE_CHUNK)))
        return out.reshape(t, -1), jnp.sum(sizes, axis=0)
    named = scoped(scope)
    with named("router"):
        w, experts, kept = _route(
            h, lp["router"], lp.get("router_bias"), top_k=st["top_k"],
            scale=st["scale"], norm_topk=st["norm_topk"],
            scoring=st.get("scoring", "sigmoid"),
            n_group=st.get("n_group", 1), topk_group=st.get("topk_group", 1))
        if valid is not None:
            experts = jnp.where(valid[:, None], experts, -1)
    routed, sizes = experts_ffn(h, w, experts, lp["gate_up"], lp["down"],
                                first=st["first"], backend=backend,
                                scope=scope)
    if kept is not None:
        with named("router"):
            # the groups that hold a held expert: a static slice
            per = lp["router"].shape[1] // kept.shape[1]
            last = st["first"] + lp["gate_up"].shape[0] - 1
            here = jnp.any(kept[:, st["first"] // per:last // per + 1],
                           axis=1)
            if valid is not None:
                here &= valid
            sizes = jnp.concatenate(
                [sizes, jnp.sum(here, dtype=sizes.dtype)[None]])
    with named("shared"):
        shared = swiglu_ffn(h, lp, dtype)
    return (routed + shared.astype(jnp.float32)).astype(dtype), sizes


def attention_mask(tq, window=None):
    """[tq, tq] bool: causal, and within ``window`` keys where given."""
    import jax.numpy as jnp

    i = jnp.arange(tq)[:, None]
    j = jnp.arange(tq)[None, :]
    seen = j <= i
    if window is not None:
        seen &= (i - j) < window
    return seen


def forward_logits(p, ids):
    """[T, vocab] float32 logits of one row of token ids [T]: the plain
    whole-sequence pass (dense masked softmax, no cache)."""
    import jax
    import jax.numpy as jnp

    from ..incubate.nn.functional import _rope_tables
    from ..incubate.nn.functional._rope_common import rotate_half
    from .decoder_stack import head_logits, rms, swiglu_ffn

    dtype = p["embed"].dtype
    t = ids.shape[0]
    nh, kvh, dh, eps = p["nh"], p["nkv"], p["dh"], p["eps"]
    cos, sin = _rope_tables(t, dh, p["theta"], True, jnp.float32)
    cos, sin = cos[:, None, :], sin[:, None, :]
    x = jnp.take(p["embed"], ids, axis=0)
    for lp, spec in zip(p["layers"], p["specs"]):
        q = rms((x @ lp["wq"]).reshape(t, nh, dh), lp["qn"], eps, dtype)
        k = rms((x @ lp["wk"]).reshape(t, kvh, dh), lp["kn"], eps, dtype)
        v = (x @ lp["wv"]).reshape(t, kvh, dh)
        if spec.rope:
            q, k = ((a.astype(jnp.float32) * cos
                     + rotate_half(a.astype(jnp.float32), True) * sin
                     ).astype(dtype) for a in (q, k))
        k = jnp.repeat(k, nh // kvh, axis=1)
        v = jnp.repeat(v, nh // kvh, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * dh ** -0.5
        s = jnp.where(attention_mask(t, spec.window)[None], s, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                         v.astype(jnp.float32)).reshape(t, nh * dh)
        x = x + rms(ctx.astype(dtype) @ lp["wo"], lp["ln1"], eps, dtype)
        if spec.ffn == "moe":
            f, _ = moe_ffn(x, lp, p["moe"], dtype)
        else:
            f = swiglu_ffn(x, lp, dtype)
        x = x + rms(f, lp["ln2"], eps, dtype)
    return head_logits(p, rms(x, p["norm"], eps, dtype)).astype(
        jnp.float32)


# --- the layers ---------------------------------------------------------------
def _init(config, std=0.02):
    """The initializer of this model's leaves: normal(0, std), or the
    shape alone under ``deferred_init``."""
    if not config.deferred_init:
        return nn.initializer.Normal(0.0, std)
    import jax

    from ..core.dtype import convert_dtype

    return lambda shape, dtype: jax.ShapeDtypeStruct(
        tuple(shape), convert_dtype(dtype))


class _Linear(nn.Layer):
    """A bias-free ``[in, out]`` projection's leaf."""

    def __init__(self, config, n_in, n_out):
        super().__init__()
        self.weight = self.create_parameter(
            [n_in, n_out], dtype=config.dtype,
            default_initializer=_init(config))


class _Norm(nn.Layer):
    def __init__(self, config, size):
        super().__init__()
        self.weight = self.create_parameter(
            [size], dtype=config.dtype,
            default_initializer=nn.initializer.Constant(1.0))


class _MLP(nn.Layer):
    """SwiGLU leaves under LlamaMLP's names."""

    def __init__(self, config, width):
        super().__init__()
        h = config.hidden_size
        self.gate_proj = _Linear(config, h, width)
        self.up_proj = _Linear(config, h, width)
        self.down_proj = _Linear(config, width, h)


class ExaoneMoeAttention(nn.Layer):
    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        h, dh = config.hidden_size, config.head_dim
        self.q_proj = _Linear(config, h, config.num_attention_heads * dh)
        self.k_proj = _Linear(config, h, config.num_key_value_heads * dh)
        self.v_proj = _Linear(config, h, config.num_key_value_heads * dh)
        self.o_proj = _Linear(config, config.num_attention_heads * dh, h)
        self.q_norm = _Norm(config, dh)
        self.k_norm = _Norm(config, dh)


class ExaoneMoeRouter(nn.Layer):
    """The whole router: every chip routes over all the experts."""

    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        self.weight = self.create_parameter(
            [config.hidden_size, config.num_experts], dtype=config.dtype,
            default_initializer=_init(config))
        self.e_score_correction_bias = self.create_parameter(
            [config.num_experts], dtype="float32",
            default_initializer=nn.initializer.Constant(0.0))


class ExaoneMoeExperts(nn.Layer):
    """The held experts' matrices and nothing of the others': gate and
    up side by side ``[count, H, 2 I]``, down ``[count, I, H]``."""

    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        count = config.experts_held[1]
        h, i = config.hidden_size, config.moe_intermediate_size
        self.gate_up_proj = self.create_parameter(
            [count, h, 2 * i], dtype=config.dtype,
            default_initializer=_init(config))
        self.down_proj = self.create_parameter(
            [count, i, h], dtype=config.dtype,
            default_initializer=_init(config))


class ExaoneMoeSparseBlock(nn.Layer):
    """Router, held experts and the shared expert of one sparse layer."""

    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        self.gate = ExaoneMoeRouter(config)
        self.experts = ExaoneMoeExperts(config)
        self.shared_experts = _MLP(
            config, config.moe_intermediate_size * config.num_shared_experts)


class ExaoneMoeDecoderLayer(nn.Layer):
    def __init__(self, config: ExaoneMoeConfig, layer: int):
        super().__init__()
        self.self_attn = ExaoneMoeAttention(config)
        self.mlp = (ExaoneMoeSparseBlock(config) if config.is_sparse(layer)
                    else _MLP(config, config.intermediate_size))
        self.post_attention_layernorm = _Norm(config, config.hidden_size)
        self.post_feedforward_layernorm = _Norm(config, config.hidden_size)


class ExaoneMoeModel(nn.Layer):
    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _Linear(config, config.vocab_size,
                                    config.hidden_size)
        self.layers = nn.LayerList([
            ExaoneMoeDecoderLayer(config, l)
            for l in range(config.num_hidden_layers)])
        self.norm = _Norm(config, config.hidden_size)


class ExaoneMoeForCausalLM(nn.Layer):
    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        if config.tie_word_embeddings:
            raise ValueError("exaone_moe has an untied head")
        self.config = config
        self.exaone = ExaoneMoeModel(config)
        self.lm_head = _Linear(config, config.hidden_size, config.vocab_size)

    def decode_view(self):
        """The parameter view ``models/decoder_stack.py`` runs over a KV
        cache: the Llama view's names where the leaves mean the same,
        ``specs`` (one ``LayerSpec`` a layer: attention kind and window,
        RoPE or none, q/k norm, norm placement, dense or expert FFN),
        ``moe`` (the router's statics and the held experts) and
        ``prefill="flash"`` for ``ServeEngine``."""
        cfg = self.config
        layers = []
        for l, layer in enumerate(self.exaone.layers):
            a, m = layer.self_attn, layer.mlp
            lp = dict(
                wq=a.q_proj.weight._value, wk=a.k_proj.weight._value,
                wv=a.v_proj.weight._value, wo=a.o_proj.weight._value,
                qn=a.q_norm.weight._value, kn=a.k_norm.weight._value,
                ln1=layer.post_attention_layernorm.weight._value,
                ln2=layer.post_feedforward_layernorm.weight._value)
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
            embed=self.exaone.embed_tokens.weight._value,
            norm=self.exaone.norm.weight._value,
            head=self.lm_head.weight._value,
            layers=layers,
            nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
            dh=cfg.head_dim, eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
            specs=tuple(cfg.layer_spec(l)
                        for l in range(cfg.num_hidden_layers)),
            prefill="flash",
            moe=dict(top_k=cfg.num_experts_per_tok,
                     scale=cfg.routed_scaling_factor,
                     norm_topk=cfg.norm_topk_prob, first=cfg.experts_held[0],
                     count=cfg.experts_held[1], num_experts=cfg.num_experts),
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
