"""Granite 4.0-H with routed experts (the H-Small shape: a softmax top-k
router, held experts and a shared SwiGLU after the mixer of every layer)
through the program, at tiny widths on the CPU, against the benchmark's
plain reference (`benchmark/reference/granite_moe_hybrid.py`, which imports
nothing of the program) on seeded weights: the model's forward,
`ServeEngine` prefill then decode with Mamba and attention layers both
present, the expert-parallel shares adding up to the uncut layer, the
router as published, the parameter counts of the published and the cut
configurations, what the configuration refuses, that a model without
experts is built as before, and the scopes and counters of a decode
step."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu  # noqa: E402,F401
from benchmark.harness import weights as _weights  # noqa: E402
from benchmark.reference import granite_moe_hybrid as ref  # noqa: E402
from benchmark.stacks import granite_hybrid as dense_stack  # noqa: E402
from benchmark.stacks import granite_moe_hybrid as stack  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.models import exaone_moe  # noqa: E402
from paddle_tpu.models import granite_hybrid as prog  # noqa: E402
from paddle_tpu.serve import ServeEngine  # noqa: E402

#: float32 program against float32 reference: the same sums in other
#: orders (a chunked scan, a one-token step, a grouped product)
F32_GAP = 1e-3
SMALL = os.path.join(ROOT, "benchmark", "configs", "granite-4.0-h-small.json")


def tiny_cfg(**kw):
    """Both kinds of layer, heads of 64, 8 experts of width 32 of which
    this chip holds 4 (the first half), 3 a token; a shared MLP of 48."""
    cfg = {
        "hidden_act": "silu", "normalization_function": "rmsnorm",
        "hidden_size": 128, "intermediate_size": 32,
        "shared_intermediate_size": 48, "num_hidden_layers": 3,
        "layer_types": ["mamba", "attention", "mamba"],
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "attention_multiplier": 1 / 64, "attention_bias": False,
        "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 16,
        "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_expand": 2,
        "mamba_chunk_size": 8, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16,
        "position_embedding_type": "nope", "num_local_experts": 4,
        "num_experts_published": 8, "experts_held": [0, 4],
        "num_experts_per_tok": 3, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 512, "tie_word_embeddings": True,
        "vocab_size": 128, "dtype": "float32", "initializer_range": 0.5}
    cfg.update(kw)
    return cfg


def seeded(cfg, seed=7):
    """(the program's model holding the seeded weights, the reference's
    parameters of the same weights)."""
    w = _weights.make_weights(stack.leaf_specs(cfg), seed, cfg["dtype"])
    model = stack.build_model(cfg)
    for n, p in model.named_parameters():
        assert tuple(p.shape) == tuple(w[n].shape), n
        p._replace_value(w[n])
    model.eval()
    return model, ref.stack_params(w, cfg)


def test_leaf_names_are_the_programs():
    cfg = tiny_cfg()
    model = stack.build_model(cfg)
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} == {
        n: tuple(s) for n, s, _, _ in stack.leaf_specs(cfg)}
    assert model.num_parameters() == stack.dims(cfg)["parameters"]
    e = "model.layers.1.block_sparse_moe."
    shapes = dict((n, tuple(p.shape)) for n, p in model.named_parameters())
    assert shapes[e + "router.layer.weight"] == (128, 8)    # all 8 routed
    assert shapes[e + "input_linear.weight"] == (4, 128, 64)  # 4 held
    assert shapes[e + "output_linear.weight"] == (4, 32, 128)


def test_model_forward_matches_the_reference():
    """Rows of 40 (five whole chunks), 21 and 5 through both kinds of
    layer, each followed by its expert layer."""
    cfg = tiny_cfg()
    model, params = seeded(cfg)
    rng = np.random.default_rng(0)
    for t in (40, 21, 5):
        ids = rng.integers(1, 128, (1, t))
        got = np.asarray(model(paddle_tpu.to_tensor(ids))._value)[0]
        want = np.asarray(ref.logits_of(params, ids[0], cfg))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_engine_prefill_then_decode_is_the_reference(backend):
    """Four requests through two slots (so that two inherit a slot another
    stream left), prompts not a multiple of the chunk: every served token
    is the reference's best at its position, computed over the whole row
    at once; the held experts go through `moe_experts` under the Pallas
    interpreter on the second backend. (Seed 6: greedy text on seeded
    weights soon repeats itself; on this seed the streams hold 2-8
    distinct tokens.)"""
    cfg = tiny_cfg()
    model, params = seeded(cfg, seed=6)
    eng = ServeEngine(model, max_slots=2, block_size=4, num_blocks=48,
                      max_seq_len=64, attention_backend=backend,
                      name=f"grm-{backend}")
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
            for n, m in [(13, 9), (5, 14), (21, 6), (9, 8)]]
    eng.run()
    assert eng.decode_traces == 1
    for r in reqs:
        gaps = ref.served_gaps(params, cfg, np.asarray(r.prompt),
                               np.asarray(r.output_ids), 64)
        assert len(gaps) == r.max_new_tokens
        assert gaps.max() < F32_GAP, gaps
    assert any(len(set(r.output_ids)) > 2 for r in reqs)


def _layer(cfg, seed=3, t=24):
    w = _weights.make_weights(stack.leaf_specs(cfg), seed, "float32")
    lp = ref.stack_params(w, cfg)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(seed),
                          (t, cfg["hidden_size"]), jnp.float32)
    return u, lp


def _program_share(u, lp, cfg, first, count):
    """The program's expert layer as the chip holding experts [first,
    first + count) computes it."""
    e = "block_sparse_moe."
    view = dict(router=lp[e + "router.layer.weight"],
                gate_up=lp[e + "input_linear.weight"][first:first + count],
                down=lp[e + "output_linear.weight"][first:first + count],
                w_in=lp["shared_mlp.input_linear.weight"],
                wd=lp["shared_mlp.output_linear.weight"])
    st = dict(top_k=cfg["num_experts_per_tok"], scale=1.0, norm_topk=True,
              scoring="softmax", first=first)
    out, sizes = exaone_moe.moe_ffn(u, view, st, jnp.float32,
                                    backend="reference")
    return out, sizes


def test_expert_parallel_shares_add_up_to_the_uncut_layer():
    """Two chips' shares of 8 experts, 4 each: their outputs less the
    shared expert that each computes alike, plus it once, are the uncut
    reference layer's; each chip's group sizes count the assignments it
    holds, the two together every assignment."""
    cfg = tiny_cfg(experts_held=[0, 8], num_local_experts=8)
    u, lp = _layer(cfg)
    with jax.default_matmul_precision("highest"):
        whole = ref._moe(u, lp, cfg, ref._dense._einsum("f32"), None)
        shared = ref._swiglu(u, lp["shared_mlp.input_linear.weight"],
                             lp["shared_mlp.output_linear.weight"],
                             ref._dense._einsum("f32"))
        (a, na), (b, nb) = (_program_share(u, lp, cfg, f, 4)
                            for f in (0, 4))
    np.testing.assert_allclose(np.asarray(a + b - shared),
                               np.asarray(whole), atol=1e-5, rtol=1e-4)
    assert int(na.sum() + nb.sum()) == 24 * 3
    # a share alone is the reference given that share
    e = "block_sparse_moe."
    held = dict(lp, **{e + n: lp[e + n][4:] for n in (
        "input_linear.weight", "output_linear.weight")})
    with jax.default_matmul_precision("highest"):
        half = ref._moe(u, held, dict(cfg, experts_held=[4, 4]),
                        ref._dense._einsum("f32"), None)
    np.testing.assert_allclose(np.asarray(b), np.asarray(half), atol=1e-5,
                               rtol=1e-4)


def test_the_router_is_top_k_of_the_logits_then_a_softmax_over_them():
    """`_route(scoring="softmax", norm_topk=True, scale=1)` (a softmax
    over all, renormalised over the chosen) and the reference's gates are
    both the published rule, in numpy."""
    rng = np.random.default_rng(5)
    u = rng.normal(size=(32, 64)).astype(np.float32)
    w = rng.normal(size=(64, 72)).astype(np.float32)
    logits = u.astype(np.float64) @ w
    chosen = np.argsort(-logits, axis=1)[:, :10]
    top = np.take_along_axis(logits, chosen, 1)
    want = np.exp(top - top.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    g, e, _ = exaone_moe._route(
        jnp.asarray(u), jnp.asarray(w), None, top_k=10, scale=1.0,
        norm_topk=True, scoring="softmax", n_group=1, topk_group=1)
    np.testing.assert_array_equal(np.asarray(e), chosen)
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        g2, e2 = ref.gates(jnp.asarray(u), jnp.asarray(w), 10,
                           ref._dense._einsum("f32"))
    np.testing.assert_array_equal(np.asarray(e2), chosen)
    np.testing.assert_allclose(np.asarray(g2), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("cut,total", [
    ("published", 32_207_337_984), ("cell", 4_757_211_776)])
def test_parameter_counts(cut, total):
    """Per layer: a Mamba mixer 102,286,976, attention 41,943,040, the
    shared MLP 18,874,368, the router 294,912, an expert 9,437,184, two
    norms; the embedding 100,352 x 4,096 (tied) and the final norm."""
    cfg = json.load(open(SMALL))
    if cut == "published":
        cfg = dict(cfg, num_hidden_layers=40, experts_held=[0, 72],
                   vocab_size=100_352)
    d = stack.dims(cfg)
    assert d["parameters"] == total
    mamba, attn, shared, router, expert = (
        102_286_976, 41_943_040, 18_874_368, 294_912, 9_437_184)
    layers, held = cfg["num_hidden_layers"], cfg["experts_held"][1]
    n_attn = layers // 10
    assert total == (layers - n_attn) * mamba + n_attn * attn + layers * (
        shared + router + held * expert + 2 * 4096) \
        + cfg["vocab_size"] * 4096 + 4096
    if cut == "published":
        # active: ten routed experts of the 72 a layer
        assert total - 40 * 62 * expert == 8_803_121_664
    else:
        assert total == cfg["parameters"]


@pytest.mark.parametrize("kw,match", [
    (dict(num_local_experts=8, num_experts_per_tok=0), "num_experts_per_tok"),
    (dict(num_local_experts=8, num_experts_per_tok=9), "num_experts_per_tok"),
    (dict(num_local_experts=8, num_experts_per_tok=2, experts_held=(6, 4)),
     "experts_held"),
    (dict(experts_held=(0, 4)), "has none"),
    (dict(mamba_n_groups=2), "mamba_n_groups"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
])
def test_the_configuration_refuses_what_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        prog.GraniteHybridConfig.tiny(**kw)


def test_without_experts_the_model_is_built_as_before():
    """`num_local_experts` 0: no expert leaf, the fused MLP in every
    layer's spec, no `moe` statics, the leaves the micro's stack names."""
    model = prog.GraniteHybridForCausalLM(prog.GraniteHybridConfig.tiny())
    assert not any("block_sparse_moe" in n
                   for n, _ in model.named_parameters())
    view = model.decode_view()
    assert "moe" not in view
    assert {s.ffn for s in view["specs"]} == {"swiglu_fused"}
    assert "router" not in view["layers"][0]
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "granite-4.0-h-micro.json")))
    cfg = dict(cfg, num_hidden_layers=2, vocab_size=256)
    micro = dense_stack.build_model(cfg)
    assert {n: tuple(p.shape) for n, p in micro.named_parameters()} == {
        n: tuple(s) for n, s, _, _ in dense_stack.leaf_specs(cfg)}


def test_a_decode_step_names_the_expert_layer_beside_the_mixer():
    """The decode program holds `layer<i>/moe/router|dispatch|experts|
    combine|shared` in a Mamba layer and in the attention layer, beside
    `layer<i>/ssm/...` and `layer<i>/attn`; the engine's `serve.moe_*`
    counters count its steps and `serve.ssm_state_bytes` its state."""
    cfg = tiny_cfg()
    model, _ = seeded(cfg)
    eng = ServeEngine(model, max_slots=2, block_size=4, num_blocks=48,
                      max_seq_len=64, name="grm-trace")
    text = eng.lowered()["decode"].as_text(debug_info=True)
    for i in range(3):
        for part in ("router", "dispatch", "experts", "combine", "shared"):
            assert f"layer{i}/moe/{part}" in text, (i, part)
    for part in ("in_proj", "conv", "scan", "gate_norm", "out"):
        assert f"layer0/ssm/{part}" in text
    assert "layer1/attn" in text and "layer1/ssm" not in text
    eng.submit(np.arange(1, 10), max_new_tokens=6)
    eng.submit(np.arange(3, 8), max_new_tokens=6)
    eng.run()
    value = lambda name, **kw: obs.registry.get(name).value(
        engine="grm-trace", **kw)
    # five decode steps of two streams through three sparse layers
    assert value("serve.moe_tokens_routed") == 5 * 2 * 3
    held = value("serve.moe_assignments_held")
    assert 0 < held <= 5 * 2 * 3 * 3
    assert sum(value("serve.moe_expert_tokens_sum", layer=l)
               for l in range(3)) == held
    assert all(value("serve.moe_expert_tokens_max", layer=l) >= 1
               for l in range(3))
    state = 2 * 4 * 64 * 16 + 3 * 2 * (256 + 32)   # a Mamba layer's numbers
    assert value("serve.ssm_state_bytes") == 2 * state * 4
