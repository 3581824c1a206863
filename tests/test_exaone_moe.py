"""EXAONE-MoE through the program, at tiny widths on the CPU, against the
benchmark's plain reference (`benchmark/reference/exaone_moe.py`, which
imports nothing of the program) on seeded weights: the model's forward,
`ServeEngine` prefill then decode through both kinds of cache (the ring
wraps, slots churn, one decode trace), the shares of an expert-parallel
deployment adding up to the uncut layer, a skewed router dropping
nothing, both pools' accounting, and the prefix cache refused."""
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
from benchmark.reference import exaone_moe as ref  # noqa: E402
from benchmark.stacks import exaone_moe as stack  # noqa: E402
from paddle_tpu.models import exaone_moe as prog  # noqa: E402
from paddle_tpu.serve import ServeEngine  # noqa: E402

PATTERN = ["sliding_attention"] * 3 + ["full_attention"]


def tiny_cfg(**kw):
    cfg = {
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
        "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 5, "layer_types": PATTERN * 2,
        "sliding_window": 8, "first_k_dense_replace": 1,
        "num_experts_published": 8, "experts_held": [0, 8],
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "moe_intermediate_size": 32, "scoring_func": "sigmoid",
        "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
        "topk_group": 1, "rms_norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
        "max_position_embeddings": 512, "tie_word_embeddings": False,
        "vocab_size": 128, "dtype": "float32", "initializer_range": 0.3}
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
    return model, ref.stack_params(w, cfg), w


def engine_of(model, **kw):
    geo = dict(max_slots=3, block_size=4, num_blocks=40, max_seq_len=64)
    geo.update(kw)
    return ServeEngine(model, **geo)


def test_leaf_names_are_the_programs():
    cfg = tiny_cfg()
    model = stack.build_model(cfg)
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} == {
        n: tuple(s) for n, s, _, _ in stack.leaf_specs(cfg)}


@pytest.mark.parametrize("held", [[0, 8], [2, 3]])
def test_model_forward_matches_the_reference(held):
    cfg = tiny_cfg(experts_held=held)
    model, params, _ = seeded(cfg)
    ids = np.random.default_rng(0).integers(1, 128, (2, 40))
    got = np.asarray(model(paddle_tpu.to_tensor(ids))._value)
    for row, out in zip(ids, got):
        want = np.asarray(ref.logits_of(params, row, cfg))
        np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_engine_streams_match_the_reference_through_both_caches(backend):
    """Streams of 30 to 50 positions over rings of 3 x 4 (every ring
    wraps several times), five requests through three slots, one decode
    trace; every served token is the reference's best at its position."""
    cfg = tiny_cfg(experts_held=[2, 4])
    model, params, _ = seeded(cfg)
    eng = engine_of(model, attention_backend=backend, name=f"ex-{backend}")
    assert eng.ring_blocks == 3 and eng.window == 8
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
            for n, m in [(5, 30), (17, 25), (9, 40), (26, 12), (3, 33)]]
    eng.run()
    assert eng.decode_traces == 1
    for r in reqs:
        gaps = ref.served_gaps(params, cfg, np.asarray(r.prompt),
                               np.asarray(r.output_ids), 64)
        assert len(gaps) == r.max_new_tokens
        assert gaps.max() < 1e-3, gaps
    assert eng.pool.used_blocks == 0
    assert eng.window_pool.used_blocks == 0


def test_preemption_returns_both_pools_and_keeps_the_tokens():
    """A full-layer pool too small for three long streams: the youngest
    is preempted, its ring goes back with its blocks, and after its
    re-prefill (prompt + generated into a fresh ring) it still matches."""
    cfg = tiny_cfg()
    model, params, _ = seeded(cfg)
    eng = engine_of(model, num_blocks=20, name="ex-preempt")
    rng = np.random.default_rng(2)
    reqs = [eng.submit(rng.integers(1, 128, 10), max_new_tokens=30)
            for _ in range(3)]
    seen_window = 0
    while eng.has_work:
        eng.step()
        seen_window = max(seen_window, eng.window_pool.used_blocks)
        live = [r for r in eng._slots if r is not None]
        assert eng.window_pool.used_blocks == 3 * len(live)
        assert eng.pool.used_blocks == sum(len(r.blocks) for r in live)
    assert sum(r.preemptions for r in reqs) >= 1 and seen_window == 9
    assert eng.pool.used_blocks == 0 and eng.window_pool.used_blocks == 0
    for r in reqs:
        gaps = ref.served_gaps(params, cfg, np.asarray(r.prompt),
                               np.asarray(r.output_ids), 64)
        assert gaps.max() < 1e-3, gaps


def test_pool_occupancy_is_labelled_by_kind():
    from paddle_tpu import observability as obs

    cfg = tiny_cfg()
    model, _, _ = seeded(cfg)
    eng = engine_of(model, name="ex-occ")
    eng.submit(np.arange(1, 10), max_new_tokens=3)
    eng.step()
    g = obs.registry.get("serve.pool_occupancy")
    assert g.value(engine="ex-occ", kind="window") == pytest.approx(3 / 9, abs=1e-3)
    assert g.value(engine="ex-occ", kind="full") == g.value(engine="ex-occ")
    eng.run()
    assert g.value(engine="ex-occ", kind="window") == 0.0


def test_prefix_cache_is_refused_for_rings():
    model, _, _ = seeded(tiny_cfg())
    with pytest.raises(NotImplementedError, match="ring"):
        engine_of(model, prefix_cache=True, name="ex-prefix")


def test_decode_counters_read_the_share_that_lands_here():
    from paddle_tpu import observability as obs

    cfg = tiny_cfg(experts_held=[0, 4])
    model, _, _ = seeded(cfg)
    eng = engine_of(model, name="ex-count")
    eng.submit(np.arange(1, 12), max_new_tokens=20)
    eng.submit(np.arange(3, 9), max_new_tokens=20)
    eng.run()
    routed = obs.registry.get("serve.moe_tokens_routed").value(
        engine="ex-count")
    held = obs.registry.get("serve.moe_assignments_held").value(
        engine="ex-count")
    # 19 decode steps of two streams through four sparse layers
    assert routed == 19 * 2 * 4
    assert 0 < held <= routed * 2
    total = sum(obs.registry.get("serve.moe_expert_tokens_sum").value(
        engine="ex-count", layer=l) for l in (1, 2, 3, 4))
    assert total == held
    for l in (1, 2, 3, 4):
        assert obs.registry.get("serve.moe_expert_tokens_max").value(
            engine="ex-count", layer=l) >= 1


def _layer_inputs(cfg, seed=3, t=24):
    w = _weights.make_weights(stack.leaf_specs(cfg), seed, "float32")
    params = ref.stack_params(w, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (t, cfg["hidden_size"]), jnp.float32)
    return x, params["layers"][1]


def _program_moe(x, lp, cfg, backend="reference", valid=None):
    first, count = cfg["experts_held"]
    view = dict(router=lp["mlp.gate.weight"],
                router_bias=lp["mlp.gate.e_score_correction_bias"],
                gate_up=lp["mlp.experts.gate_up_proj"],
                down=lp["mlp.experts.down_proj"],
                wg=lp["mlp.shared_experts.gate_proj.weight"],
                wu=lp["mlp.shared_experts.up_proj.weight"],
                wd=lp["mlp.shared_experts.down_proj.weight"])
    st = dict(top_k=cfg["num_experts_per_tok"],
              scale=cfg["routed_scaling_factor"],
              norm_topk=cfg["norm_topk_prob"], first=first)
    return prog.moe_ffn(x, view, st, jnp.float32, backend=backend,
                        valid=valid)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their routed parts, with the
    shared expert counted once, equal the reference's whole layer."""
    whole = tiny_cfg()
    x, lp = _layer_inputs(whole)
    es = ref._einsum("f32")
    want = np.asarray(ref._moe(x, lp, whole, es))
    sh = "mlp.shared_experts."
    shared = np.asarray(ref._swiglu(
        x, lp[sh + "gate_proj.weight"], lp[sh + "up_proj.weight"],
        lp[sh + "down_proj.weight"], es))
    total = shared.copy()
    sizes = []
    for first in range(0, 8, 2):
        cut = tiny_cfg(experts_held=[first, 2])
        lp_cut = dict(lp)
        for leaf in ("mlp.experts.gate_up_proj", "mlp.experts.down_proj"):
            lp_cut[leaf] = lp[leaf][first:first + 2]
        out, n = _program_moe(x, lp_cut, cut, backend="interpret")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref._moe(x, lp_cut, cut, es)),
            atol=1e-4, rtol=1e-4)
        total += np.asarray(out) - shared
        sizes.append(np.asarray(n))
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)
    assert np.concatenate(sizes).sum() == 24 * 2    # no assignment lost


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_a_router_skewed_onto_one_held_expert_drops_no_token(backend):
    """The selection bias puts expert 5 first for every token: its group
    is all 24 tokens (three tiles of 16 rows' worth of buffer), none is
    dropped, and the result is the reference's."""
    cfg = tiny_cfg(experts_held=[4, 3])
    x, lp = _layer_inputs(cfg)
    lp = dict(lp)
    lp["mlp.gate.e_score_correction_bias"] = jnp.zeros(8).at[5].set(10.0)
    out, sizes = _program_moe(x, lp, cfg, backend=backend)
    assert int(sizes[1]) == 24 and int(sizes.sum()) <= 48
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref._moe(x, lp, cfg, ref._einsum("f32"))),
        atol=1e-4, rtol=1e-4)
    # rows that are no tokens are routed nowhere
    valid = jnp.arange(24) < 10
    _, sizes = _program_moe(x, lp, cfg, backend=backend, valid=valid)
    assert int(sizes[1]) == 10


def test_a_long_prompt_goes_through_the_sparse_ffn_in_pieces(monkeypatch):
    cfg = tiny_cfg(experts_held=[1, 5])
    x, lp = _layer_inputs(cfg, t=32)
    whole, n_whole = _program_moe(x, lp, cfg)
    monkeypatch.setattr(prog, "MOE_CHUNK", 8)
    pieces, n_pieces = _program_moe(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(pieces), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    assert (np.asarray(n_pieces) == np.asarray(n_whole)).all()


def test_served_gaps_excuse_a_few_positions_by_rank_and_no_more():
    """The reference divides the 5% largest gaps of a request by 20 (the
    positions a routing flip in bfloat16 explains at the published
    widths) and leaves every other position as it is."""
    cfg = tiny_cfg()
    _, params, _ = seeded(cfg)
    rng = np.random.default_rng(4)
    prompt, served = rng.integers(1, 128, 10), rng.integers(1, 128, 100)
    raw_room, ref.FLIP_ROOM = ref.FLIP_ROOM, 1.0
    try:
        raw = ref.served_gaps(params, cfg, prompt, served, 128)
    finally:
        ref.FLIP_ROOM = raw_room
    got = ref.served_gaps(params, cfg, prompt, served, 128)
    top = np.argsort(raw)[-5:]
    np.testing.assert_allclose(got[top], raw[top] / 20.0, rtol=1e-6)
    np.testing.assert_array_equal(np.delete(got, top), np.delete(raw, top))
    assert raw.max() > 1.0      # random tokens lie far below the best
    assert got.max() == pytest.approx(np.sort(raw)[-6])
