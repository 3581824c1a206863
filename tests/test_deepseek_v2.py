"""DeepSeek-V2 (latent attention, group-limited routing) through the
program, at tiny widths on the CPU, against the benchmark's plain reference
(`benchmark/reference/deepseek_v2.py`, which imports nothing of the
program, keeps no cache and absorbs nothing) on seeded weights: the model's
forward, `ServeEngine` prefill then decode through the latent pool (slots
churn, streams are preempted, a burst, the prefix cache), the absorbed
decode against expanded attention on the same rows, `mla_decode` under the
Pallas interpreter against its jnp reference, the router against a numpy
group-limited top-k, the eight shares of a layer adding up to the uncut
layer, the YaRN tables against a direct formula, the counters, and that no
step ever holds a cached token's per-head key or value."""
import os
import re
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
from benchmark.reference import deepseek_v2 as ref  # noqa: E402
from benchmark.stacks import deepseek_v2 as stack  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.models import decoder_stack  # noqa: E402
from paddle_tpu.models import deepseek_v2 as prog  # noqa: E402
from paddle_tpu.models.exaone_moe import moe_ffn, route  # noqa: E402
from paddle_tpu.ops import mla as mla_ops  # noqa: E402
from paddle_tpu.ops.pallas.mla_decode import (mla_decode_kernel,  # noqa: E402
                                              mla_decode_reference)
from paddle_tpu.serve import ServeEngine  # noqa: E402

#: float32 program against float32 reference
F32_GAP = 1e-3

YARN = {"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 32}


def tiny_cfg(**kw):
    """Three layers (a dense one and two sparse), 4 heads of 16 + 8 and
    16, a latent of 32 (a row of 40 numbers in 128 lanes), 16 experts in 8
    groups of 2, 3 groups and 3 experts a token, YaRN over 32 positions so
    that rows of 64 pass the ramp."""
    cfg = {
        "hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1,
        "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "first_k_dense_replace": 1, "n_routed_experts_published": 16,
        "experts_held": [0, 16], "num_experts_per_tok": 3,
        "n_shared_experts": 2, "moe_intermediate_size": 32,
        "scoring_func": "softmax", "topk_method": "group_limited_greedy",
        "norm_topk_prob": False, "routed_scaling_factor": 16, "n_group": 8,
        "topk_group": 3, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": dict(YARN), "max_position_embeddings": 512,
        "tie_word_embeddings": False, "vocab_size": 128,
        "dtype": "float32", "initializer_range": 0.3}
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


def engine_of(model, **kw):
    geo = dict(max_slots=3, block_size=4, num_blocks=40, max_seq_len=64)
    geo.update(kw)
    return ServeEngine(model, **geo)


def gaps_of(params, cfg, req, pad_to=64):
    raw_room, ref.FLIP_ROOM = ref.FLIP_ROOM, 1.0     # nothing excused
    try:
        return ref.served_gaps(params, cfg, np.asarray(req.prompt),
                               np.asarray(req.output_ids), pad_to)
    finally:
        ref.FLIP_ROOM = raw_room


def test_leaf_names_are_the_programs():
    cfg = tiny_cfg(experts_held=[2, 2])
    model = stack.build_model(cfg)
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} == {
        n: tuple(s) for n, s, _, _ in stack.leaf_specs(cfg)}
    assert model.num_parameters() == stack.dims(cfg)["parameters"]
    specs = model.decode_view()["specs"]
    assert [(s.mixer, s.ffn) for s in specs] == [
        ("mla", "swiglu"), ("mla", "moe"), ("mla", "moe")]


def test_the_config_refuses_what_the_family_is_not():
    with pytest.raises(ValueError, match="softmax"):
        prog.DeepseekV2Config.tiny(scoring_func="sigmoid")
    with pytest.raises(ValueError, match="groups"):
        prog.DeepseekV2Config.tiny(n_routed_experts=12)
    with pytest.raises(ValueError, match="experts_held"):
        prog.DeepseekV2Config.tiny(experts_held=(10, 8))
    cfg = prog.DeepseekV2Config()
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * 1.2608 ** 2,
                                           rel=1e-4)
    assert cfg.rope_statics()["mscale"] == 1.0


@pytest.mark.parametrize("held", [[0, 16], [0, 2], [6, 4]])
def test_model_forward_matches_the_reference(held):
    cfg = tiny_cfg(experts_held=held)
    model, params = seeded(cfg)
    ids = np.random.default_rng(0).integers(1, 128, (2, 40))
    got = np.asarray(model(paddle_tpu.to_tensor(ids))._value)
    for row, out in zip(ids, got):
        want = np.asarray(ref.logits_of(params, row, cfg))
        assert np.std(want) > 0.3           # the logits say something
        np.testing.assert_allclose(out, want, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_engine_streams_match_the_reference_through_the_latent_pool(
        backend):
    """Five requests through three slots, one decode trace: every served
    token is the reference's best at its position (prefill, then decode
    through the cache, against the reference's plain expanded forward)."""
    cfg = tiny_cfg(experts_held=[0, 4])
    model, params = seeded(cfg)
    eng = engine_of(model, attention_backend=backend, name=f"ds-{backend}")
    assert [tuple(a.shape for a in c) for c in eng._caches] == [
        ((1, 40, 4, 128),)] * 3
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
            for n, m in [(5, 30), (17, 25), (9, 40), (26, 12), (3, 33)]]
    eng.run()
    assert eng.decode_traces == 1
    for r in reqs:
        gaps = gaps_of(params, cfg, r)
        assert len(gaps) == r.max_new_tokens
        assert gaps.max() < F32_GAP, gaps
    assert eng.pool.used_blocks == 0


def test_preemption_returns_the_blocks_and_keeps_the_tokens():
    """A pool too small for three long streams: the youngest is
    preempted, and after its re-prefill (prompt + generated, expanded)
    its decode (absorbed) still matches."""
    cfg = tiny_cfg()
    model, params = seeded(cfg)
    eng = engine_of(model, num_blocks=20, name="ds-preempt")
    rng = np.random.default_rng(2)
    reqs = [eng.submit(rng.integers(1, 128, 10), max_new_tokens=30)
            for _ in range(3)]
    while eng.has_work:
        eng.step()
        live = [r for r in eng._slots if r is not None]
        assert eng.pool.used_blocks == sum(len(r.blocks) for r in live)
    assert sum(r.preemptions for r in reqs) >= 1
    assert eng.pool.used_blocks == 0
    for r in reqs:
        assert gaps_of(params, cfg, r).max() < F32_GAP


def test_a_burst_decodes_the_same_tokens():
    cfg = tiny_cfg(experts_held=[4, 6])
    model, params = seeded(cfg)
    outs = {}
    for burst in (1, 4):
        eng = engine_of(model, decode_burst=burst, name=f"ds-burst{burst}")
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=20)
                for n in (6, 13)]
        eng.run()
        outs[burst] = [r.output_ids for r in reqs]
        for r in reqs:
            assert gaps_of(params, cfg, r).max() < F32_GAP
    assert outs[1] == outs[4]


def test_the_prefix_cache_shares_latent_blocks():
    """Every layer is full attention and a latent block is shared like
    any block: a second prompt with the same first blocks mounts them,
    prefills its suffix through the absorbed path, and matches; a prompt
    that is all shared blocks recomputes its last token into a
    copy-on-write duplicate."""
    cfg = tiny_cfg()
    model, params = seeded(cfg)
    eng = engine_of(model, prefix_cache=True, name="ds-prefix")
    rng = np.random.default_rng(4)
    base = rng.integers(1, 128, 16)
    first = eng.submit(base, max_new_tokens=6)
    eng.run()
    second = eng.submit(np.concatenate([base[:12], rng.integers(1, 128, 7)]),
                        max_new_tokens=9)
    third = eng.submit(base, max_new_tokens=5)          # all blocks shared
    eng.run()
    hits = obs.registry.get("serve.prefix_hits").value(engine="ds-prefix")
    assert hits == 2 and second.shared_blocks == 3
    assert obs.registry.get("serve.cow_copies").value(
        engine="ds-prefix") == 1
    assert third.output_ids == first.output_ids[:5]
    for r in (first, second, third):
        assert gaps_of(params, cfg, r).max() < F32_GAP


def test_generate_refuses_the_mixer():
    model, _ = seeded(tiny_cfg())
    with pytest.raises(NotImplementedError, match="ServeEngine"):
        paddle_tpu.models.generate(
            model, paddle_tpu.to_tensor(np.arange(1, 6)[None]),
            max_new_tokens=2)


def test_counters_read_the_latent_cache_and_the_held_group():
    cfg = tiny_cfg(experts_held=[2, 2])                  # group 1 of 8
    model, _ = seeded(cfg)
    eng = engine_of(model, name="ds-count")
    value = lambda name, **kw: obs.registry.get(name).value(
        engine="ds-count", **kw)
    assert value("serve.latent_cache_bytes") == 3 * 40 * 4 * 128 * 4
    eng.submit(np.arange(1, 12), max_new_tokens=20)
    eng.submit(np.arange(3, 9), max_new_tokens=20)
    eng.run()
    # 19 decode steps of two streams: rows 11 + 6 of the prompts, then
    # 38 decoded rows, in each of three layers; contexts 12..30 and 7..25
    assert value("serve.latent_rows_written") == 3 * (17 + 38)
    assert value("serve.mla_ctx_tokens") == sum(range(12, 31)) + sum(
        range(7, 26))
    # pages of 4 rows, computed as held: ceil(context / 4) a stream a step
    assert value("serve.mla_pages_computed") == sum(
        -(-n // 4) for n in list(range(12, 31)) + list(range(7, 26)))
    routed = value("serve.moe_tokens_routed")
    here = value("serve.moe_tokens_to_held_group")
    held = value("serve.moe_assignments_held")
    assert routed == 19 * 2 * 2
    assert 0 < here <= routed and 0 < held <= here * 2
    assert sum(value("serve.moe_expert_tokens_sum", layer=l)
               for l in (1, 2)) == held
    # tools/serve_counters.py prints them
    from tools.serve_counters import counters

    got = counters("ds-count", block_size=4)
    assert got["latent"] == {
        "cache_bytes": 3 * 40 * 4 * 128 * 4, "rows_written": 3 * 55,
        "mla_ctx_tokens": value("serve.mla_ctx_tokens"),
        "ctx_tokens_a_step": round(value("serve.mla_ctx_tokens") / 19, 1),
        "mla_pages_computed": value("serve.mla_pages_computed"),
        "computed_over_read": round(
            value("serve.mla_pages_computed") * 4
            / value("serve.mla_ctx_tokens"), 4)}
    assert counters("ds-count")["latent"]["computed_over_read"] is None
    assert got["moe"]["tokens_to_held_group"] == here
    assert got["moe"]["held_group_share"] == round(here / routed, 4)
    assert counters("no-such-engine")["latent"]["mla_ctx_tokens"] in (0, None)


# --- the decode path never expands a cached token ----------------------------
def test_no_step_holds_a_cached_tokens_per_head_key_or_value():
    """The lowered decode step, suffix prefill and burst of an engine
    whose sizes are all different numbers: no value in them has a shape
    that holds cached tokens or pages (44 positions a slot, 11 pages of 4,
    30 blocks, 120 rows of the pool) beside the 6 heads and a head size
    (12, 20). Expanding the cache would make `[5, 44, 6, 12]`. The cold
    prefill expands its own rows (a bucket of 16) and nothing cached, and
    what it writes into the pool is the padded row alone."""
    cfg = tiny_cfg(hidden_size=48, num_attention_heads=6, qk_nope_head_dim=12,
                   v_head_dim=20, experts_held=[0, 2], vocab_size=96)
    model, _ = seeded(cfg)
    eng = engine_of(model, max_slots=5, num_blocks=30, max_seq_len=44,
                    name="ds-shapes")
    assert [tuple(a.shape for a in c) for c in eng._caches] == [
        ((1, 30, 4, 128),)] * 3
    lowered = eng.lowered(prompt_lens=(16,), suffix_lens=(8,), bursts=(2,))
    cached, heads, sizes = {44, 11, 30, 120}, 6, {12, 20}

    def shapes(text):
        return {tuple(int(d) for d in m.split("x"))
                for m in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)
                for m in [m.rstrip("x")]}

    for name in ("decode", "suffix_prefill.8", "burst.2"):
        seen = shapes(lowered[name].as_text())
        assert (5, 6, 128) in seen or (8, 6, 128) in seen, name  # absorbed q
        bad = [s for s in seen
               if cached & set(s) and heads in s and sizes & set(s)]
        assert not bad, (name, bad)
    pre = shapes(lowered["prefill.16"].as_text())
    assert (16, 6, 20) in pre                     # its own rows, expanded
    assert not [s for s in pre if cached & set(s) and heads in s
                and sizes & set(s)]
    assert {s for s in pre if 30 in s} == {(1, 30, 4, 128)}


def test_absorbed_attention_equals_expanded_attention_on_the_same_rows():
    rng = np.random.default_rng(5)
    st = dict(nope=16, rope=8, v=16, rank=32)
    nh, t = 4, 23
    lp = {"wkvb": jnp.asarray(rng.normal(size=(32, nh * 32)) * 0.2,
                              jnp.float32)}
    latent = jnp.asarray(rng.normal(size=(t, 40)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(1, nh, 16)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(1, nh, 8)), jnp.float32)
    k, v = mla_ops.expand(lp, st, latent)
    assert k.shape == (t, nh, 24) and v.shape == (t, nh, 16)
    s = jnp.einsum("qhd,khd->hqk", jnp.concatenate([q_nope, q_pe], -1),
                   k) * 0.3
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(
        1, nh * 16)
    # the same rows as a pool of 6 pages of 4, the row's 23 live
    q = mla_ops.absorb_q(lp, st, q_nope, q_pe, 128)
    assert q.shape == (1, nh, 128)
    assert not np.asarray(q[:, :, 40:]).any()
    pool = jnp.zeros((1, 8, 4, 128)).at[0, :6, :, :40].set(
        jnp.pad(latent, ((0, 1), (0, 0))).reshape(6, 4, 40))
    out = mla_decode_reference(q, pool, jnp.asarray([t]),
                               jnp.arange(6)[None], dv=32, sm_scale=0.3)
    got = mla_ops.absorb_o(lp, st, out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# name: (pages_per_seq, lengths). Pages of 8 rows and eight pages a turn: a
# turn is 64 rows.
LENGTHS = {
    "ragged": (11, [13, 5, 88, 32, 1]),
    "idle_slots": (11, [0, 40, 0, 0, 7]),
    "first_row_idle": (11, [0, 0, 21, 64, 3]),
    "page_edges": (11, [8, 16, 64, 88, 32]),    # every row ends on an edge
    "all_idle": (11, [0, 0, 0, 0, 0]),
    "a_turn_and_a_page": (11, [72, 65, 64, 63, 88]),
    # the unmasked body alone: every row ends on a turn's edge, an idle
    # slot between live ones
    "turn_edges": (22, [64, 128, 0, 64, 128]),
    "a_row_past_the_edge": (22, [65, 129, 0, 65, 129]),
    "one_page": (11, [8, 8, 8, 8, 8]),
    "one_row": (11, [1, 1, 1, 1, 1]),
    # a last turn of 1 to 8 pages with no turn before it, then with one
    "every_last_turn": (11, [3, 12, 20, 30, 36, 44, 52, 60, 69, 76, 84]),
    # tables narrower than a turn: a turn is five pages there
    "tables_under_a_turn": (5, [40, 33, 8, 0, 39, 24, 17]),
    "two_turns_and_more": (22, [128, 176, 129, 0, 150, 175]),
    "mixed": (22, [0, 64, 65, 8, 1, 0, 176, 128, 57, 130]),
}


@pytest.mark.parametrize("case", sorted(LENGTHS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mla_decode_kernel_equals_its_reference(case, dtype):
    """Rows of one turn, of a turn and a page, of a turn less a row, of
    whole turns only (no masked body runs), of every size of last turn,
    idle rows before, between and after: the first copies of the next
    live row follow each of them."""
    rng = np.random.default_rng(6)
    pps, lens = LENGTHS[case]
    b, nh, lanes, dv, page, nb = len(lens), 4, 256, 128, 8, 256
    dt = jnp.dtype(dtype)
    pool = jnp.asarray(rng.normal(size=(1, nb, page, lanes)), dt)
    q = jnp.asarray(rng.normal(size=(b, nh, lanes)), dt)
    lengths = jnp.asarray(lens, jnp.int32)
    tables = jnp.asarray(rng.permutation(nb)[:b * pps].reshape(b, pps),
                         jnp.int32)
    got = mla_decode_kernel(q, pool, lengths, tables, dv=dv, sm_scale=0.07,
                            interpret=True)
    want = mla_decode_reference(q, pool, lengths, tables, dv=dv,
                                sm_scale=0.07)
    assert got.shape == (b, nh, dv) and got.dtype == dt
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    idle = np.asarray(lengths) == 0
    assert not np.asarray(got, np.float32)[idle].any()


def test_pages_computed_follows_what_a_stream_holds():
    """The pages a stream holds, whole turns and the last one alike;
    lengths past the table are cut to it."""
    from paddle_tpu.ops.pallas.mla_decode import pages_computed

    lens = [0, 1, 128, 129, 1024, 1025, 1700, 8192, 9000]
    assert pages_computed(lens, 128, 64).tolist() == [
        0, 1, 1, 2, 8, 9, 14, 64, 64]
    assert pages_computed([40, 41, 8, 0], 8, 5).tolist() == [5, 5, 1, 0]


def test_mla_decode_refuses_shapes_that_are_not_a_latent_pool():
    q = jnp.zeros((2, 4, 128))
    with pytest.raises(ValueError, match="latent pool"):
        mla_decode_reference(q, jnp.zeros((2, 8, 4, 128)), jnp.zeros(2, int),
                             jnp.zeros((2, 3), int), dv=64, sm_scale=1.0)
    with pytest.raises(ValueError, match="lanes"):
        mla_decode_reference(q, jnp.zeros((1, 8, 4, 256)), jnp.zeros(2, int),
                             jnp.zeros((2, 3), int), dv=64, sm_scale=1.0)
    assert mla_ops.row_lanes(dict(rank=512, rope=64)) == 640
    assert mla_ops.row_lanes(dict(rank=32, rope=8)) == 128


# --- the router ------------------------------------------------------------------
def numpy_route(logits, *, top_k, scale, n_group, topk_group):
    """Group-limited greedy selection, one token at a time, ties to the
    lower index (a stable sort)."""
    out_w, out_e = [], []
    for row in np.asarray(logits, np.float64):
        s = np.exp(row - row.max())
        s /= s.sum()
        per = len(s) // n_group
        best = s.reshape(n_group, per).max(-1)
        groups = np.argsort(-best, kind="stable")[:topk_group]
        left = np.zeros_like(s)
        for g in groups:
            left[g * per:(g + 1) * per] = s[g * per:(g + 1) * per]
        chosen = np.argsort(-left, kind="stable")[:top_k]
        out_e.append(chosen)
        out_w.append(s[chosen] * scale)
    return np.asarray(out_w), np.asarray(out_e)


@pytest.mark.parametrize("case", ["random", "ties", "one_group_leads"])
def test_the_router_is_a_group_limited_top_k(case):
    rng = np.random.default_rng(8)
    t, e, g = 40, 16, 8
    if case == "ties":
        # few distinct values: groups tie, experts tie
        logits = rng.integers(0, 3, (t, e)).astype(np.float32)
    elif case == "one_group_leads":
        logits = rng.normal(size=(t, e)).astype(np.float32)
        logits[:, 6:8] += 5.0
    else:
        logits = rng.normal(size=(t, e)).astype(np.float32)
    # an identity router reads the logits off the hidden state
    w, experts = route(jnp.asarray(logits), jnp.eye(e), None, top_k=3,
                       scale=16.0, norm_topk=False, scoring="softmax",
                       n_group=g, topk_group=3)
    want_w, want_e = numpy_route(logits, top_k=3, scale=16.0, n_group=g,
                                 topk_group=3)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    # at most three groups a token
    assert all(len({x // 2 for x in row}) <= 3 for row in want_e)
    if case == "one_group_leads":
        assert all({6, 7} <= set(row) for row in np.asarray(experts))


def test_the_sigmoid_router_is_unchanged_by_the_new_arguments():
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.1, jnp.float32)
    w, e = route(h, wr, bias, top_k=2, scale=2.5)
    s = jax.nn.sigmoid(np.asarray(h) @ np.asarray(wr))
    want_e = np.argsort(-(np.asarray(s) + np.asarray(bias)),
                        kind="stable")[:, :2]
    np.testing.assert_array_equal(np.asarray(e), want_e)
    picked = np.take_along_axis(np.asarray(s), want_e, 1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(-1, keepdims=True) * 2.5,
        rtol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        route(h, wr, None, top_k=2, scale=1.0, scoring="tanh")


def _layer_inputs(cfg, seed=3, t=24):
    w = _weights.make_weights(stack.leaf_specs(cfg), seed, "float32")
    params = ref.stack_params(w, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (t, cfg["hidden_size"]), jnp.float32)
    return x, params["layers"][1]


def _program_moe(x, lp, cfg, backend="reference", valid=None):
    first, count = cfg["experts_held"]
    view = dict(router=lp["mlp.gate.weight"],
                gate_up=lp["mlp.experts.gate_up_proj"],
                down=lp["mlp.experts.down_proj"],
                wg=lp["mlp.shared_experts.gate_proj.weight"],
                wu=lp["mlp.shared_experts.up_proj.weight"],
                wd=lp["mlp.shared_experts.down_proj.weight"])
    st = dict(top_k=cfg["num_experts_per_tok"],
              scale=cfg["routed_scaling_factor"],
              norm_topk=cfg["norm_topk_prob"], first=first,
              scoring="softmax", n_group=cfg["n_group"],
              topk_group=cfg["topk_group"])
    return moe_ffn(x, view, st, jnp.float32, backend=backend, valid=valid)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_the_eight_shares_add_up_to_the_uncut_layer(backend):
    """Eight chips of one routing group (two experts) each: their routed
    parts, with the shared experts counted once, equal the reference's
    whole layer; no assignment is lost, and a token counts towards a
    chip's group exactly where it kept that group."""
    whole = tiny_cfg()
    x, lp = _layer_inputs(whole)
    es = ref._einsum("f32")
    want = np.asarray(ref._moe(x, lp, whole, es))
    sh = "mlp.shared_experts."
    shared = np.asarray(ref._swiglu(
        x, lp[sh + "gate_proj.weight"], lp[sh + "up_proj.weight"],
        lp[sh + "down_proj.weight"], es))
    _, _, kept = ref._choose(ref._scores(x, lp, es), whole)
    total = shared.copy()
    sizes = []
    for group in range(8):
        cut = tiny_cfg(experts_held=[2 * group, 2])
        lp_cut = dict(lp)
        for leaf in ("mlp.experts.gate_up_proj", "mlp.experts.down_proj"):
            lp_cut[leaf] = lp[leaf][2 * group:2 * group + 2]
        out, n = _program_moe(x, lp_cut, cut, backend=backend)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref._moe(x, lp_cut, cut, es)),
            atol=1e-4, rtol=1e-4)
        total += np.asarray(out) - shared
        n = np.asarray(n)
        assert n.shape == (3,)             # two experts, and the group
        assert n[2] == int(np.asarray(kept)[:, group].sum())
        sizes.append(n)
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)
    sizes = np.asarray(sizes)
    assert sizes[:, :2].sum() == 24 * 3     # no assignment lost
    assert sizes[:, 2].sum() == 24 * 3      # three groups a token
    # rows that are no tokens are routed nowhere and count for no group
    cut = tiny_cfg(experts_held=[0, 2])
    lp_cut = dict(lp)
    for leaf in ("mlp.experts.gate_up_proj", "mlp.experts.down_proj"):
        lp_cut[leaf] = lp[leaf][:2]
    _, n = _program_moe(x, lp_cut, cut, backend=backend,
                        valid=jnp.arange(24) < 10)
    assert int(n[2]) == int(np.asarray(kept)[:10, 0].sum())


# --- YaRN ----------------------------------------------------------------------------
@pytest.mark.parametrize("rs, dim, theta", [
    (dict(YARN, factor=40, original_max_position_embeddings=4096), 64,
     10000.0),
    (YARN, 8, 10000.0),
    (dict(YARN, factor=8, beta_fast=16, beta_slow=2,
          original_max_position_embeddings=256), 32, 500000.0)])
def test_yarn_tables_against_a_direct_formula(rs, dim, theta):
    want = ref.yarn_inv_freq(dim, theta, rs)
    got = decoder_stack.yarn_inv_freq(
        dim, theta, factor=rs["factor"], beta_fast=rs["beta_fast"],
        beta_slow=rs["beta_slow"],
        original=rs["original_max_position_embeddings"])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    plain = theta ** (-2.0 * np.arange(dim // 2) / dim)
    # the fastest pair is left alone, the slowest divided by the factor
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / rs["factor"], rel=1e-5)
    cos, sin = decoder_stack.yarn_tables(
        50, dim, theta, factor=rs["factor"], beta_fast=rs["beta_fast"],
        beta_slow=rs["beta_slow"],
        original=rs["original_max_position_embeddings"], mscale=1.0)
    ang = np.arange(50)[:, None] * want[None, :]
    np.testing.assert_allclose(np.asarray(cos),
                               np.cos(np.concatenate([ang, ang], -1)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin),
                               np.sin(np.concatenate([ang, ang], -1)),
                               atol=1e-5)


def test_the_published_ramp_runs_over_pairs_10_to_24():
    """theta 10000 over 64 dims, 32 turns and 1 turn in 4,096 positions:
    the pairs whose wavelengths are 128 and 4,096 positions."""
    inv = ref.yarn_inv_freq(64, 10000.0, dict(
        YARN, factor=40, original_max_position_embeddings=4096))
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    ratio = plain / inv
    assert np.allclose(ratio[:11], 1.0) and np.allclose(ratio[24:], 40.0)
    assert (np.diff(ratio[10:25]) > 0).all()
    assert ref.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                              "rope_scaling": dict(YARN, factor=40)}) \
        == pytest.approx(192 ** -0.5 * 1.26081 ** 2, rel=1e-4)
