"""GLM-5 (latent attention with DeepSeek sparse attention, sigmoid routing)
through the program, at tiny widths on the CPU, against the benchmark's
plain reference (`benchmark/reference/glm_moe_dsa.py`, which imports nothing
of the program, keeps no cache, absorbs nothing and selects its own way) on
seeded weights, with `index_topk` 8 and contexts of 20 to 60 so that the
selection engages: the model's forward, `ServeEngine` prefill then decode
through the latent and index-key pools (slots churn, a stream is
preempted, a burst, the prefix cache), the selection against a plain numpy
one, `dsa_index` and `mla_sparse_decode` under the Pallas interpreter
against their references, the selections in VMEM (`dsa_select`,
`dsa_prefill_select`) interpreted against `top_mask` and `prefill_select`,
the counters, and the dense control that does not pass."""
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
from benchmark.reference import glm_moe_dsa as ref  # noqa: E402
from benchmark.stacks import glm_moe_dsa as stack  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.models import glm_moe_dsa as prog  # noqa: E402
from paddle_tpu.ops import dsa  # noqa: E402
from paddle_tpu.ops.pallas.dsa_index import (dsa_index_kernel,  # noqa: E402
                                             dsa_index_reference)
from paddle_tpu.ops.pallas.dsa_select import (  # noqa: E402
    dsa_prefill_select_kernel, dsa_select_kernel)
from paddle_tpu.ops.pallas.mla_sparse_decode import (  # noqa: E402
    keep_of, mla_sparse_decode_kernel, mla_sparse_decode_reference)
from paddle_tpu.serve import ServeEngine  # noqa: E402

#: float32 program against float32 reference
F32_GAP = 1e-3
TOPK = 8


def tiny_cfg(**kw):
    """Three layers (a dense one and two sparse), 4 heads of 16 + 8 and
    16, a latent of 32 (a row of 40 numbers in 128 lanes), an indexer of 4
    heads of 16 that keeps the best 8 keys, 16 experts, 3 a token."""
    cfg = {
        "hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1,
        "topk_method": "noaux_tc", "hidden_size": 64,
        "intermediate_size": 96, "num_hidden_layers": 3,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
        "v_head_dim": 16, "index_n_heads": 4, "index_head_dim": 16,
        "index_topk": TOPK, "index_norm_eps": 1e-6,
        "first_k_dense_replace": 1, "n_routed_experts_published": 16,
        "experts_held": [0, 16], "num_experts_per_tok": 3,
        "n_shared_experts": 1, "moe_intermediate_size": 32,
        "scoring_func": "sigmoid", "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
        "rms_norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
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
    return model, ref.stack_params(w, cfg)


def engine_of(model, **kw):
    geo = dict(max_slots=3, block_size=4, num_blocks=60, max_seq_len=64)
    geo.update(kw)
    return ServeEngine(model, **geo)


def gaps_of(params, cfg, req, pad_to=64, control=None):
    raw_room, ref.FLIP_ROOM = ref.FLIP_ROOM, 1.0     # nothing excused
    try:
        return ref.served_gaps(params, cfg, np.asarray(req.prompt),
                               np.asarray(req.output_ids), pad_to,
                               control=control)
    finally:
        ref.FLIP_ROOM = raw_room


def test_leaf_names_are_the_programs():
    cfg = tiny_cfg(experts_held=[2, 2])
    model = stack.build_model(cfg)
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} == {
        n: tuple(s) for n, s, _, _ in stack.leaf_specs(cfg)}
    assert model.num_parameters() == stack.dims(cfg)["parameters"]
    view = model.decode_view()
    assert [(s.mixer, s.ffn) for s in view["specs"]] == [
        ("mla", "swiglu"), ("mla", "moe"), ("mla", "moe")]
    assert view["dsa"] == dict(heads=4, dim=16, rope=8, topk=TOPK, eps=1e-6)
    assert view["attn_scale"] == 24 ** -0.5 and "rope_scaling" not in view


def test_the_config_refuses_what_the_family_is_not():
    with pytest.raises(ValueError, match="sigmoid"):
        prog.GlmMoeDsaConfig.tiny(scoring_func="softmax")
    with pytest.raises(ValueError, match="rotates"):
        prog.GlmMoeDsaConfig.tiny(index_head_dim=4)
    with pytest.raises(ValueError, match="experts_held"):
        prog.GlmMoeDsaConfig.tiny(experts_held=(10, 8))
    cfg = prog.GlmMoeDsaConfig()
    assert (cfg.qk_head_dim, cfg.index_topk, cfg.n_routed_experts) == (
        256, 2048, 256)


@pytest.mark.parametrize("held", [[0, 16], [6, 4]])
def test_model_forward_matches_the_reference(held):
    cfg = tiny_cfg(experts_held=held)
    model, params = seeded(cfg)
    ids = np.random.default_rng(0).integers(1, 128, (2, 40))
    got = np.asarray(model(paddle_tpu.to_tensor(ids))._value)
    for row, out in zip(ids, got):
        want = np.asarray(ref.logits_of(params, row, cfg))
        assert np.std(want) > 0.3           # the logits say something
        np.testing.assert_allclose(out, want, atol=5e-4, rtol=5e-4)
        # and the selection moves them: the dense control is elsewhere
        dense = np.asarray(ref.logits_of(params, row, cfg, "dense"))
        assert np.abs(dense - want)[TOPK:].max() > 0.1
        np.testing.assert_allclose(dense[:TOPK], want[:TOPK], atol=5e-4,
                                   rtol=5e-4)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_engine_streams_match_the_reference_through_both_pools(backend):
    """Five requests through three slots, one decode trace: every served
    token is the reference's best at its position (prefill under its
    selection, then decode through the latent and index-key pools,
    against the reference's plain expanded forward)."""
    cfg = tiny_cfg(experts_held=[0, 4])
    model, params = seeded(cfg)
    eng = engine_of(model, attention_backend=backend, name=f"glm-{backend}")
    assert [tuple(a.shape for a in c) for c in eng._caches] == [
        ((1, 60, 4, 128), (1, 60, 4, 16))] * 3
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
            for n, m in [(5, 30), (17, 25), (9, 40), (26, 30), (3, 33)]]
    eng.run()
    assert eng.decode_traces == 1
    for r in reqs:
        gaps = gaps_of(params, cfg, r)
        assert len(gaps) == r.max_new_tokens
        assert gaps.max() < F32_GAP, gaps
    assert eng.pool.used_blocks == 0


def test_preemption_returns_the_blocks_and_keeps_the_tokens():
    """A pool too small for three long streams: the youngest is preempted
    (both pools' blocks go back with it), and after its re-prefill under
    the selection its decode still matches."""
    cfg = tiny_cfg()
    model, params = seeded(cfg)
    eng = engine_of(model, num_blocks=24, name="glm-preempt")
    rng = np.random.default_rng(2)
    reqs = [eng.submit(rng.integers(1, 128, 14), max_new_tokens=30)
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
        eng = engine_of(model, decode_burst=burst, name=f"glm-burst{burst}")
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=20)
                for n in (6, 13)]
        eng.run()
        outs[burst] = [r.output_ids for r in reqs]
        for r in reqs:
            assert gaps_of(params, cfg, r).max() < F32_GAP
    assert outs[1] == outs[4]


def test_the_prefix_cache_shares_both_pools_blocks():
    """A block holds latent rows and index keys alike, so it is shared
    whole: a second prompt with the same first blocks mounts them and
    prefills its suffix through the decode path (each suffix row scores
    the cached keys and selects), a prompt that is all shared blocks
    recomputes its last token into a copy-on-write duplicate of both."""
    cfg = tiny_cfg()
    model, params = seeded(cfg)
    eng = engine_of(model, prefix_cache=True, name="glm-prefix")
    rng = np.random.default_rng(4)
    base = rng.integers(1, 128, 16)
    first = eng.submit(base, max_new_tokens=6)
    eng.run()
    second = eng.submit(np.concatenate([base[:12], rng.integers(1, 128, 9)]),
                        max_new_tokens=9)
    third = eng.submit(base, max_new_tokens=5)          # all blocks shared
    eng.run()
    hits = obs.registry.get("serve.prefix_hits").value(engine="glm-prefix")
    assert hits == 2 and second.shared_blocks == 3
    assert third.output_ids == first.output_ids[:5]
    for r in (first, second, third):
        assert gaps_of(params, cfg, r).max() < F32_GAP


def test_counters_read_the_index_cache_and_the_selection():
    cfg = tiny_cfg(experts_held=[2, 2])
    model, _ = seeded(cfg)
    eng = engine_of(model, name="glm-count")
    value = lambda name, **kw: obs.registry.get(name).value(
        engine="glm-count", **kw)
    assert value("serve.latent_cache_bytes") == 3 * 60 * 4 * 128 * 4
    assert value("serve.index_cache_bytes") == 3 * 60 * 4 * 16 * 4
    eng.submit(np.arange(1, 12), max_new_tokens=20)
    eng.submit(np.arange(3, 9), max_new_tokens=20)
    eng.run()
    # 19 decode steps of two streams, contexts 12..30 and 7..25
    ctx = list(range(12, 31)) + list(range(7, 26))
    assert value("serve.dsa_index_rows") == sum(ctx)
    assert value("serve.dsa_selected_rows") == sum(min(n, TOPK) for n in ctx)
    # prompts of 11 and 6: query t attends min(t + 1, 8) keys
    assert value("serve.dsa_prefill_pairs_selected") == (36 + 3 * 8) + 21
    assert value("serve.dsa_prefill_pairs_causal") == 66 + 21
    from tools.serve_counters import counters

    got = counters("glm-count")["dsa"]
    assert got == {
        "index_cache_bytes": 3 * 60 * 4 * 16 * 4,
        "index_rows": sum(ctx),
        "selected_rows": sum(min(n, TOPK) for n in ctx),
        "selected_over_index_rows": round(
            sum(min(n, TOPK) for n in ctx) / sum(ctx), 4),
        "prefill_pairs_selected": 81, "prefill_pairs_causal": 87}
    assert counters("no-such-engine")["dsa"]["index_rows"] in (0, None)


def test_the_dense_control_does_not_pass():
    """The mathematics without its selection, put in the program's place:
    past the first `index_topk` positions the tokens it puts first lie
    further below the reference's best than the float32 program's do, by
    far."""
    cfg = tiny_cfg()
    model, params = seeded(cfg)
    eng = engine_of(model, name="glm-dense")
    rng = np.random.default_rng(8)
    reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=30)
            for n in (20, 28)]
    eng.run()
    for r in reqs:
        assert gaps_of(params, cfg, r).max() < F32_GAP
        assert gaps_of(params, cfg, r, control="dense").max() > 0.05


# --- the selection ---------------------------------------------------------------
def numpy_scores(q, w, k):
    s = np.einsum("qhd,kd->qhk", q.astype(np.float64), k.astype(np.float64))
    return (np.maximum(s, 0) * w[:, :, None]).sum(1) * q.shape[-1] ** -0.5


def numpy_select(scores, lengths, topk):
    """Row by row: a stable sort by score, largest first, earlier positions
    first among equals; the first min(length, topk) of the seen ones."""
    out = []
    for row, n in zip(scores, lengths):
        order = sorted(range(n), key=lambda s: (-row[s], s))
        out.append(sorted(order[:min(n, topk)]))
    return out


@pytest.mark.parametrize("case", ["random", "ties"])
def test_the_selection_is_the_top_k_with_ties_to_the_earlier(case):
    rng = np.random.default_rng(9)
    t, heads, dim = 40, 4, 16
    q = rng.normal(size=(t, heads, dim)).astype(np.float32)
    k = rng.normal(size=(t, dim)).astype(np.float32)
    w = rng.normal(size=(t, heads)).astype(np.float32)
    scores = numpy_scores(q, w, k).astype(np.float32)
    if case == "ties":           # a few values, many ties among them
        scores = np.round(scores * 2) / 2
    got = np.asarray(dsa.index_scores(jnp.asarray(q), jnp.asarray(w),
                                      jnp.asarray(k), dim ** -0.5))
    np.testing.assert_allclose(got, numpy_scores(q, w, k), rtol=1e-4,
                               atol=1e-4)
    lengths = np.arange(1, t + 1)                 # causal: query t sees t+1
    want = numpy_select(scores, lengths, TOPK)
    causal = np.arange(t)[None, :] <= np.arange(t)[:, None]
    mask = np.asarray(dsa.top_mask(jnp.asarray(scores), jnp.asarray(causal),
                                   TOPK))
    assert [sorted(np.flatnonzero(m)) for m in mask] == want


@pytest.mark.parametrize("case", ["normal", "signs_and_zeros", "ties",
                                  "fewer_than_k"])
def test_the_kth_score_by_halvings_is_the_sorted_kth(case):
    """The k-th largest by 32 halvings of the order-keeping key is what a
    sort gives: over scores of both signs, zeros of both signs, many ties,
    and rows with fewer finite scores than k (the k-th is then -inf)."""
    rng = np.random.default_rng(13)
    s = rng.normal(size=(6, 50)).astype(np.float32) * 100
    if case == "signs_and_zeros":
        s[:, ::3] = 0.0
        s[:, 1::7] = -0.0
        s[0] = -np.abs(s[0])
    elif case == "ties":
        s = np.round(s / 50)
    elif case == "fewer_than_k":
        s[:, 5:] = -np.inf
    for k in (1, 7, 12, 50):
        got = np.asarray(dsa.kth_largest(jnp.asarray(s), k))[:, 0]
        want = -np.sort(-s, axis=1)[:, k - 1]
        np.testing.assert_array_equal(got, want + 0.0)


def test_a_prompts_selection_goes_by_blocks_of_queries():
    """The prompt's mask, a block of 8 queries at a time, is the plain
    selection of its causal scores: the blocks change nothing."""
    rng = np.random.default_rng(10)
    t, heads, dim = 32, 4, 16
    q = rng.normal(size=(t, heads, dim)).astype(np.float32)
    k = rng.normal(size=(t, dim)).astype(np.float32)
    w = rng.normal(size=(t, heads)).astype(np.float32)
    want = numpy_select(numpy_scores(q, w, k), np.arange(1, t + 1), TOPK)
    for block in (8, 32):
        got = np.asarray(dsa.prefill_select(
            jnp.asarray(q), jnp.asarray(w), jnp.asarray(k), TOPK,
            dim ** -0.5, block=block))
        assert got.dtype == np.int8
        assert [np.flatnonzero(m).tolist() for m in got] == want
    assert dsa.selected_pairs(11, 8) == (36 + 3 * 8, 66)
    assert dsa.selected_pairs(5, 8, start=11) == (40, 16 * 17 // 2 - 66)


# --- the kernels under the interpreter --------------------------------------------
# name: (pages_per_seq, lengths), pages of 8 rows: below, at and past the
# 8 selected, one row, an idle slot, a turn of 16 pages and more
INDEX_LENGTHS = {
    "below_at_past": (11, [5, 8, 9, 88, 40]),
    "idle_slots": (11, [0, 40, 0, 7, 0]),
    "one_row": (11, [1, 1, 1]),
    "all_idle": (11, [0, 0, 0]),
    "turns_of_sixteen": (40, [128, 129, 0, 300, 17]),
}


@pytest.mark.parametrize("case", sorted(INDEX_LENGTHS))
def test_dsa_index_kernel_equals_its_reference(case):
    rng = np.random.default_rng(11)
    pps, lens = INDEX_LENGTHS[case]
    b, heads, dim, page, nb = len(lens), 4, 128, 8, 256
    pool = jnp.asarray(rng.normal(size=(1, nb, page, dim)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(b, heads, dim)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(b, heads)), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    tables = jnp.asarray(rng.permutation(nb)[:b * pps].reshape(b, pps),
                         jnp.int32)
    got = np.asarray(dsa_index_kernel(q, w, pool, lengths, tables,
                                      scale=dim ** -0.5, interpret=True))
    want = np.asarray(dsa_index_reference(q, w, pool, lengths, tables,
                                          scale=dim ** -0.5))
    assert got.shape == (b, pps * page)
    seen = np.arange(pps * page)[None, :] < np.asarray(lens)[:, None]
    assert np.isneginf(got[~seen]).all() and np.isneginf(want[~seen]).all()
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-4, atol=1e-3)
    # the oracle itself against the plain score of the same keys
    keys = np.asarray(pool[0][tables].reshape(b, -1, dim), np.float32)
    for r, n in enumerate(lens):
        plain = numpy_scores(np.asarray(q[r:r + 1], np.float32),
                             np.asarray(w[r:r + 1]), keys[r, :n])[0]
        np.testing.assert_allclose(want[r, :n], plain, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("case", sorted(INDEX_LENGTHS))
@pytest.mark.parametrize("k", [8, 32])
def test_mla_sparse_decode_kernel_equals_its_reference(case, k):
    """The selected rows of streams below, at and past `k`, of one row and
    of none, through a table of scattered pages: the kernel's page walk
    under the selection, its reference, and the plain softmax over the
    selected rows read straight from the pool."""
    rng = np.random.default_rng(12)
    pps, lens = INDEX_LENGTHS[case]
    b, nh, lanes, dv, page, nb = len(lens), 4, 256, 128, 8, 256
    pool = jnp.asarray(rng.normal(size=(1, nb, page, lanes)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(b, nh, lanes)), jnp.bfloat16)
    lengths = jnp.asarray(lens, jnp.int32)
    tables = jnp.asarray(rng.permutation(nb)[:b * pps].reshape(b, pps),
                         jnp.int32)
    scores = jnp.asarray(rng.normal(size=(b, pps * page)), jnp.float32)
    pos = jnp.arange(pps * page)[None, :]
    chosen = dsa.top_mask(scores, pos < lengths[:, None], k)
    assert np.asarray(chosen.sum(-1)).tolist() == [min(n, k) for n in lens]
    keep = keep_of(chosen)
    got = mla_sparse_decode_kernel(q, pool, keep, lengths, tables, dv=dv,
                                   sm_scale=0.07, interpret=True)
    want = mla_sparse_decode_reference(q, pool, keep, lengths, tables,
                                       dv=dv, sm_scale=0.07)
    assert got.shape == (b, nh, dv) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)
    flat = np.asarray(pool[0], np.float32)[np.asarray(tables)].reshape(
        b, -1, lanes)
    for r, n in enumerate(lens):
        if n == 0:
            assert not np.asarray(got[r], np.float32).any()
            continue
        pick = flat[r, np.flatnonzero(np.asarray(chosen[r]))]
        s = np.asarray(q[r], np.float32) @ pick.T * 0.07
        p = np.exp(s - s.max(-1, keepdims=True))
        plain = (p / p.sum(-1, keepdims=True)) @ pick[:, :dv]
        np.testing.assert_allclose(np.asarray(want[r], np.float32), plain,
                                   atol=3e-2)


# --- the selection in VMEM under the interpreter ----------------------------------
def _decode_scores(case, rng):
    """(scores [B, S], lengths, topk) of a named case of the decode
    selection."""
    s = rng.normal(size=(6, 640)).astype(np.float32)
    lens = [0, 5, 200, 333, 640, 129]
    k = 64
    if case == "ties_at_the_kth":            # a few values, many equal
        s = np.round(s * 2) / 2
    elif case == "infs_and_signed_zeros":
        s[:, ::3] = 0.0
        s[:, 1::5] = -0.0
        s[2, 10:190] = -np.inf
        s[3, :] = -np.inf
        s[4, 100:] = np.inf
    elif case == "lengths_at_most_topk":
        lens = [64, 63, 1, 0, 17, 64]
    elif case == "lengths_off_the_lane_tiles":
        s = rng.normal(size=(11, 2600)).astype(np.float32)
        lens = [1500, 1025, 1023, 2600, 2599, 130, 0, 7, 1100, 2048, 64]
        k = 1000
    elif case == "a_tiny_model":             # the engine's tiny scale
        s = np.round(rng.normal(size=(3, 64)) * 4).astype(np.float32)
        lens = [20, 64, 8]
        k = TOPK
    return s, lens, k


@pytest.mark.parametrize("case", ["random", "ties_at_the_kth",
                                  "infs_and_signed_zeros",
                                  "lengths_at_most_topk",
                                  "lengths_off_the_lane_tiles",
                                  "a_tiny_model"])
def test_dsa_select_kernel_is_top_mask_bit_for_bit(case):
    """The decode selection in VMEM is `top_mask` then `keep_of`, bit for
    bit: many scores equal at the k-th, -inf, +inf and -0.0 beside +0.0,
    a row of length 0, rows of no more than `topk`, lengths off the
    128-lane tiles and rows past a multiple of eight, a `topk` of 64 of
    640 and the tiny model's 8 of 64."""
    s, lens, k = _decode_scores(case, np.random.default_rng(14))
    scores, lengths = jnp.asarray(s), jnp.asarray(lens, jnp.int32)
    pos = jnp.arange(s.shape[1])[None, :]
    want = np.asarray(keep_of(dsa.top_mask(scores, pos < lengths[:, None],
                                           k)))
    got = np.asarray(dsa_select_kernel(scores, lengths, topk=k,
                                       interpret=True))
    assert got.shape == (len(lens), 1, s.shape[1]) and got.dtype == np.float32
    np.testing.assert_array_equal(got, want)
    assert ((got == 0).sum(-1)[:, 0] == np.minimum(lens, k)).all()


@pytest.mark.parametrize("t,block", [(256, 128), (256, 64), (384, 128),
                                     (48, None)])
def test_dsa_prefill_select_kernel_equals_prefill_select(t, block):
    """A prompt's selection scored and selected in VMEM is
    `prefill_select`'s on scores exact in float32 (small integers for
    queries, keys and weights, so no order of the sums moves one): two to
    four blocks of queries, and the rows before the `topk`-th that take
    every causal key."""
    rng = np.random.default_rng(15)
    heads, dim, k = 4, 16, 40
    q = jnp.asarray(rng.integers(-2, 3, (t, heads, dim)), jnp.float32)
    key = jnp.asarray(rng.integers(-2, 3, (t, dim)), jnp.float32)
    w = jnp.asarray(rng.integers(-2, 3, (t, heads)), jnp.float32)
    want = np.asarray(dsa.prefill_select(q, w, key, k, 0.25, block=16))
    got = np.asarray(dsa_prefill_select_kernel(q, w, key, k, 0.25,
                                               block=block, interpret=True))
    assert got.dtype == np.int8 and got.shape == (t, t)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(np.arange(1, t + 1), k)).all()
