"""What the GLM-5 cell adds to the benchmark, on hand-worked numbers and at
a tiny size on the CPU: the GLM-5 stack's counts at the published sizes (the
decode count absorbed and over the selected rows), the two kernels' work,
the new cell's files and traffic, the sixteen shares of a sparse layer
adding up to the uncut layer, a run of the rest of a run that is
`correct`, and the float8 and dense controls that are not."""
import json
import os
import time
import types

import numpy as np
import pytest

from benchmark.harness import counts, peaks, serve, spec
from benchmark.harness.traffic import Traffic
from benchmark.stacks import glm_moe_dsa as stack
from benchmark.work import dsa_index, mla_sparse_decode, moe_experts

from .conftest import CPU_DEVICE

HERE = os.path.dirname(__file__)
NAME = "glm5-l5-serve-longdoc-closed32"
CFG = json.load(open(os.path.join(HERE, "..", "configs", "glm-5.json")))
CELL = json.load(open(os.path.join(HERE, "..", "workloads", NAME + ".json")))
MANIFEST = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
#: the published config.json's numbers, as the source URL gives them
PUBLISHED = os.path.join(HERE, "glm5_published.json")
TINY = os.path.join(HERE, "tiny_glm5")

Q_A, Q_B = 6144 * 2048, 2048 * 64 * 256
KV_A, KV_B = 6144 * 576, 512 * 64 * 448
O = 64 * 256 * 6144
ATTN = Q_A + Q_B + KV_A + KV_B + O                  # the matrices
INDEXER = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
EXPERT = 3 * 6144 * 2048
DENSE = 3 * 6144 * 12288
ROUTER = 6144 * 256


def test_parameters_by_hand():
    assert ATTN == 165_019_648                         # 165.0M
    assert INDEXER + 2 * 128 == 9_371_904              # 9.37M
    norms = 2048 + 512 + 2 * 6144
    dense_layer = ATTN + INDEXER + 2 * 128 + DENSE + norms
    assert dense_layer == 400_898_816                  # 400.9M
    sparse_layer = ATTN + INDEXER + 2 * 128 + norms + ROUTER + 256 \
        + EXPERT + 16 * EXPERT
    assert sparse_layer == 817_708_032                 # 817.7M
    ends = 2 * 19360 * 6144 + 6144
    assert ends == 237_901_824                         # 237.9M
    total = dense_layer + 4 * sparse_layer + ends
    d = stack.dims(CFG)
    assert d["parameters"] == total == CFG["parameters"] == 3_909_632_768
    assert total * 2 == pytest.approx(7.82e9, rel=1e-3)       # bf16 bytes
    assert (d["layers"], d["sparse_layers"], d["experts_held"],
            d["experts_published"], d["top_k"]) == (5, 4, 16, 256, 8)
    assert (d["latent_rank"], d["rope_dim"], d["heads"], d["index_heads"],
            d["index_dim"], d["index_topk"]) == (512, 64, 64, 32, 128, 2048)
    assert (d["width"], d["expert_width"], d["expert_params"]) == (
        6144, 2048, EXPERT)


def test_flops_count_the_indexer_and_the_selected_rows():
    d = stack.dims(CFG)
    # 0.5 routed experts a token a sparse layer: 8 of 256, 16 held
    per_token = (ATTN + INDEXER + DENSE) + 4 * (
        ATTN + INDEXER + ROUTER + EXPERT + 0.5 * EXPERT) + 6144 * 19360
    assert d["matmul_params"] == per_token
    # 32 streams holding 314k rows: the indexer over every row (32 x 128
    # a row), attention over 32 x 2,048 of them (64 x (576 + 512))
    assert stack.decode_flops(CFG, 32, 314_000) == 2 * per_token * 32 \
        + 5 * (32 * 128 * 2 * 314_000 + 64 * 1088 * 2 * 32 * 2048)
    # a stream shorter than 2,048 attends what it holds
    assert stack.decode_flops(CFG, 2, 3000) == 2 * per_token * 2 \
        + 5 * (32 * 128 * 2 * 3000 + 64 * 1088 * 2 * 3000)
    # a prompt of 7,600: every causal key up to 2,048, then 2,048 a query,
    # at 256 + 256 a head; the indexer over every causal pair
    n = 7600
    pairs = 2048 * 2049 // 2 + (n - 2048) * 2048
    want = 2 * per_token * n + 5 * (64 * 512 * 2 * pairs
                                    + 32 * 128 * 2 * n * (n + 1) // 2) \
        - 2 * 19360 * 6144 * (n - 1)
    assert stack.prefill_flops(CFG, n) == want
    assert want == pytest.approx(26.2e12, rel=0.02)  # ~26 TFLOP
    # under 2,048 the selection is every causal key
    assert stack.prefill_flops(CFG, 300) == 2 * per_token * 300 + 5 * (
        64 * 512 * 2 + 32 * 128 * 2) * (300 * 301 // 2) \
        - 2 * 19360 * 6144 * 299


def test_every_published_number_is_in_the_file_or_named_as_reduced():
    entry = json.load(open(PUBLISHED))
    listed = next(c for c in MANIFEST["configs"] if c["name"] == "glm-5")
    assert listed["source"] == entry["source_url"] == CFG["source"]
    differs = {k for k, v in entry["config"].items() if CFG.get(k) != v}
    assert differs == set(listed["reduced"]) == set(CFG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    for key, cut in CFG["reduced"].items():
        assert cut["published"] == entry["config"][key]
        assert cut["here"] == CFG[key]
    assert CFG["n_routed_experts_published"] == 256
    assert CFG["experts_held"] == [0, CFG["n_routed_experts"]] == [0, 16]
    assert CFG["stack"] == "glm_moe_dsa"
    assert set(CFG["assumed"]) >= {
        "indexer", "indexer_float8", "index_key_norm", "index_rope",
        "rope_layout", "router", "initializer_range", "dtype", "cache",
        "head_dim"}
    assert "16 chips" in CFG["deployment"]


def test_leaf_specs_hold_only_the_share():
    shapes = {n: s for n, s, _, _ in stack.leaf_specs(CFG)}
    a = "model.layers.3.self_attn."
    assert shapes[a + "q_b_proj.weight"] == (2048, 64 * 256)
    assert shapes[a + "kv_b_proj.weight"] == (512, 64 * 448)
    assert shapes[a + "o_proj.weight"] == (64 * 256, 6144)
    assert shapes[a + "indexer.wq_b.weight"] == (2048, 32 * 128)
    assert shapes[a + "indexer.wk.weight"] == (6144, 128)
    assert shapes[a + "indexer.k_norm.bias"] == (128,)
    assert shapes[a + "indexer.weights_proj.weight"] == (6144, 32)
    assert shapes["model.layers.1.mlp.experts.gate_up_proj"] == (
        16, 6144, 4096)
    assert shapes["model.layers.4.mlp.experts.down_proj"] == (16, 2048, 6144)
    assert shapes["model.layers.2.mlp.gate.weight"] == (6144, 256)
    assert shapes["model.layers.2.mlp.gate.e_score_correction_bias"] == (
        256,)
    assert shapes["lm_head.weight"] == (6144, 19360)
    assert "model.layers.0.mlp.gate_proj.weight" in shapes
    assert "model.layers.5.self_attn.q_a_proj.weight" not in shapes


def _ctx(decode_rows, sum_ctx):
    sp = types.SimpleNamespace(dims=stack.dims(CFG), name="no-such-engine")
    return {"spec": sp, "traced": {"decode_rows": decode_rows,
                                   "sum_ctx": sum_ctx}}


def test_the_two_kernels_work_by_hand():
    pk = peaks.peaks_for("TPU v5 lite")
    # one decode step of 32 streams holding 314k rows, 5 layers
    flops, nbytes = dsa_index.work({}, _ctx(32, 314_000), {"dsa_index": 5})
    assert flops == 5 * 314_000 * 32 * 128 * 2
    assert nbytes == 5 * (314_000 * 256 + 32 * 32 * (256 + 4))
    secs, bound = counts.roofline_seconds(flops, nbytes, pk)
    assert bound == "memory"
    assert secs == pytest.approx(0.49e-3, rel=0.02)     # ~0.49 ms a step
    flops, nbytes = mla_sparse_decode.work({}, _ctx(32, 314_000),
                                           {"mla_sparse_decode": 5})
    rows = 32 * 2048
    assert flops == 5 * rows * 64 * 1088 * 2
    assert nbytes == 5 * (rows * 1152 + 32 * 64 * 1088 * 2)
    assert 5 * rows * 1152 == pytest.approx(0.377e9, rel=0.01)
    secs, bound = counts.roofline_seconds(flops, nbytes, pk)
    assert secs == pytest.approx(0.46e-3, rel=0.1)
    # a step whose streams hold fewer rows than they could select
    assert mla_sparse_decode.work({}, _ctx(4, 5000), {})[0] == \
        5 * 5000 * 64 * 1088 * 2
    for work in (dsa_index, mla_sparse_decode):
        assert work.work({}, _ctx(0, 0), {}) is None
    # a stack without an indexer reads nothing
    from benchmark.stacks import deepseek_v2

    other = json.load(open(os.path.join(HERE, "..", "configs",
                                        "deepseek-v2.json")))
    ctx = _ctx(32, 314_000)
    ctx["spec"].dims = deepseek_v2.dims(other)
    assert dsa_index.work({}, ctx, {}) is None
    assert mla_sparse_decode.work({}, ctx, {}) is None


def test_moe_experts_work_at_the_new_shape():
    # one decode step of 32 streams: 4 layers, 8 calls, 16 held
    # assignments a layer by the expectation (32 x 8 x 16 / 256)
    flops, nbytes = moe_experts.work(
        {"count_by": "moe_experts"}, _ctx(32, 314_000), {"moe_experts": 8})
    assert flops == pytest.approx(6 * 6144 * 2048 * 64)
    hit = 16 * (1 - (15 / 16) ** 16)
    assert hit == pytest.approx(10.3, abs=0.05)          # 64% of 16
    assert 4 * hit * EXPERT * 2 == pytest.approx(3.11e9, rel=0.01)


def test_the_new_cell_is_one_entry_with_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == NAME)
    assert cell["chips"] == 1 and cell["config"] == "glm-5"
    assert len(cell["why"]) <= 200
    # by name, never by place: a later PR appends its own after these
    reports = {m["name"] for m in MANIFEST["end_to_end"]
               if "workloads" not in m or NAME in m["workloads"]}
    assert reports == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in MANIFEST["per_layer"]
            if NAME in m.get("workloads", ())}
    assert mine == {
        "mfu.decode", "device.idle_pct.decode", "serve.host_ms_p50.decode",
        "serve.decode_device_ms_p50.decode", "moe_experts_roofline",
        "serve.moe_load_max_over_mean", "dsa_index_roofline",
        "mla_sparse_decode_roofline"}
    assert all(m["moves"] == "serve_tokens_per_s"
               for m in MANIFEST["per_layer"]
               if NAME in m.get("workloads", ()))
    for name in ("dsa_index_roofline", "mla_sparse_decode_roofline"):
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert entry["layer"] == "kernels (ops/pallas)"
        assert entry["workloads"] == [NAME] and entry["unit"] == "%"
    sp = spec.Spec(NAME)
    assert {m["name"] for m, _ in sp.per_layer()} == mine
    kernel = name.replace("_roofline", "")
    assert dict(next(f for m, f in sp.per_layer() if m["name"] == name)) == {
        "reader": "kernel_roofline", "kernels": [kernel],
        "count_by": kernel, "work": kernel}


def test_the_cells_traffic_is_what_the_issue_wrote():
    assert CELL["engine"] == {
        "max_slots": 32, "block_size": 128, "num_blocks": CELL["engine"][
            "num_blocks"], "max_seq_len": 20480, "prefix_cache": False,
        "decode_burst": 1}
    assert 4096 <= CELL["engine"]["num_blocks"] <= 4608
    assert (CELL["kind"], CELL["loop"], CELL["clients"], CELL["round"],
            CELL["draw"], CELL["ramp_batch"], CELL["temperature"]) == (
        "serve", "closed", 32, 32, "grid", 2, 0.0)
    assert CELL["prompt_len"] == {"dist": "lognormal", "median": 7168,
                                  "sigma": CELL["prompt_len"]["sigma"],
                                  "min": 3072, "max": 12288}
    assert CELL["prompt_len"]["sigma"] in (0.35, 0.25)
    assert CELL["output_len"] == {"dist": "lognormal", "median": 4096,
                                  "sigma": 0.4, "min": 1536, "max": 8192}
    others = [json.load(open(os.path.join(HERE, "..", "workloads", f)))
              for f in os.listdir(os.path.join(HERE, "..", "workloads"))
              if f != NAME + ".json"]
    assert CELL["shape_seed"] not in [c.get("shape_seed") for c in others]
    t = Traffic(CELL, 2**31 + 38, stack.vocab_size(CFG))
    reqs = [t.request(i) for i in range(64)]
    lens = np.array([len(p) for p, _, _ in reqs])
    outs = np.array([o for _, o, _ in reqs])
    # every decode stream holds more rows than the 2,048 it selects
    assert lens.min() >= 3072 > 2048 and lens.max() <= 12288
    assert outs.min() >= 1536 and outs.max() <= 8192
    assert abs(np.median(lens) - 7168) < 400
    assert abs(np.median(outs) - 4096) < 300
    assert all(p.max() < 19360 and p.min() >= 1 for p, _, _ in reqs)
    assert (lens + outs).max() <= CELL["engine"]["max_seq_len"]


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The reference's sparse FFN at a tiny size: the sixteen shares'
    routed terms (each a chip's held experts) and the shared expert once
    add up to the layer that holds all 32 experts."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import weights
    from benchmark.reference import glm_moe_dsa as ref

    cfg = json.load(open(os.path.join(TINY, "configs", "glm5-tiny.json")))
    cfg = dict(cfg, dtype="float32", n_routed_experts=32,
               experts_held=[0, 32])
    w = weights.make_weights(stack.leaf_specs(cfg), 5, "float32")
    lp = ref.stack_params(w, cfg)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(0), (24, cfg["hidden_size"]))
    es = ref._einsum("f32")
    whole = ref._moe(h, lp, cfg, es)
    shared = ref._moe(h, dict(lp, **{
        "mlp.experts.gate_up_proj": lp["mlp.experts.gate_up_proj"][:0],
        "mlp.experts.down_proj": lp["mlp.experts.down_proj"][:0]}),
        dict(cfg, experts_held=[0, 0]), es)
    parts = shared
    for first in range(0, 32, 2):
        share = dict(lp, **{
            "mlp.experts.gate_up_proj":
                lp["mlp.experts.gate_up_proj"][first:first + 2],
            "mlp.experts.down_proj":
                lp["mlp.experts.down_proj"][first:first + 2]})
        parts = parts + ref._moe(h, share, dict(cfg, experts_held=[
            first, 2]), es) - shared
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-4, atol=2e-4)
    assert float(jnp.std(whole - shared)) > 0.01      # the experts count


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(TINY, "manifest.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [7, 2**31 + 38])
def test_a_run_at_a_tiny_size_is_correct(tiny, seed):
    """The rest of a run through `run_cell`: the program's model built by
    the stack, the seeded weights, `ServeEngine` over the latent and index
    pools, warm-up, a closed loop whose contexts pass the 16 selected
    rows, the reference over what was served. In float32: at this size a
    key that bfloat16 rounding moves across the 16th index score weighs a
    16th of a softmax (one of 2,048 at the cell's), which moves a logit
    by several units."""
    from benchmark import run

    line = run.run_cell("glm5-tiny-closed", seed, 1.0, False,
                        device=dict(CPU_DEVICE),
                        t_start=time.perf_counter(), bench_dir=TINY,
                        manifest=tiny)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("control", ["fp8", "dense"])
@pytest.mark.parametrize("seed", [1, 2])
def test_the_controls_are_not_correct(tiny, control, seed):
    """The tokens that float8 matrix products put first, and those of the
    mathematics without its selection, lie further below the reference's
    best than the limit allows: contexts of 40-80 against 16 selected."""
    sp = spec.Spec("glm5-tiny-closed", bench_dir=TINY, manifest=tiny)
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(1, 1024, 40), rng.integers(1, 1024, 40))
              for _ in range(2)]
    gap, n = serve.served_gap(sp, seed, sample, control=control)
    assert n == 80 and gap > sp.cell["limits"]["served_logit_gap"]
