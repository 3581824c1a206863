"""What the Granite 4.0-H Small configuration added to the benchmark, on
hand-worked numbers and at a tiny size on the CPU: the stack's counts at
the cut and the published sizes, the configuration against the catalog,
the work its kernels' rooflines read, the new cell's entries and traffic, a
run of the rest of a run that is `correct`, and controls that are not."""
import json
import os
import time
import types

import numpy as np
import pytest

from benchmark.harness import counts, peaks, serve, spec
from benchmark.harness.traffic import Traffic
from benchmark.stacks import granite_moe_hybrid as stack
from benchmark.work import moe_experts, paged_decode, paged_decode_swa, \
    ssm_decode

from .conftest import CPU_DEVICE

HERE = os.path.dirname(__file__)
NAME = "granite4hsmall-l10-serve-chat-closed128"
CFG = json.load(open(os.path.join(
    HERE, "..", "configs", "granite-4.0-h-small.json")))
CELL = json.load(open(os.path.join(HERE, "..", "workloads", NAME + ".json")))
MANIFEST = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
#: the published config.json's numbers, as the source URL gives them
PUBLISHED = os.path.join(HERE, "granite_small_published.json")
TINY = os.path.join(HERE, "tiny_granite_moe")

MAMBA = 4096 * 16768 + 4 * 8448 + 8448 + 3 * 128 + 8192 + 8192 * 4096
ATTN = 2 * 4096 * 4096 + 2 * 4096 * 1024         # q, o; k, v
SHARED = 3 * 4096 * 1536
ROUTER = 4096 * 72
EXPERT = 3 * 4096 * 768


def test_parameters_by_hand():
    assert (MAMBA, ATTN, SHARED, ROUTER, EXPERT) == (
        102_286_976, 41_943_040, 18_874_368, 294_912, 9_437_184)
    layer = SHARED + ROUTER + 36 * EXPERT + 2 * 4096
    total = 9 * MAMBA + ATTN + 10 * layer + 50176 * 4096 + 4096
    d = stack.dims(CFG)
    assert d["parameters"] == total == CFG["parameters"] == 4_757_211_776
    assert total * 2 == pytest.approx(9.51e9, rel=1e-3)      # bf16 bytes
    assert (d["ssm_layers"], d["full_layers"], d["sliding_layers"],
            d["window"], d["sparse_layers"]) == (9, 1, 0, 0, 10)
    assert (d["experts_held"], d["experts_published"], d["top_k"],
            d["expert_width"], d["expert_params"]) == (36, 72, 10, 768,
                                                       EXPERT)
    assert d["ssm_state_elems"] == 128 * 64 * 128 and d["head_dim"] == 128


def test_flops_of_a_token_by_hand():
    d = stack.dims(CFG)
    # a Mamba layer's two projections or attention's four, in every layer
    # the shared expert, the router and 10 x 36 / 72 = 5 routed experts in
    # expectation, the head's slice
    per_token = 9 * (4096 * 16768 + 8192 * 4096) + ATTN \
        + 10 * (SHARED + ROUTER + 5 * EXPERT) + 50176 * 4096
    assert d["matmul_params"] == per_token
    recur = 6 * 128 * 64 * 128
    assert stack.decode_flops(CFG, 128, 130_000) == 2 * per_token * 128 \
        + 32 * 4 * 128 * 130_000 + 9 * recur * 128
    full = 300 * 301 // 2
    assert stack.prefill_flops(CFG, 300) == 2 * per_token * 300 \
        + 32 * 4 * 128 * full + 9 * recur * 300 - 2 * 50176 * 4096 * 299


def test_every_published_number_is_in_the_file_or_reduced():
    entry = json.load(open(PUBLISHED))
    listed = next(c for c in MANIFEST["configs"]
                  if c["name"] == "granite-4.0-h-small")
    assert listed["source"] == entry["source_url"] == CFG["source"]
    changed = {k for k, v in entry["config"].items() if CFG.get(k) != v}
    assert changed == set(listed["reduced"]) == set(CFG["reduced"]) == {
        "num_hidden_layers", "num_local_experts", "vocab_size"}
    assert (CFG["num_hidden_layers"], CFG["num_local_experts"],
            CFG["vocab_size"]) == (10, 36, 50176)
    assert (CFG["num_experts_published"], CFG["experts_held"],
            CFG["vocab_size_published"]) == (72, [0, 36], 100352)
    assert CFG["layer_types"] == entry["config"]["layer_types"]
    assert CFG["stack"] == "granite_moe_hybrid"
    assert set(CFG["assumed"]) >= {
        "expert_width", "expert_layout", "router", "ssm_state_dtype",
        "seeded_ssm_leaves"}
    assert "2 chips" in CFG["deployment"]


def test_leaf_specs_extend_the_dense_stacks():
    specs = {n: (s, i, sc) for n, s, i, sc in stack.leaf_specs(CFG)}
    e = "model.layers.5.block_sparse_moe."
    assert specs[e + "router.layer.weight"][0] == (4096, 72)
    assert specs[e + "input_linear.weight"][0] == (36, 4096, 1536)
    assert specs[e + "output_linear.weight"][0] == (36, 768, 4096)
    assert specs["model.layers.0.mamba.in_proj.weight"][0] == (4096, 16768)
    assert specs["model.layers.5.self_attn.k_proj.weight"][0] == (4096, 1024)
    assert specs["model.layers.9.shared_mlp.input_linear.weight"][0] == (
        4096, 3072)
    assert specs["model.embed_tokens.weight"][0] == (50176, 4096)
    assert "model.layers.10.mamba.in_proj.weight" not in specs


def _ctx(decode_rows, sum_ctx):
    sp = types.SimpleNamespace(dims=stack.dims(CFG), name="no-such-engine")
    return {"spec": sp, "traced": {"decode_rows": decode_rows,
                                   "sum_ctx": sum_ctx}}


def test_kernel_work_by_hand():
    """One decode step of 128 streams of some 1,010 tokens."""
    ctx = _ctx(128, 129_000)
    # the recurrent step: 9 calls of 128 rows, 2 MiB of state a row read
    # and written
    flops, nbytes = ssm_decode.work({}, ctx, {"ssm_decode": 9})
    state = 128 * 64 * 128 * 2
    io = (2 * 8192 + 2 * 128) * 2 + 128 * 4
    assert nbytes == 128 * 9 * (2 * state + io)
    assert nbytes == pytest.approx(4.85e9, rel=0.01)
    assert flops == 128 * 9 * 6 * 128 * 64 * 128
    # the one attention layer, heads of 128, no window
    flops, nbytes = paged_decode_swa.work({}, ctx, {})
    assert (flops, nbytes) == paged_decode.count(129_000, 128, 32, 8, 128)
    assert nbytes == pytest.approx(0.53e9, rel=0.02)
    # the experts: 128 x 10 x 36 / 72 = 640 held assignments a layer, all
    # 36 experts hit; 20 grouped products (two a layer)
    flops, nbytes = moe_experts.work({"count_by": "moe_experts"}, ctx,
                                     {"moe_experts": 20})
    assert flops == 2 * EXPERT * 6400
    rows = 6400 * (2 * 4096 + 3 * 768) * 2        # routed rows in and out
    assert nbytes == pytest.approx(10 * 36 * EXPERT * 2 + rows, rel=1e-6)
    assert nbytes == pytest.approx(6.93e9, rel=0.01)
    secs, bound = counts.roofline_seconds(flops, nbytes,
                                          peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and secs == pytest.approx(8.3e-3, rel=0.02)


def test_the_new_cell_is_one_entry_with_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == NAME)
    assert cell["chips"] == 1 and cell["config"] == "granite-4.0-h-small"
    assert len(cell["why"]) <= 200
    reports = {m["name"] for m in MANIFEST["end_to_end"]
               if "workloads" not in m or NAME in m["workloads"]}
    assert reports == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in MANIFEST["per_layer"]
            if NAME in m.get("workloads", ())}
    assert mine == {
        "mfu.decode", "device.idle_pct.decode", "serve.host_ms_p50.decode",
        "serve.decode_device_ms_p50.decode", "moe_experts_roofline",
        "serve.moe_load_max_over_mean", "ssm_decode_roofline",
        "paged_decode_swa_roofline"}
    sp = spec.Spec(NAME)
    assert {m["name"] for m, _ in sp.per_layer()} == mine
    assert sp.stack is stack


def test_the_cells_traffic_is_the_micro_cells_at_128_clients():
    micro = json.load(open(os.path.join(
        HERE, "..", "workloads", "granite4hmicro-serve-chat-closed96.json")))
    assert CELL["engine"] == {
        "max_slots": 128, "block_size": 128, "num_blocks": 2048,
        "max_seq_len": 4096, "prefix_cache": False, "decode_burst": 1}
    assert (CELL["kind"], CELL["loop"], CELL["clients"], CELL["round"],
            CELL["draw"], CELL["ramp_seconds"], CELL["temperature"],
            CELL["check_requests"]) == (
        "serve", "closed", 128, 32, "grid", 3.0, 0.0, 6)
    for k in ("prompt_len", "output_len", "round", "draw", "ramp_seconds",
              "ramp_batch", "temperature", "check_requests"):
        assert CELL[k] == micro[k], k
    assert CELL["shape_seed"] != micro["shape_seed"]
    t = Traffic(CELL, 2**31 + 41, stack.vocab_size(CFG))
    reqs = [t.request(i) for i in range(64)]
    lens = np.array([len(p) for p, _, _ in reqs])
    outs = np.array([o for _, o, _ in reqs])
    assert lens.min() >= 32 and lens.max() <= 1024
    assert outs.min() >= 64 and outs.max() <= 3072
    assert all(p.max() < 50176 and p.min() >= 1 for p, _, _ in reqs)
    assert (lens + outs).max() <= CELL["engine"]["max_seq_len"]


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(TINY, "manifest.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [7, 2**31 + 31])
def test_a_run_at_a_tiny_size_is_correct(tiny, seed):
    """The rest of a run through `run_cell`: the program's model built by
    the stack with half its experts held, the seeded weights, `ServeEngine`
    with per-slot state in bfloat16, warm-up, a closed loop, the reference
    over what was served."""
    from benchmark import run

    line = run.run_cell("granite-moe-tiny-closed", seed, 1.0, False,
                        device=dict(CPU_DEVICE),
                        t_start=time.perf_counter(), bench_dir=TINY,
                        manifest=tiny)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("control", ["fp8", "no_shared"])
def test_the_controls_are_not_correct(tiny, control):
    """The tokens that float8 matrix products, or a layer without its
    shared expert, put first lie further below the reference's best than
    the limit allows, even after the excused positions are divided."""
    sp = spec.Spec("granite-moe-tiny-closed", bench_dir=TINY, manifest=tiny)
    rng = np.random.default_rng(3)
    sample = [(rng.integers(1, 1024, 40), rng.integers(1, 1024, 60))
              for _ in range(2)]
    gap, n = serve.served_gap(sp, 3, sample, control=control)
    assert n == 120 and gap > sp.cell["limits"]["served_logit_gap"]
