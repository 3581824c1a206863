"""What PR 33 added to the benchmark, on hand-worked numbers and at a tiny
size on the CPU: the DeepSeek-V2 stack's counts at the published sizes (the
decode count ABSORBED), the latent kernel's work, the new cell's files and
traffic, a run of the rest of a run that is `correct`, and the float8
control that is not."""
import json
import os
import time
import types

import numpy as np
import pytest

from benchmark.harness import counts, peaks, serve, spec
from benchmark.harness.traffic import Traffic
from benchmark.stacks import deepseek_v2 as stack
from benchmark.work import mla_decode, moe_experts

from .conftest import CPU_DEVICE

HERE = os.path.dirname(__file__)
NAME = "dsv2-l5-serve-reason-closed192"
CFG = json.load(open(os.path.join(HERE, "..", "configs", "deepseek-v2.json")))
CELL = json.load(open(os.path.join(HERE, "..", "workloads", NAME + ".json")))
MANIFEST = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = os.path.join(HERE, "tiny_deepseek")

Q_A, Q_B = 5120 * 1536, 1536 * 128 * 192
KV_A, KV_B = 5120 * 576, 512 * 128 * 256
O = 128 * 128 * 5120
ATTN = Q_A + Q_B + KV_A + KV_B + O                  # the matrices
EXPERT = 3 * 5120 * 1536
SHARED = 3 * 5120 * 3072
DENSE = 3 * 5120 * 12288
ROUTER = 5120 * 160


def test_parameters_by_hand():
    assert (Q_A, Q_B, KV_A, KV_B, O) == (
        7_864_320, 37_748_736, 2_949_120, 16_777_216, 83_886_080)
    norms = 1536 + 512 + 2 * 5120
    assert ATTN + 1536 + 512 == 149_227_520
    assert ATTN + DENSE + norms == 337_981_440
    outside = ATTN + norms + ROUTER + SHARED
    assert outside == 197_242_880 and EXPERT == 23_592_960
    total = 337_981_440 + 4 * (outside + 20 * EXPERT) \
        + 2 * 12800 * 5120 + 5120
    d = stack.dims(CFG)
    assert d["parameters"] == total == CFG["parameters"] == 3_145_466_880
    assert total * 2 == pytest.approx(6.29e9, rel=1e-3)      # bf16 bytes
    # the whole published model, by the same parts
    whole = 337_981_440 + 59 * (outside + 160 * EXPERT) \
        + 2 * 102400 * 5120 + 5120
    assert whole == pytest.approx(235.7e9, rel=1e-3)
    assert (d["layers"], d["sparse_layers"], d["experts_held"],
            d["experts_published"], d["top_k"]) == (5, 4, 20, 160, 6)
    assert (d["latent_rank"], d["rope_dim"], d["heads"]) == (512, 64, 128)


def test_flops_of_a_token_count_the_absorbed_decode():
    d = stack.dims(CFG)
    # 0.75 routed experts a token a sparse layer: 6 of 160, 20 held
    per_token = (ATTN + DENSE) + 4 * (ATTN + ROUTER + SHARED
                                      + 0.75 * EXPERT) + 5120 * 12800
    assert d["matmul_params"] == per_token
    # 192 streams whose contexts sum to 580k: a head's score is 576
    # multiply-adds a row and its value 512
    assert stack.decode_flops(CFG, 192, 580_000) == \
        2 * per_token * 192 + 5 * 128 * (576 + 512) * 2 * 580_000
    # an expanded decode would pay 2 x kv_b a context token and layer
    expanded = 5 * 2 * KV_B * 580_000
    assert expanded / (5 * 128 * 1088 * 2 * 580_000) > 100
    # a prompt of 300: token j sees j + 1 keys at 192 and 128
    pairs = 300 * 301 // 2
    assert stack.prefill_flops(CFG, 300) == 2 * per_token * 300 \
        + 5 * 128 * (192 + 128) * 2 * pairs - 2 * 12800 * 5120 * 299
    assert stack.forward_flops(CFG, 1, 499) == 2 * per_token \
        + 5 * 128 * ((192 + 128) * 2 + 1088 * 2 * 499)


def test_every_published_number_is_in_the_file_or_named_as_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    entry = next(e for e in map(json.loads, open(CATALOG))
                 if e["name"] == "DeepSeek-V2")
    listed = next(c for c in MANIFEST["configs"]
                  if c["name"] == "deepseek-v2")
    assert listed["source"] == entry["source_url"] == CFG["source"]
    differs = {k for k, v in entry["config"].items() if CFG.get(k) != v}
    assert differs == set(listed["reduced"]) == set(CFG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, cut in CFG["reduced"].items():
        assert cut["published"] == entry["config"][key]
        assert cut["here"] == CFG[key]
    assert CFG["n_routed_experts_published"] == 160
    assert CFG["experts_held"] == [0, CFG["n_routed_experts"]] == [0, 20]
    assert CFG["stack"] == "deepseek_v2"
    assert set(CFG["assumed"]) >= {
        "latent_norms", "rope_layout", "yarn", "router", "initializer_range",
        "dtype", "cache"}
    assert "deployment" in CFG and "group 0" in CFG["deployment"]


def test_leaf_specs_hold_only_the_share():
    shapes = {n: s for n, s, _, _ in stack.leaf_specs(CFG)}
    a = "model.layers.3.self_attn."
    assert shapes[a + "q_a_proj.weight"] == (5120, 1536)
    assert shapes[a + "q_b_proj.weight"] == (1536, 128 * 192)
    assert shapes[a + "kv_a_proj_with_mqa.weight"] == (5120, 576)
    assert shapes[a + "kv_b_proj.weight"] == (512, 128 * 256)
    assert shapes[a + "o_proj.weight"] == (128 * 128, 5120)
    assert shapes["model.layers.1.mlp.experts.gate_up_proj"] == (
        20, 5120, 3072)
    assert shapes["model.layers.4.mlp.experts.down_proj"] == (20, 1536, 5120)
    assert shapes["model.layers.2.mlp.gate.weight"] == (5120, 160)
    assert shapes["model.layers.2.mlp.shared_experts.up_proj.weight"] == (
        5120, 3072)
    assert shapes["lm_head.weight"] == (5120, 12800)
    assert "model.layers.0.mlp.gate_proj.weight" in shapes
    assert "model.layers.5.self_attn.q_a_proj.weight" not in shapes
    assert not [n for n in shapes if "e_score_correction_bias" in n]


def _ctx(decode_rows, sum_ctx):
    sp = types.SimpleNamespace(dims=stack.dims(CFG), name="no-such-engine")
    return {"spec": sp, "traced": {"decode_rows": decode_rows,
                                   "sum_ctx": sum_ctx}}


def test_mla_decode_work_by_hand():
    # one decode step of 192 streams holding 580k tokens: 5 calls
    flops, nbytes = mla_decode.work({}, _ctx(192, 580_000), {"mla_decode": 5})
    assert flops == 580_000 * 5 * 128 * 1088 * 2
    rows = 580_000 * 5 * 576 * 2                  # 1,152 bytes a row
    q_out = 192 * 5 * 128 * (576 + 512) * 2
    assert nbytes == rows + q_out
    assert rows == pytest.approx(3.34e9, rel=0.01)
    # 242 FLOPs a byte of the rows; the chip's ridge is 240.5
    assert flops / rows == pytest.approx(241.8, abs=0.1)
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["bf16_flops_per_s"] / pk["hbm_bytes_per_s"] == pytest.approx(
        240.5, abs=0.1)
    secs, bound = counts.roofline_seconds(flops, nbytes, pk)
    # the query and the result tip it to the memory's side, by 7%
    assert bound == "memory" and secs == pytest.approx(4.41e-3, rel=0.01)
    assert flops / pk["bf16_flops_per_s"] == pytest.approx(4.10e-3, rel=0.01)
    assert mla_decode.work({}, _ctx(0, 0), {"mla_decode": 5}) is None
    # a stack without latent layers reads nothing
    from benchmark.stacks import gpt

    other = json.load(open(os.path.join(
        HERE, "..", "configs", "cerebras-gpt-590m.json")))
    ctx = _ctx(192, 580_000)
    ctx["spec"].dims = gpt.dims(other)
    assert mla_decode.work({}, ctx, {}) is None


def test_moe_experts_work_at_the_new_shape():
    # one decode step of 192 streams: 4 layers, 8 calls, 144 held
    # assignments a layer by the expectation (192 x 6 x 20 / 160)
    flops, nbytes = moe_experts.work(
        {"count_by": "moe_experts"}, _ctx(192, 580_000), {"moe_experts": 8})
    assert flops == pytest.approx(6 * 5120 * 1536 * 576)
    hit = 20 * (1 - (19 / 20) ** 144)
    assert hit == pytest.approx(19.99, abs=0.01)
    assert nbytes == pytest.approx(
        4 * hit * EXPERT * 2 + 576 * (2 * 5120 + 3 * 1536) * 2)
    assert nbytes == pytest.approx(3.79e9, rel=0.01)
    _, bound = counts.roofline_seconds(flops, nbytes,
                                       peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"


def test_the_new_cell_is_one_entry_with_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == NAME)
    assert cell["chips"] == 1 and cell["config"] == "deepseek-v2"
    assert len(cell["why"]) <= 200
    # (by name, not by place: a later PR appends its own after these)
    assert len(MANIFEST["workloads"]) >= 6
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"][:6])
    reports = {m["name"] for m in MANIFEST["end_to_end"]
               if "workloads" not in m or NAME in m["workloads"]}
    assert reports == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in MANIFEST["per_layer"]
            if NAME in m.get("workloads", ())}
    assert mine == {
        "mfu.decode", "device.idle_pct.decode", "serve.host_ms_p50.decode",
        "moe_experts_roofline", "serve.moe_load_max_over_mean",
        "mla_decode_roofline"}
    assert all(m["moves"] == "serve_tokens_per_s"
               for m in MANIFEST["per_layer"]
               if NAME in m.get("workloads", ()))
    entry = next(m for m in MANIFEST["per_layer"]
                 if m["name"] == "mla_decode_roofline")
    assert entry["layer"] == "kernels (ops/pallas)"
    assert entry["workloads"] == [NAME]
    sp = spec.Spec(NAME)
    assert {m["name"] for m, _ in sp.per_layer()} == mine
    assert dict(next(f for m, f in sp.per_layer()
                     if m["name"] == "mla_decode_roofline")) == {
        "reader": "kernel_roofline", "kernels": ["mla_decode"],
        "count_by": "mla_decode", "work": "mla_decode"}


def test_the_cells_traffic_is_what_the_issue_wrote():
    assert CELL["engine"] == {
        "max_slots": 192, "block_size": 128, "num_blocks": CELL["engine"][
            "num_blocks"], "max_seq_len": 8192, "prefix_cache": False,
        "decode_burst": 1}
    assert 5632 <= CELL["engine"]["num_blocks"] <= 6656
    assert (CELL["kind"], CELL["loop"], CELL["clients"], CELL["round"],
            CELL["draw"], CELL["ramp_seconds"], CELL["ramp_batch"],
            CELL["temperature"], CELL["check_requests"]) == (
        "serve", "closed", 192, 32, "grid", 3.0, 4, 0.0, 6)
    assert CELL["prompt_len"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.8, "min": 128, "max": 2048}
    assert CELL["output_len"] == {"dist": "lognormal", "median": 4096,
                                  "sigma": 0.5, "min": 512, "max": 6144}
    others = [json.load(open(os.path.join(HERE, "..", "workloads", f)))
              for f in os.listdir(os.path.join(HERE, "..", "workloads"))
              if f != NAME + ".json"]
    assert CELL["shape_seed"] not in [c.get("shape_seed") for c in others]
    t = Traffic(CELL, 2**31 + 33, stack.vocab_size(CFG))
    reqs = [t.request(i) for i in range(64)]
    lens = np.array([len(p) for p, _, _ in reqs])
    outs = np.array([o for _, o, _ in reqs])
    assert lens.min() >= 128 and lens.max() <= 2048
    assert outs.min() >= 512 and outs.max() <= 6144
    assert abs(np.median(lens) - 512) < 40
    assert abs(np.median(outs) - 4096) < 300
    assert all(p.max() < 12800 and p.min() >= 1 for p, _, _ in reqs)
    assert (lens + outs).max() <= CELL["engine"]["max_seq_len"]
    # the pool holds every stream at its longest: no window may preempt
    assert 192 * ((lens + outs).max() // 128 + 1) <= 2 * CELL["engine"][
        "num_blocks"]


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(TINY, "manifest.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [7, 2**31 + 33])
def test_a_run_at_a_tiny_size_is_correct(tiny, seed):
    """The rest of a run through `run_cell`: the program's model built by
    the stack, the seeded weights, `ServeEngine` over the latent pool in
    bfloat16, warm-up, a closed loop, the reference over what was served."""
    from benchmark import run

    line = run.run_cell("deepseek-tiny-closed", seed, 1.0, False,
                        device=dict(CPU_DEVICE),
                        t_start=time.perf_counter(), bench_dir=TINY,
                        manifest=tiny)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_in_float8_is_not_correct(tiny, seed):
    """The tokens that float8 matrix products put first lie further below
    the reference's best than the limit allows, at this size as on the
    chip (PERF.md §6, PR 33)."""
    sp = spec.Spec("deepseek-tiny-closed", bench_dir=TINY, manifest=tiny)
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(1, 1024, 40), rng.integers(1, 1024, 60))
              for _ in range(2)]
    gap, n = serve.served_gap(sp, seed, sample, control="fp8")
    assert n == 120 and gap > sp.cell["limits"]["served_logit_gap"]
