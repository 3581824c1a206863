"""What PR 31 added to the benchmark, on hand-worked numbers and at a tiny
size on the CPU: the Granite hybrid stack's counts at the published sizes,
the recurrent step's work, the new cell's files and traffic, a run of the
rest of a run that is `correct`, and the float8 control that is not."""
import json
import os
import time
import types

import numpy as np
import pytest

from benchmark.harness import counts, peaks, serve, spec
from benchmark.harness.traffic import Traffic
from benchmark.stacks import granite_hybrid as stack
from benchmark.work import paged_decode, paged_decode_swa, ssm_decode

from .conftest import CPU_DEVICE

HERE = os.path.dirname(__file__)
NAME = "granite4hmicro-serve-chat-closed96"
CFG = json.load(open(os.path.join(
    HERE, "..", "configs", "granite-4.0-h-micro.json")))
CELL = json.load(open(os.path.join(HERE, "..", "workloads", NAME + ".json")))
MANIFEST = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = os.path.join(HERE, "tiny_granite")

MAMBA = 2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048
ATTN = 2 * 2048 * 2048 + 2 * 2048 * 512          # q, o; k, v
MLP = 3 * 2048 * 8192
EMBED = 100352 * 2048


def test_parameters_by_hand():
    assert (MAMBA, ATTN, MLP, EMBED) == (
        25_847_232, 10_485_760, 50_331_648, 205_520_896)
    total = 36 * (MAMBA + MLP + 4096) + 4 * (ATTN + MLP + 4096) \
        + EMBED + 2048
    d = stack.dims(CFG)
    assert d["parameters"] == total == CFG["parameters"] == 3_191_396_096
    assert total * 2 == pytest.approx(6.38e9, rel=1e-3)      # bf16 bytes
    assert (d["ssm_layers"], d["full_layers"], d["sliding_layers"],
            d["window"]) == (36, 4, 0, 0)
    assert d["ssm_state_elems"] == 64 * 64 * 128 == 524288
    assert d["state_itemsize"] == 2 and d["head_dim"] == 64


def test_flops_of_a_token_by_hand():
    d = stack.dims(CFG)
    # the products a token passes: a Mamba layer's in and out projections
    # (not its convolution, norms or vectors), attention's four, the MLP,
    # the tied head
    per_token = 36 * (2048 * 8512 + 4096 * 2048) + 4 * ATTN + 40 * MLP \
        + EMBED
    assert d["matmul_params"] == per_token
    recur = 6 * 64 * 64 * 128
    # 96 streams whose contexts sum to 97k: four layers read them
    assert stack.decode_flops(CFG, 96, 97_000) == 2 * per_token * 96 \
        + 32 * 4 * 64 * 4 * 97_000 + 36 * recur * 96
    full = 300 * 301 // 2
    assert stack.prefill_flops(CFG, 300) == 2 * per_token * 300 \
        + 32 * 4 * 64 * 4 * full + 36 * recur * 300 \
        - 2 * 100352 * 2048 * 299
    assert stack.forward_flops(CFG, 1, 499) == 2 * per_token \
        + 32 * 4 * 64 * 4 * 500 + 36 * recur


def test_every_published_number_is_in_the_file_and_nothing_is_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    entry = next(e for e in map(json.loads, open(CATALOG))
                 if e["name"] == "granite-4.0-h-micro")
    listed = next(c for c in MANIFEST["configs"]
                  if c["name"] == "granite-4.0-h-micro")
    assert listed["source"] == entry["source_url"] == CFG["source"]
    assert {k for k, v in entry["config"].items() if CFG.get(k) != v} \
        == set()
    assert listed["reduced"] == [] and CFG["reduced"] == {}
    assert CFG["stack"] == "granite_hybrid"
    assert set(CFG["assumed"]) >= {
        "ssm_state_dtype", "time_step_limit", "gate_before_norm",
        "norm_groups", "initializer_range", "seeded_ssm_leaves"}
    assert CFG["layer_types"].count("mamba") == 36
    assert [l for l, t in enumerate(CFG["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]


def test_leaf_specs_are_the_whole_model():
    specs = {n: (s, i, sc) for n, s, i, sc in stack.leaf_specs(CFG)}
    assert specs["model.layers.0.mamba.in_proj.weight"][0] == (2048, 8512)
    assert specs["model.layers.0.mamba.conv1d.weight"][0] == (4, 4352)
    assert specs["model.layers.5.self_attn.k_proj.weight"][0] == (2048, 512)
    assert specs["model.layers.39.shared_mlp.input_linear.weight"][0] == (
        2048, 16384)
    assert "model.layers.5.mamba.in_proj.weight" not in specs
    assert "lm_head.weight" not in specs                  # the head is tied
    assert specs["model.layers.1.mamba.A_log"][1] == "zeros"     # A = 1
    assert specs["model.layers.1.mamba.dt_bias"][1:] == ("normal", 1.0)
    assert specs["model.layers.1.mamba.D"][1] == "ones"


def test_the_seeded_decays_spread_over_the_unit_interval():
    """What `assumed.seeded_ssm_leaves` says the three leaves come to."""
    rng = np.random.default_rng(0)
    n = 200_000
    dt = np.log1p(np.exp(rng.normal(0, np.hypot(0.02 * 2048 ** 0.5, 1.0), n)))
    decay = np.exp(-dt)                                    # A = 1
    q = np.quantile(decay, [0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99])
    assert 0.02 < q[0] < 0.06 and 0.1 < q[1] < 0.2 and 0.25 < q[2] < 0.33
    assert 0.45 < q[3] < 0.55 and 0.67 < q[4] < 0.75 and 0.8 < q[5] < 0.9
    # the longest memories are tens of tokens: on the one token that
    # seeded weights repeat, a state stored in bfloat16 stalls where a
    # step moves it by under 2**-9 of itself, which no head here reaches
    assert 0.94 < q[6] < 0.975


def _ctx(decode_rows, sum_ctx):
    sp = types.SimpleNamespace(dims=stack.dims(CFG), name="no-such-engine")
    return {"spec": sp, "traced": {"decode_rows": decode_rows,
                                   "sum_ctx": sum_ctx}}


def test_ssm_decode_work_by_hand():
    # one decode step of 96 streams: 36 calls of 96 rows
    flops, nbytes = ssm_decode.work({}, _ctx(96, 97_000), {"ssm_decode": 36})
    state = 64 * 64 * 128 * 2                        # 1 MB in bfloat16
    io = (2 * 4096 + 2 * 128) * 2 + 64 * 4           # x, y, B, C; dt
    assert nbytes == 96 * 36 * (2 * state + io)
    assert nbytes == pytest.approx(7.31e9, rel=0.01)
    assert flops == 96 * 36 * 6 * 64 * 64 * 128
    secs, bound = counts.roofline_seconds(flops, nbytes,
                                          peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and secs == pytest.approx(8.9e-3, rel=0.02)
    assert ssm_decode.work({}, _ctx(0, 0), {"ssm_decode": 36}) is None
    # a stack without such layers reads nothing
    from benchmark.stacks import gpt

    other = json.load(open(os.path.join(
        HERE, "..", "configs", "cerebras-gpt-590m.json")))
    ctx = _ctx(96, 97_000)
    ctx["spec"].dims = gpt.dims(other)
    assert ssm_decode.work({}, ctx, {}) is None


def test_paged_decode_swa_counts_the_four_attention_layers():
    flops, nbytes = paged_decode_swa.work({}, _ctx(96, 97_000), {})
    one = paged_decode.count(97_000, 96, 32, 8, 64)
    assert (flops, nbytes) == (4 * one[0], 4 * one[1])
    assert nbytes == pytest.approx(0.8e9, rel=0.05)


def test_the_new_cell_is_one_entry_with_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == NAME)
    assert cell["chips"] == 1 and cell["config"] == "granite-4.0-h-micro"
    assert len(cell["why"]) <= 200
    assert MANIFEST["workloads"][-1] is cell
    reports = {m["name"] for m in MANIFEST["end_to_end"]
               if "workloads" not in m or NAME in m["workloads"]}
    assert reports == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in MANIFEST["per_layer"]
            if NAME in m.get("workloads", ())}
    assert mine == {
        "mfu.decode", "device.idle_pct.decode", "serve.host_ms_p50.decode",
        "paged_decode_swa_roofline", "ssm_decode_roofline"}
    assert all(m["moves"] == "serve_tokens_per_s"
               for m in MANIFEST["per_layer"]
               if NAME in m.get("workloads", ()))
    assert MANIFEST["per_layer"][-1]["name"] == "ssm_decode_roofline"
    sp = spec.Spec(NAME)
    assert {m["name"] for m, _ in sp.per_layer()} == mine
    assert dict(sp.per_layer()[-1][1]) == {
        "reader": "kernel_roofline", "kernels": ["ssm_decode"],
        "count_by": "ssm_decode", "work": "ssm_decode"}


def test_the_cells_traffic_is_what_the_issue_wrote():
    assert CELL["engine"] == {
        "max_slots": 96, "block_size": 128, "num_blocks": 1536,
        "max_seq_len": 4096, "prefix_cache": False, "decode_burst": 1}
    assert (CELL["kind"], CELL["loop"], CELL["clients"], CELL["round"],
            CELL["draw"], CELL["ramp_seconds"], CELL["ramp_batch"],
            CELL["temperature"], CELL["check_requests"]) == (
        "serve", "closed", 96, 32, "grid", 3.0, 4, 0.0, 6)
    assert CELL["prompt_len"] == {"dist": "lognormal", "median": 256,
                                  "sigma": 0.8, "min": 32, "max": 1024}
    assert CELL["output_len"] == {"dist": "lognormal", "median": 768,
                                  "sigma": 0.6, "min": 64, "max": 3072}
    t = Traffic(CELL, 2**31 + 31, stack.vocab_size(CFG))
    reqs = [t.request(i) for i in range(64)]
    lens = np.array([len(p) for p, _, _ in reqs])
    outs = np.array([o for _, o, _ in reqs])
    assert lens.min() >= 32 and lens.max() <= 1024
    assert outs.min() >= 64 and outs.max() <= 3072
    assert abs(np.median(lens) - 256) < 25
    assert abs(np.median(outs) - 768) < 60
    assert all(p.max() < 100352 and p.min() >= 1 for p, _, _ in reqs)
    assert (lens + outs).max() <= CELL["engine"]["max_seq_len"]


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(TINY, "manifest.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [7, 2**31 + 31])
def test_a_run_at_a_tiny_size_is_correct(tiny, seed):
    """The rest of a run through `run_cell`: the program's model built by
    the stack, the seeded weights, `ServeEngine` with per-slot state in
    bfloat16, warm-up, a closed loop, the reference over what was served."""
    from benchmark import run

    line = run.run_cell("granite-tiny-closed", seed, 1.0, False,
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
    the reference's best than the limit allows, at this size as on the chip
    (PERF.md §6, PR 31); no position is excused."""
    sp = spec.Spec("granite-tiny-closed", bench_dir=TINY, manifest=tiny)
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(1, 1024, 40), rng.integers(1, 1024, 60))
              for _ in range(2)]
    gap, n = serve.served_gap(sp, seed, sample, control="fp8")
    assert n == 120 and gap > sp.cell["limits"]["served_logit_gap"]
