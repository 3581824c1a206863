"""What PR 27 added to the benchmark, on hand-worked numbers: the
EXAONE-MoE stack's counts at the published sizes, the two kernels' work,
the counter reader on a recorded registry, and the new cell's traffic."""
import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import counts, peaks
from benchmark.harness.traffic import Traffic
from benchmark.readers import counter_load
from benchmark.stacks import exaone_moe as stack
from benchmark.work import moe_experts, paged_decode, paged_decode_swa

HERE = os.path.dirname(__file__)
CFG = json.load(open(os.path.join(
    HERE, "..", "configs", "k-exaone-236b-a23b.json")))
CELL = json.load(open(os.path.join(
    HERE, "..", "workloads", "kexaone-l5-serve-reason-closed128.json")))
MANIFEST = json.load(open(os.path.join(HERE, "..", "..", "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

ATTN = 2 * 6144 * 64 * 128 + 2 * 6144 * 8 * 128     # q, o; k, v
EXPERT = 3 * 6144 * 2048
DENSE = 3 * 6144 * 18432
ROUTER = 6144 * 128


def test_parameters_by_hand():
    assert (ATTN, EXPERT, DENSE) == (113_246_208, 37_748_736, 339_738_624)
    norms = 2 * 6144 + 2 * 128
    sparse = ATTN + ROUTER + 128 + 17 * EXPERT + norms
    total = (ATTN + DENSE + norms) + 4 * sparse + 2 * 19200 * 6144 + 6144
    d = stack.dims(CFG)
    assert d["parameters"] == total == CFG["parameters"]
    assert total == pytest.approx(3.71e9, rel=2e-3)
    assert total * 2 == pytest.approx(7.42e9, rel=2e-3)     # bf16 bytes


def test_flops_of_a_token_by_hand():
    d = stack.dims(CFG)
    # one routed expert a token a sparse layer: 8 of 128, 16 held
    per_token = (ATTN + DENSE) + 4 * (ATTN + ROUTER + 2 * EXPERT) \
        + 6144 * 19200
    assert d["matmul_params"] == per_token
    assert (d["sliding_layers"], d["full_layers"], d["sparse_layers"]) == (
        4, 1, 4)
    # 128 streams whose contexts sum to 300k: the full layer reads them,
    # each sliding layer 128 keys a stream
    pairs = 1 * 300_000 + 4 * 128 * 128
    assert stack.decode_flops(CFG, 128, 300_000) == \
        2 * per_token * 128 + 64 * 4 * 128 * pairs
    # a prompt of 300: token j sees j + 1 keys, 128 at most in a window
    full = 300 * 301 // 2
    band = 128 * 129 // 2 + (300 - 128) * 128
    assert stack.prefill_flops(CFG, 300) == 2 * per_token * 300 \
        + 64 * 4 * 128 * (full + 4 * band) - 2 * 19200 * 6144 * 299
    assert stack.forward_flops(CFG, 1, 499) == \
        2 * per_token + 64 * 4 * 128 * (500 + 4 * 128)


def test_every_published_number_is_in_the_file_or_named_as_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    entry = next(e for e in map(json.loads, open(CATALOG))
                 if e["name"] == "K-EXAONE-236B-A23B")
    listed = next(c for c in MANIFEST["configs"]
                  if c["name"] == "k-exaone-236b-a23b")
    assert listed["source"] == entry["source_url"] == CFG["source"]
    differs = {k for k, v in entry["config"].items() if CFG.get(k) != v}
    assert differs == set(listed["reduced"]) == set(CFG["reduced"])
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "num_experts_per_tok", "sliding_window",
              "num_attention_heads", "num_key_value_heads")
    assert not differs & set(widths)
    assert CFG["num_experts_published"] == entry["config"]["num_experts"]
    assert CFG["experts_held"] == [0, CFG["num_experts"]]


def test_leaf_specs_hold_only_the_share():
    shapes = {n: s for n, s, _, _ in stack.leaf_specs(CFG)}
    assert shapes["exaone.layers.1.mlp.experts.gate_up_proj"] == (
        16, 6144, 4096)
    assert shapes["exaone.layers.4.mlp.experts.down_proj"] == (
        16, 2048, 6144)
    assert shapes["exaone.layers.2.mlp.gate.weight"] == (6144, 128)
    assert shapes["lm_head.weight"] == (6144, 19200)
    assert "exaone.layers.0.mlp.gate_proj.weight" in shapes
    assert "exaone.layers.5.self_attn.q_proj.weight" not in shapes


def _ctx(decode_rows, sum_ctx):
    sp = types.SimpleNamespace(dims=stack.dims(CFG), name="no-such-engine")
    return {"spec": sp, "traced": {"decode_rows": decode_rows,
                                   "sum_ctx": sum_ctx}}


def test_moe_experts_work_by_hand():
    d = stack.dims(CFG)
    # one decode step of 128 streams: 4 layers, 8 calls, 128 held
    # assignments a layer by the expectation (no counters recorded here)
    flops, nbytes = moe_experts.work(
        {"count_by": "moe_experts"}, _ctx(128, 300_000), {"moe_experts": 8})
    assert flops == 6 * 6144 * 2048 * 512
    hit = 16 * (1 - (15 / 16) ** 128)
    assert hit == pytest.approx(16, abs=0.01)
    assert nbytes == pytest.approx(
        4 * hit * EXPERT * 2 + 512 * (2 * 6144 + 3 * 2048) * 2)
    assert nbytes == pytest.approx(4.83e9, rel=0.01)
    _, bound = counts.roofline_seconds(flops, nbytes,
                                       peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    # few tokens hit few experts: 4 assignments on 16 experts
    assert moe_experts.expected_hit(4, 16) == pytest.approx(3.64, abs=0.01)
    assert moe_experts.work({"count_by": "moe_experts"}, _ctx(0, 0),
                            {"moe_experts": 8}) is None
    assert d["expert_width"] == 2048


def test_paged_decode_swa_work_by_hand():
    flops, nbytes = paged_decode_swa.work({}, _ctx(128, 300_000), {})
    q_out = 2 * 128 * 64 * 128 * 2
    kv = 2 * 8 * 128 * 2                              # a key and a value row
    assert nbytes == (kv * 300_000 + q_out) + 4 * (kv * 128 * 128 + q_out)
    assert flops == 4 * 64 * 128 * (300_000 + 4 * 128 * 128)
    every_layer_full = paged_decode.count(300_000, 128, 64, 8, 128)[1] * 5
    assert nbytes < every_layer_full / 3
    assert paged_decode_swa.work({}, _ctx(0, 0), {}) is None


def test_counter_reader_on_a_recorded_registry():
    from paddle_tpu import observability as obs

    params = {"name": "serve.moe_load_max_over_mean", "experts":
              "experts_held", "max": "bench.test_moe_max",
              "sum": "bench.test_moe_sum"}
    ctx = _ctx(1, 1)
    assert counter_load.read(params, ctx) is None       # no such counter
    biggest = obs.counter("bench.test_moe_max", "test")
    total = obs.counter("bench.test_moe_sum", "test")
    assert counter_load.read(params, ctx) is None       # nothing recorded
    ctx["spec"].name = "recorded"
    for layer, (mx, sm) in {1: (12, 128), 2: (20, 128)}.items():
        biggest.inc(mx, engine="recorded", layer=layer)
        total.inc(sm, engine="recorded", layer=layer)
    biggest.inc(99, engine="another", layer=1)
    total.inc(99, engine="another", layer=1)
    # (12 + 20) over (256 / 16)
    assert counter_load.read(params, ctx) == pytest.approx(2.0)
    assert "2 sparse layers" in ctx["notes"]["serve.moe_load_max_over_mean"]
    # with the routed tokens' counter named: 256 of 256 x 8 assignments
    obs.counter("bench.test_moe_routed", "test").inc(256, engine="recorded")
    params.update(routed="bench.test_moe_routed", top_k="top_k")
    assert counter_load.read(params, ctx) == pytest.approx(2.0)
    assert ctx["notes"]["serve.moe_load_max_over_mean"].endswith("0.1250")


def test_the_new_cell_is_one_entry_with_its_metrics():
    cell = next(w for w in MANIFEST["workloads"]
                if w["name"] == "kexaone-l5-serve-reason-closed128")
    assert cell["chips"] == 1 and cell["config"] == "k-exaone-236b-a23b"
    assert len(cell["why"]) <= 200
    reports = {m["name"] for m in MANIFEST["end_to_end"]
               if "workloads" not in m or cell["name"] in m["workloads"]}
    # `tpot_p95_ms` is not reported: one step in 17 holds a prefill, so
    # the 95th percentile of the token gaps lies on the edge between two
    # kinds of step and spread by 1.30% over six seeds where half its
    # bound is 1.25% (PERF.md §6, PR 27)
    assert reports == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in MANIFEST["per_layer"]
            if cell["name"] in m.get("workloads", ())}
    assert mine == {
        "mfu.decode", "device.idle_pct.decode", "serve.host_ms_p50.decode",
        "moe_experts_roofline", "paged_decode_swa_roofline",
        "serve.moe_load_max_over_mean"}
    assert all(m["moves"] == "serve_tokens_per_s"
               for m in MANIFEST["per_layer"]
               if cell["name"] in m.get("workloads", ()))
    for name in mine:
        assert os.path.exists(os.path.join(HERE, "..", "metrics",
                                           name + ".json"))


def test_the_cells_traffic_is_what_the_issue_wrote():
    assert CELL["engine"] == {
        "max_slots": 128, "block_size": 128, "num_blocks": CELL["engine"][
            "num_blocks"], "max_seq_len": 8192, "prefix_cache": False,
        "decode_burst": 1}
    assert 3072 <= CELL["engine"]["num_blocks"] <= 6144
    assert (CELL["loop"], CELL["clients"], CELL["round"], CELL["draw"]) == (
        "closed", 128, 32, "grid")
    t = Traffic(CELL, 2**31 + 27, stack.vocab_size(CFG))
    reqs = [t.request(i) for i in range(64)]
    lens = np.array([len(p) for p, _, _ in reqs])
    outs = np.array([o for _, o, _ in reqs])
    assert lens.min() >= 128 and lens.max() <= 2048
    assert outs.min() >= 256 and outs.max() <= 6144
    assert abs(np.median(lens) - 512) < 40
    assert abs(np.median(outs) - 2048) < 150
    assert all(p.max() < 19200 and p.min() >= 1 for p, _, _ in reqs)
    assert (lens + outs).max() <= CELL["engine"]["max_seq_len"]
    # the same first requests and starting points whatever the seed
    first = [sorted((len(Traffic(CELL, s, 19200).request(c)[0]),
                     Traffic(CELL, s, 19200).phase(c))
                    for c in range(8)) for s in (1, 2)]
    assert len(first[0]) == 8
