"""Benchmarks on one TPU chip. Prints ONE JSON line PER metric:
{"metric", "value", "unit", "vs_baseline"}.

Configs mirror BASELINE.json's families (the reference publishes no
in-tree numbers — BASELINE.md):

- llama:  Llama-decoder pretraining tokens/sec/chip. This is a 645M-param
  model with v5e-matched shapes (H=2048/I=5632/L=10) — the single-chip
  HBM-sized stand-in for the Llama-3-8B north star, whose full geometry
  needs the multi-chip path (validated by __graft_entry__.dryrun_multichip).
- resnet: ResNet-50 ImageNet-shape images/sec (single chip, synthetic data).
- moe:    ERNIE-style MoE decoder step time / tokens/sec on one chip
  (expert-parallel sharding is exercised by the dryrun; here all experts
  are chip-resident).
- bert:   BERT-base MLM+NSP pretraining sequences/sec (BASELINE config 2;
  the fleet data-parallel allreduce path is exercised by the dryrun's
  dp axis — here the single-chip step the reference gates per-config).
- sdxl:   Stable-Diffusion-XL-geometry UNet denoising train step
  images/sec (BASELINE config 5: conv + GroupNorm + cross-attention
  compiler path). MFU from an analytic conv+attn FLOP count.
- decode: llama-645M greedy KV-cache decode tokens/sec/chip (the
  serving path; its bar is the HBM memory-bandwidth roofline, not MFU).

``vs_baseline`` is measured MFU / 0.40 — the Megatron-LM A100 MFU bar the
north star asks us to match (">= A100-NCCL MFU") — except for decode,
where it is the fraction of the memory-bandwidth roofline achieved. The
dense-model loss is single-batch memorization, meaningless as a quality
signal, and is NOT printed in the metric.

Run: python bench.py [--config llama|resnet|moe|all] [--profile]
[--steps N]. Each process first prints its device line (platform,
device_kind, count). Without a TPU it runs tiny CPU configs for the
tests; what those print is not a device metric.
--profile captures one step with paddle.profiler.Profiler and writes
bench_trace.json (chrome trace).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

A100_MFU_BAR = 0.40

# set by main() so _emit can attribute the measured MFU to the config
# in the train.mfu gauge (the roll-up + flight recorder read it back)
_BENCH_CONFIG = "bench"


def _emit(metric, value, unit, mfu):
    import paddle_tpu.observability as obs

    if obs.enabled():
        # the bench's measured MFU is the authoritative figure for this
        # config: publish it through the metrics layer so the roll-up
        # line and any flight dump carry it
        obs.registry.get("train.mfu").set(round(float(mfu), 5),
                                          name=_BENCH_CONFIG)
        obs.emit("bench.result", config=_BENCH_CONFIG, unit=unit,
                 value=round(float(value), 1), mfu=round(float(mfu), 4))
    print(json.dumps({
        "metric": metric,
        "value": round(float(value), 1),
        "unit": unit,
        "vs_baseline": round(mfu / A100_MFU_BAR, 3),
    }), flush=True)


def _emit_metrics_block():
    """One JSON line with the observability roll-up (compile counts and
    wall time, cache hit rate, retraces, measured MFU, HBM watermark)
    printed next to the metric line of each config. Requires --metrics
    (which enables paddle_tpu.observability)."""
    import paddle_tpu.observability as obs

    if not obs.enabled():
        return
    obs.sample_device_memory()
    mets = obs.dump()["metrics"]

    def series(name):
        return mets.get(name, {}).get("series", [])

    def tot(name):
        return sum(s.get("value", s.get("count", 0)) for s in series(name))

    def hist_sum(name):
        return sum(s.get("sum", 0.0) for s in series(name))

    def gauge_max(name):
        vals = [s.get("value") for s in series(name)
                if isinstance(s.get("value"), (int, float))]
        return max(vals) if vals else None

    def hist_quantile(name, q, labels=None):
        """Quantile estimate from merged histogram bucket counts
        (linear interpolation inside the crossing bucket). The load
        generator reports exact sample quantiles too; this is the
        registry-side figure so the roll-up works from a dump alone.
        ``labels`` restricts the merge to series carrying those label
        values (e.g. one lifecycle phase of trace.phase_seconds)."""
        ss = series(name)
        if labels:
            ss = [s for s in ss
                  if all((s.get("labels") or {}).get(k) == v
                         for k, v in labels.items())]
        if not ss:
            return None
        bounds = ss[0].get("bounds")
        if not bounds:
            return None
        counts = [0] * (len(bounds) + 1)
        total = 0
        for s in ss:
            for i, c in enumerate(s.get("bucket_counts", [])):
                counts[i] += c
                total += c
        if not total:
            return None
        target = q * total
        cum = 0
        lo = 0.0
        for i, c in enumerate(counts):
            hi = bounds[i] if i < len(bounds) else max(
                s.get("max", bounds[-1]) for s in ss)
            if cum + c >= target and c:
                frac = (target - cum) / c
                return round(lo + frac * (hi - lo), 6)
            cum += c
            lo = hi
        return round(lo, 6)

    hits, misses = tot("dispatch.cache_hits"), tot("dispatch.cache_misses")
    print(json.dumps({"metrics": {
        "dispatch_calls": tot("dispatch.calls"),
        "jit_cache_hits": hits,
        "jit_cache_misses": misses,
        "cache_hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else None,
        "retraces": tot("dispatch.retraces"),
        "to_static_compiles": tot("jit.compiles"),
        "executor_compiles": tot("executor.compiles"),
        "executor_replays": tot("executor.replays"),
        # ROADMAP open item: compile wall time in BENCH records
        "executor_compile_seconds": round(hist_sum("executor.compile_seconds"), 3),
        "jit_compile_seconds": round(hist_sum("jit.compile_seconds"), 3),
        # step-telemetry roll-ups (observability.runtime)
        "train_steps": tot("train.steps"),
        "step_seconds_total": round(hist_sum("train.step_seconds"), 3),
        "mfu": gauge_max("train.mfu"),
        "hbm_watermark_bytes": gauge_max("device.hbm_watermark_bytes"),
        # elastic recovery roll-ups (distributed/elastic.py; nonzero only
        # for runs that actually restarted/resumed)
        "elastic_restarts": tot("elastic.restarts"),
        "elastic_peer_deaths": tot("elastic.peer_deaths"),
        "elastic_steps_lost": tot("elastic.steps_lost"),
        "elastic_rerendezvous_seconds":
            round(hist_sum("elastic.rerendezvous_seconds"), 3),
        "elastic_checkpoint_restore_seconds":
            round(hist_sum("elastic.checkpoint_restore_seconds"), 3),
        # fleet telemetry roll-ups (observability/fleet.py; nonzero only
        # for multi-process runs shipping snapshots / aggregating skew)
        "fleet_ranks_reporting": gauge_max("fleet.ranks_reporting"),
        "fleet_step_skew_seconds": gauge_max("fleet.step_skew_seconds"),
        "fleet_stragglers_detected": tot("fleet.stragglers_detected"),
        "fleet_ship_failures": tot("fleet.ship_failures"),
        # lint->rewrite roll-ups (static/analysis/rewrite.py; nonzero
        # when the optimize exercise / PADDLE_TPU_OPTIMIZE ran)
        "opt_findings_fixed": tot("opt.findings_fixed"),
        "opt_ops_removed": tot("opt.ops_removed"),
        "opt_fixedpoint_iterations": gauge_max("opt.fixedpoint_iterations"),
        "opt_rewrite_seconds": round(hist_sum("opt.rewrite_seconds"), 3),
        "opt_passes_skipped": tot("opt.passes_skipped"),
        # static cost-model roll-ups (static/analysis/cost.py+memory.py;
        # populated by the llama optimize exercise under --metrics)
        "cost_predicted_flops": gauge_max("cost.predicted_flops"),
        "cost_model_flops_error_pct":
            gauge_max("cost.model_flops_error_pct"),
        "cost_predicted_peak_hbm_bytes":
            gauge_max("cost.predicted_peak_hbm_bytes"),
        # predicted-step-time roll-ups (static/analysis/comm_cost.py;
        # the PTL304 drift check publishes the error gauge)
        "cost_predicted_step_seconds":
            gauge_max("cost.predicted_step_seconds"),
        "cost_model_step_error_pct":
            gauge_max("cost.model_step_error_pct"),
        "comm_predicted_bytes": gauge_max("cost.comm_predicted_bytes"),
        # serving-engine roll-ups (paddle_tpu/serve; populated by the
        # `serve` config / tools/serve_load.py load runs)
        "serve_ttft_p50": hist_quantile("serve.ttft_seconds", 0.50),
        "serve_ttft_p99": hist_quantile("serve.ttft_seconds", 0.99),
        "serve_tokens_per_sec": gauge_max("serve.tokens_per_sec"),
        "serve_preemptions": tot("serve.preemptions"),
        # prefix-cache + fused-burst roll-ups (serve/engine.py PR 19):
        # hit rate over admissions, physical blocks NOT re-prefilled,
        # and scheduler host round-trips amortized per generated token
        # (1.0 = the classic one-dispatch-per-token loop; 1/N at
        # steady-state burst N)
        "serve_prefix_hit_rate": round(
            tot("serve.prefix_hits") / tot("serve.requests_admitted"), 4)
        if tot("serve.requests_admitted") else None,
        "serve_blocks_saved": tot("serve.prefix_blocks_shared"),
        "serve_host_roundtrips_per_token": round(
            tot("serve.host_roundtrips") / tot("serve.tokens_generated"),
            4) if tot("serve.tokens_generated") else None,
        # request-lifecycle tracing roll-ups (observability/tracing.py +
        # slo.py; populated when the serve config runs its traced pass)
        "serve_queue_seconds_p99":
            hist_quantile("trace.phase_seconds", 0.99,
                          labels={"phase": "queue"}),
        "serve_prefill_seconds_p99":
            hist_quantile("trace.phase_seconds", 0.99,
                          labels={"phase": "prefill"}),
        "serve_decode_gap_seconds": gauge_max("trace.decode_gap_seconds"),
        "trace_slo_breaches": tot("trace.slo_breaches"),
        # op-level execution-profiler roll-ups (observability/opprof.py;
        # populated when --opprof runs the profiled replay)
        "opprof_steps_profiled": tot("opprof.steps_profiled"),
        "opprof_attributed_pct": gauge_max("opprof.attributed_pct"),
        "opprof_overhead_pct": gauge_max("opprof.overhead_pct"),
        # continuous-health roll-ups (observability/health.py +
        # timeseries.py; populated when PADDLE_TPU_HEALTH is set)
        "health_alerts": tot("health.alerts"),
        "ts_points_recorded": tot("ts.points_recorded"),
    }}), flush=True)


def _profile_one_step(step_fn, *args):
    import paddle_tpu.profiler as profiler

    # Default targets include ProfilerTarget.TPU when an accelerator is
    # attached, so the export merges XLA xplane DEVICE events (decoded by
    # profiler/xplane.py) next to the host spans in one chrome trace.
    with profiler.Profiler() as prof:
        step_fn(*args)
    evs = prof.export("bench_trace.json") or []
    n_dev = sum(1 for e in evs if e.get("cat") == "device")
    print(json.dumps({"profile_events": len(evs),
                      "profile_device_events": n_dev}), flush=True)
    return "bench_trace.json"


def bench_llama(on_tpu, steps, warmup, peak_flops, profile=False):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    if on_tpu:
        # ~645M-param decoder with v5e-matched shapes. Measured matmul
        # ceilings on this chip: [16k,1024]x[1024,2816] runs at 0.39 MFU
        # (K too small to feed the MXU), [16k,2048]x[2048,5632] at 0.70 —
        # so hidden=2048/inter=5632 is the TPU-first geometry. The chunked
        # fused lm_head+CE avoids the fp32 [T,32k] logits that otherwise
        # cap the batch. bs=6/8 measured WORSE (padding/OOM).
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=10, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            recompute=False,
        )
        batch, seq = 4, 2048
    else:
        config = LlamaConfig.tiny()
        batch, seq = 4, 128

    model = LlamaForCausalLM(config)
    n_params = model.num_parameters()
    if on_tpu:
        model.bfloat16()
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          multi_precision=on_tpu)

    @paddle.jit.to_static(full_graph=True)
    def train_step(ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    ids_np = np.random.randint(0, config.vocab_size,
                               (batch, seq)).astype("int64")
    ids = paddle.to_tensor(ids_np)
    labels = paddle.to_tensor(np.roll(ids_np, -1, axis=1))

    for _ in range(warmup):
        loss = train_step(ids, labels)
    float(loss)  # full sync

    # per-step spans feed train.step_seconds + the flight recorder; steps
    # dispatch async so individual numbers skew dispatch-cheap/last-step-
    # heavy — the authoritative MFU comes from the synced window below
    import paddle_tpu.observability as obs
    timer = (obs.StepTimer("llama", items_per_step=batch * seq,
                           unit="tokens", sample_memory_every=0)
             if obs.enabled() else None)  # keep the no-metrics timed
    t0 = time.perf_counter()              # window identical to the seed
    for _ in range(steps):
        if timer is None:
            loss = train_step(ids, labels)
        else:
            with timer.region():
                loss = train_step(ids, labels)
    float(loss)
    dt = time.perf_counter() - t0

    tok_s = batch * seq * steps / dt
    attn_flops = 12 * config.num_hidden_layers * config.hidden_size * seq
    mfu = tok_s * (6 * n_params + attn_flops) / peak_flops
    _emit(f"llama-{n_params / 1e6:.0f}M pretrain tokens/sec/chip "
          f"(bs={batch} seq={seq}, mfu={mfu:.3f}; single-chip stand-in "
          f"for the 8B multi-chip north star)",
          tok_s, "tokens/sec/chip", mfu)
    if profile or on_tpu:
        # always capture one profiled step on real hardware (after the
        # timed window): the profile_device_events count in the bench
        # record is the driver-visible proof that the DEVICE tracer
        # (xplane capture + profiler/xplane.py decode) works on-chip
        path = _profile_one_step(train_step, ids, labels)
        print(json.dumps({"profile_trace": path}), flush=True)


def capture_llama_train_program(config=None, batch=4, seq=128,
                                with_grads=True):
    """The bench llama model captured as a static ``Program``: forward +
    CE loss (+ the grad section when ``with_grads``), with ids/labels as
    feed placeholders. The program the lint->rewrite equivalence
    harness (tests/test_rewrite_passes.py) and the ``--metrics``
    optimize exercise below both run against — one definition, so
    "clean on the bench llama train program" means THIS program.

    Returns ``(prog, feed, fetch)`` where fetch is ``[loss] + grads``
    (or ``[logits]`` without grads — the inference-export slice, where
    the loss ops are dead code by construction)."""
    import paddle_tpu as paddle
    import paddle_tpu.static as static
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    if config is None:
        config = LlamaConfig.tiny()
        # the unfused lm_head+CE path: the export slice below needs
        # materialized logits, and the loss section as separate ops
        config.fused_lm_head_ce = False
    model = LlamaForCausalLM(config)
    params = [p for p in model.parameters() if not p.stop_gradient]
    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, config.vocab_size, (batch, seq)).astype("int64")
    labels_np = np.roll(ids_np, -1, axis=1)
    prog = static.Program()
    with static.program_guard(prog):
        ids = static.data("ids", [batch, seq], "int64")
        labels = static.data("labels", [batch, seq], "int64")
        loss, logits = model(ids, labels=labels)
        if with_grads:
            grads = static.gradients([loss], params)
            fetch = [loss] + list(grads)
        else:
            fetch = [logits]
    feed = {"ids": ids_np, "labels": labels_np}
    return prog, feed, fetch


def bench_optimize(on_tpu):
    """Exercise the lint->rewrite loop on the bench llama program and
    print one JSON line with what it fixed (the ``opt.`` counters land
    in the --metrics roll-up). Two views of the SAME capture:

    - train view (fetch loss+grads): expected CLEAN — zero
      PTL101/102/103/104/105 findings after optimize_program, and the
      fetch outputs must replay bit-exactly;
    - inference-export view (fetch logits only): the CE-loss ops are
      dead and the labels feed is unused by construction — the
      findings_fixed counts the roll-up reports come from real work.

    Geometry-independent (op-level, not shape-level), so it runs the
    tiny config everywhere — on TPU the flagship timing above must not
    pay a second full-size capture."""
    import paddle_tpu.static as static
    from paddle_tpu.static.analysis import (REWRITE_CODES,
                                            optimize_program, run_lints)

    exe = static.Executor()

    prog, feed, fetch = capture_llama_train_program()
    before = exe.run(prog, feed=feed, fetch_list=fetch)
    res_train = optimize_program(prog, fetch=fetch)
    report = run_lints(prog, fetch=fetch, codes=REWRITE_CODES)
    after = exe.run(prog, feed=feed, fetch_list=fetch)
    bitexact = all(np.array_equal(b, a) for b, a in zip(before, after))

    eprog, efeed, efetch = capture_llama_train_program(with_grads=False)
    ops_before = eprog.num_ops
    res_export = optimize_program(eprog, fetch=efetch)
    print(json.dumps({"optimize": {
        "train_findings_remaining": len(report),
        "train_findings_fixed": res_train.total_fixed,
        "train_fetch_bitexact": bitexact,
        "export_findings_fixed": res_export.total_fixed,
        "export_ops_removed": ops_before - eprog.num_ops,
        "export_feeds_pruned": res_export.pruned_feeds,
        "fixedpoint_iterations": max(res_train.iterations,
                                     res_export.iterations),
        # benefit-ordered scheduling: skips across both views. The
        # clean train view records ZERO (a fully-quiescent sweep is
        # not a scheduling decision); the export view's working
        # iterations run only the passes with findings and skip the
        # rest — that is where the nonzero count comes from.
        "passes_skipped": res_train.total_skipped
                          + res_export.total_skipped,
    }}), flush=True)
    bench_cost_model()


def bench_cost_model():
    """Validate the static cost model against ground truth on the bench
    llama train program and print one JSON line (the ``cost.`` gauges
    land in the --metrics roll-up):

    - FLOPs: analytical ``program_cost`` vs XLA's compiled cost
      analysis of the SAME replay (``measure_program_flops``) —
      ``check_cost_model`` files PTL302 past 10%;
    - peak HBM: liveness estimate vs the ``device.hbm_watermark_bytes``
      delta bracketing the FIRST run of a fresh capture (earlier
      in-process allocations are subtracted out via the pre-run
      in-use baseline; on TPU the allocator watermark can still carry
      an earlier config's peak, making the measured side an upper
      bound there — the tight assertion lives in
      tests/test_cost_analysis.py);
    - step time: predicted ``max(compute, memory) + comm`` vs the
      measured replay wall time of the same capture —
      ``check_step_time_model`` files PTL304 past a generous factor-of-
      ten bound (single-chip CPU replay; the tight bound belongs on a
      calibrated TPU run via tools/comm_calibrate.py). With >=2
      devices the capture is also priced under a derived 2-way plan so
      the per-collective ``cost.comm_predicted_*`` table populates."""
    import jax

    import paddle_tpu.observability as obs
    import paddle_tpu.static as static
    from paddle_tpu.static.analysis import (check_cost_model,
                                            check_step_time_model,
                                            estimate_peak_memory,
                                            measure_program_flops,
                                            program_cost)
    from paddle_tpu.static.analysis.comm_cost import record_comm_cost
    from paddle_tpu.static.analysis.cost import (M_MEASURED_PEAK,
                                                 M_PREDICTED_PEAK)

    before = obs.sample_device_memory()["bytes_in_use"]
    prog, feed, fetch = capture_llama_train_program()
    pc = program_cost(prog, fetch)
    measured_flops = measure_program_flops(prog, feed, fetch)
    drift = check_cost_model(pc.flops, measured_flops,
                             tolerance_pct=10, name="llama")

    est = estimate_peak_memory(prog, fetch)
    exe = static.Executor()
    outs = static.Executor().run(prog, feed=feed, fetch_list=fetch,
                                 return_numpy=False)
    after = obs.sample_device_memory()
    measured_peak = max(after["watermark_bytes"] - before, 0)
    del outs
    # Replay wall time of the compiled capture = the measured side of
    # the step-time model (warm run first so compile time stays out).
    exe.run(prog, feed=feed, fetch_list=fetch, return_numpy=False)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        outs = exe.run(prog, feed=feed, fetch_list=fetch,
                       return_numpy=False)
    jax.block_until_ready(outs)
    measured_step = (time.perf_counter() - t0) / reps
    step_drift = check_step_time_model(pc.predicted_step_seconds,
                                       measured_step,
                                       tolerance_pct=900, name="llama")
    if obs.enabled():
        M_PREDICTED_PEAK.set(int(est.peak_bytes), name="llama")
        M_MEASURED_PEAK.set(int(measured_peak), name="llama")

    comm_bytes = 0
    if len(jax.devices()) >= 2:
        from paddle_tpu.distributed.auto_parallel import (
            DistTensorSpec, ProcessMesh, Shard, complete_placements)

        mesh = ProcessMesh([0, 1], dim_names=["mp"])
        # Seed the largest even-sized 2-D placeholder column-parallel so
        # the derived plan actually communicates (unseeded completion
        # replicates everything and prices zero comm).
        seeds = {}
        best = None
        for _name, vid, shape, _dtype in prog._placeholders:
            if len(shape) >= 2 and shape[-1] % 2 == 0:
                size = int(np.prod(shape))
                if best is None or size > best[0]:
                    best = (size, vid, shape)
        if best is not None:
            _, vid, shape = best
            pl = [Shard(len(shape) - 1)]
            seeds[vid] = DistTensorSpec(shape, mesh, pl)
        specs = complete_placements(prog, mesh, seeds)
        pc_sharded = program_cost(prog, fetch, placements=specs)
        if pc_sharded.comm is not None:
            record_comm_cost(pc_sharded.comm, "llama")
            comm_bytes = pc_sharded.comm.total_bytes

    err = (abs(pc.flops - measured_flops) / measured_flops * 100
           if measured_flops else None)
    print(json.dumps({"cost_model": {
        "predicted_flops": pc.flops,
        "measured_flops": measured_flops,
        "flops_error_pct": round(err, 2) if err is not None else None,
        "flops_drift_ptl302": len(drift),
        "predicted_peak_hbm_bytes": int(est.peak_bytes),
        "measured_peak_hbm_bytes": int(measured_peak),
        "peak_op_index": est.peak_op_index,
        "predicted_step_seconds": round(pc.predicted_step_seconds, 6),
        "measured_step_seconds": round(measured_step, 6),
        "step_drift_ptl304": len(step_drift),
        "comm_predicted_bytes_2way": comm_bytes,
    }}), flush=True)


def bench_opprof():
    """Measure the cost of measuring: run the op-level execution
    profiler (observability/opprof.py) over the bench llama train
    program and append a BENCH line with the amortized profiling
    overhead pct and the top-3 op step-share — so the price of
    observing is itself a tracked number (the ``--profile`` analog for
    the per-op timeline).

    The eager per-op-blocking replay is inherently slower than the
    fused jit step; what the budget pacer promises is the AMORTIZED
    rate: one profiled step per pacing interval, jit steps in between.
    That amortized steps/sec is what ``check_opprof_overhead`` holds
    against the 5% PTL503 budget here."""
    import jax

    import paddle_tpu.static as static
    from paddle_tpu.observability import opprof

    prog, feed, fetch = capture_llama_train_program()
    exe = static.Executor()
    # warm the jit path so compile stays out of both sides
    exe.run(prog, feed=feed, fetch_list=fetch, return_numpy=False)
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        outs = exe.run(prog, feed=feed, fetch_list=fetch,
                       return_numpy=False)
    jax.block_until_ready(outs)
    t_jit = (time.perf_counter() - t0) / reps

    budget_pct = opprof.DEFAULT_BUDGET_PCT
    prof = opprof.OpProfiler(name="llama", budget_pct=budget_pct)
    feed_items = sorted(feed.items())
    feed_names = tuple(k for k, _ in feed_items)
    arrays = [np.asarray(v) for _, v in feed_items]
    fetch_vids = [prog.vid_of(t) for t in fetch]
    t0 = time.perf_counter()
    _, profile = prof.run_program(prog, feed_names, arrays, fetch_vids)
    t_prof = time.perf_counter() - t0

    # amortized steps/sec at the pacer's rate: one profiled step
    # (cost t_prof, replacing a jit step) per idle window long enough
    # to keep its share under the budget
    idle = t_prof * (100.0 - budget_pct) / budget_pct
    sps_off = 1.0 / t_jit if t_jit > 0 else 0.0
    sps_on = (idle / t_jit + 1.0) / (idle + t_prof) \
        if t_jit > 0 and (idle + t_prof) > 0 else 0.0
    guard = opprof.check_opprof_overhead(sps_on, sps_off,
                                         tolerance_pct=budget_pct,
                                         name="llama")
    overhead = (100.0 * (sps_off - sps_on) / sps_off) if sps_off else 0.0

    rows = sorted(profile.rows or [],
                  key=lambda r: -float(r["measured_seconds"]))
    top3 = [{"prim": r["prim"], "op": r["index"],
             "share_pct": r["share_pct"]} for r in rows[:3]]
    lint = opprof.lint_op_profile(profile)
    print(json.dumps({"opprof": {
        "profiled_step_seconds": round(profile.step_seconds, 6),
        "jit_step_seconds": round(t_jit, 6),
        "attributed_pct": round(profile.attributed_pct, 3),
        "top3_op_step_share": top3,
        "ptl501_hot_op_drift": len(lint.by_code("PTL501")),
        "ptl502_attribution_shortfall": len(lint.by_code("PTL502")),
        "ptl503_overhead": len(guard),
    }}), flush=True)
    top3_s = ", ".join(f"{t['prim']}={t['share_pct']:.1f}%"
                       for t in top3)
    print(json.dumps({
        "metric": f"opprof overhead pct (amortized at the "
                  f"{budget_pct:.0f}% budget pacer: profiled step "
                  f"{t_prof * 1e3:.1f} ms vs jit step "
                  f"{t_jit * 1e3:.1f} ms; PTL503 above "
                  f"{budget_pct:.0f}%; top-3 op step-share {top3_s}; "
                  f"vs_baseline is profiled/unprofiled steps-per-sec)",
        "value": round(float(overhead), 3),
        "unit": "pct",
        "vs_baseline": round(sps_on / sps_off, 4) if sps_off else 0.0,
    }), flush=True)
    for d in guard:
        print(json.dumps({"diagnostic": d.render()}), flush=True)


def bench_resnet(on_tpu, steps, warmup, peak_flops):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    batch, hw = (256, 224) if on_tpu else (4, 64)
    model = resnet50(num_classes=1000)
    if on_tpu:
        model.bfloat16()
    optimizer = opt.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters(),
                             multi_precision=on_tpu)
    loss_fn = paddle.nn.CrossEntropyLoss()

    @paddle.jit.to_static(full_graph=True)
    def train_step(x, y):
        logits = model(x)
        loss = loss_fn(logits.astype("float32"), y)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    x_np = np.random.rand(batch, 3, hw, hw).astype("float32")
    y_np = np.random.randint(0, 1000, (batch,)).astype("int64")
    x = paddle.to_tensor(x_np.astype("bfloat16") if on_tpu else x_np)
    y = paddle.to_tensor(y_np)

    for _ in range(warmup):
        loss = train_step(x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step(x, y)
    float(loss)
    dt = time.perf_counter() - t0

    ips = batch * steps / dt
    # ResNet-50 @224: ~4.1 GFLOPs forward; training ~3x forward.
    fwd_flops = 4.1e9 * (hw / 224) ** 2
    mfu = ips * 3 * fwd_flops / peak_flops
    # The MFU is this chip's measured CEILING for conv-shaped
    # arithmetic, not a lowering deficiency: bare conv_general_dilated
    # at every ResNet-50 shape class runs at or ABOVE its own
    # implicit-GEMM matmul bound (tools/conv_calibration.py — conv
    # 1.6-4.1 TF/s vs GEMM bound 1.5-3.8; bare-conv band 0.12-0.19
    # MFU), because resnet's K/N GEMM widths sit at the floor of the
    # chip's width-scaling curve (115 TF/s at W=5632 -> single digits
    # at conv widths). The evidence rides IN the metric record so the
    # number is self-justifying.
    ceiling = ("chip conv ceiling: bare-conv 0.12-0.19 MFU; conv "
               "1.6-4.1 TF/s >= implicit-GEMM bound 1.5-3.8 TF/s at "
               "every shape class (tools/conv_calibration.py)")
    print(json.dumps({
        "conv_ceiling_evidence": {
            "bare_conv_mfu_band": [0.12, 0.19],
            "conv_lowering_tf_s": [1.6, 4.1],
            "implicit_gemm_bound_tf_s": [1.5, 3.8],
            "width_curve_tf_s": {"5632": 115, "2816": 72, "1536": 59,
                                 "1408": 49},
            "tool": "tools/conv_calibration.py",
        }}), flush=True)
    _emit(f"resnet50 train images/sec/chip (bs={batch} {hw}x{hw}, "
          f"mfu={mfu:.3f}; at the measured conv ceiling — {ceiling})",
          ips, "images/sec/chip", mfu)


def bench_moe(on_tpu, steps, warmup, peak_flops):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import ErnieMoeConfig, ErnieMoeForCausalLM

    paddle.seed(0)
    if on_tpu:
        # H=2048 matches the chip's GEMM sweet spot (H=1024 caps at
        # ~0.39 MFU on this chip; see bench_llama geometry note).
        # moe_activation="swiglu" (fused [d,2816] gate+up) was the
        # round-4 measured attempt to climb the width curve: 0.541 MFU
        # vs 0.546 here — a recorded null (moe_layer.py note), so the
        # bench keeps the gelu bank.
        config = ErnieMoeConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            moe_intermediate_size=1408, num_hidden_layers=6,
            num_attention_heads=16, num_key_value_heads=16,
            num_experts=8, moe_top_k=2, max_position_embeddings=2048,
        )
        batch, seq = 4, 2048
    else:
        config = ErnieMoeConfig.tiny(num_experts=4, moe_top_k=2)
        batch, seq = 2, 64

    model = ErnieMoeForCausalLM(config)
    n_params = model.num_parameters()
    if on_tpu:
        model.bfloat16()
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          multi_precision=on_tpu)

    @paddle.jit.to_static(full_graph=True)
    def train_step(ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    ids_np = np.random.randint(0, config.vocab_size,
                               (batch, seq)).astype("int64")
    ids = paddle.to_tensor(ids_np)
    labels = paddle.to_tensor(np.roll(ids_np, -1, axis=1))

    for _ in range(warmup):
        loss = train_step(ids, labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step(ids, labels)
    float(loss)
    dt = time.perf_counter() - t0

    step_ms = dt / steps * 1e3
    tok_s = batch * seq * steps / dt
    # active params per token: shared + top_k of num_experts expert FFNs
    try:
        expert_params = sum(
            int(np.prod(p.shape)) for n, p in model.named_parameters()
            if ".experts." in n)
        active = n_params - expert_params + \
            expert_params * config.moe_top_k / config.num_experts
    except Exception:
        active = n_params
    mfu = tok_s * 6 * active / peak_flops
    _emit(f"ernie-moe {n_params / 1e6:.0f}M ({config.num_experts} experts "
          f"top{config.moe_top_k}) step time (bs={batch} seq={seq}, "
          f"{tok_s:.0f} tok/s, mfu={mfu:.3f})", step_ms, "ms/step", mfu)


def bench_bert(on_tpu, steps, warmup, peak_flops):
    """BERT-base pretraining (BASELINE config 2): MLM + NSP step.

    Reference posture: PaddleNLP BERT pretrain under fleet data-parallel
    (the c_allreduce path). Single-chip here; the dp axis itself is
    validated in dryrun_multichip. Geometry note: BERT-base's W=768
    GEMMs sit low on this chip's width-scaling curve (see
    tools/conv_calibration.py) — H=768 is the model's own definition, so
    unlike llama we don't get to pick a TPU-friendlier width.

    Batch scaling RE-MEASURED as one self-consistent sweep (v5e,
    2026-07-31, round-5, ALL points with in-kernel attn dropout;
    tools/bert_batch_sweep.py): bs32 0.377 MFU, bs36 0.415, bs40 0.414,
    bs44 0.405, bs48 0.399 — bs=36 stays the peak (bs40 within 0.2%,
    then monotone decline; bs64 0.344 / bs128 OOM from the round-4
    sweep). Attention dropout (0.1, the reference's
    attention_probs_dropout_prob) runs INSIDE the Pallas flash kernel
    via a counter RNG (ops/pallas/flash_attention.py _dropout_keep), so
    training-parity dropout stays on the flash path.
    """
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import BertConfig, BertForPretraining

    paddle.seed(0)
    if on_tpu:
        config = BertConfig.base()
        # PTPU_BENCH_BERT_BS: sweep hook (tools/bert_batch_sweep) — the
        # shipped default is the measured-optimal point below
        batch, seq = int(os.environ.get("PTPU_BENCH_BERT_BS", "36")), 512
    else:
        config = BertConfig.tiny()
        batch, seq = 4, 64

    model = BertForPretraining(config)
    n_params = model.num_parameters()
    if on_tpu:
        model.bfloat16()
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          multi_precision=on_tpu)

    @paddle.jit.to_static(full_graph=True)
    def train_step(ids, tt, mlm_labels, nsp_labels):
        loss, _, _ = model(ids, tt, masked_lm_labels=mlm_labels,
                           next_sentence_labels=nsp_labels)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, config.vocab_size, (batch, seq)).astype("int64")
    tt_np = (np.arange(seq)[None, :] >= seq // 2).astype("int64") \
        * np.ones((batch, 1), "int64")
    # 15% of positions carry an MLM label, the rest are ignore_index
    mlm_np = np.where(rng.rand(batch, seq) < 0.15, ids_np, -100)
    nsp_np = rng.randint(0, 2, (batch, 1)).astype("int64")
    ids = paddle.to_tensor(ids_np)
    tt = paddle.to_tensor(tt_np)
    mlm = paddle.to_tensor(mlm_np)
    nsp = paddle.to_tensor(nsp_np)

    for _ in range(warmup):
        loss = train_step(ids, tt, mlm, nsp)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step(ids, tt, mlm, nsp)
    float(loss)
    dt = time.perf_counter() - t0

    seq_s = batch * steps / dt
    tok_s = seq_s * seq
    attn_flops = 12 * config.num_hidden_layers * config.hidden_size * seq
    mfu = tok_s * (6 * n_params + attn_flops) / peak_flops
    _emit(f"bert-base {n_params / 1e6:.0f}M pretrain (MLM+NSP) "
          f"sequences/sec/chip (bs={batch} seq={seq}, {tok_s:.0f} tok/s, "
          f"mfu={mfu:.3f}; dp allreduce path validated in dryrun)",
          seq_s, "sequences/sec/chip", mfu)


def _unet_fwd_flops_analytic(cfg, batch, ctx_len):
    """Forward FLOPs of UNet2DConditionModel, mirroring its forward's
    channel/resolution flow exactly (models/unet_diffusion.py:231).
    Counts convs, linears and attention matmuls; norms/activations are
    bandwidth-bound and omitted. Used instead of XLA cost analysis, which
    needs a second compile of the whole graph."""
    B = batch
    chs = list(cfg.block_out_channels)
    temb = chs[0] * cfg.time_embed_mult
    hw0 = cfg.sample_size
    x_dim = cfg.cross_attention_dim

    def conv(cin, cout, h, w, k=3):
        return 2 * B * cout * h * w * cin * k * k

    def res_block(cin, cout, h, w):
        f = conv(cin, cout, h, w) + conv(cout, cout, h, w) \
            + 2 * B * temb * cout
        if cin != cout:
            f += conv(cin, cout, h, w, k=1)
        return f

    def attn_block(ch, h, w):
        n = h * w
        lin = lambda i, o, rows: 2 * B * rows * i * o
        f = lin(ch, ch, n) * 2                     # proj_in / proj_out
        f += 4 * lin(ch, ch, n)                    # self q,k,v,out
        f += 2 * 2 * B * n * n * ch                # self scores + context
        f += 2 * lin(ch, ch, n)                    # cross q, out
        f += 2 * lin(x_dim, ch, ctx_len)           # cross k, v
        f += 2 * 2 * B * n * ctx_len * ch          # cross scores + context
        f += 2 * lin(ch, 4 * ch, n)                # ff1 + ff2
        return f

    total = conv(cfg.in_channels, chs[0], hw0, hw0)
    skip_chs = [chs[0]]
    in_ch = chs[0]
    for level, out_ch in enumerate(chs):
        h = hw0 >> level
        for _ in range(cfg.layers_per_block):
            total += res_block(in_ch, out_ch, h, h)
            if cfg.attention_levels[level]:
                total += attn_block(out_ch, h, h)
            in_ch = out_ch
            skip_chs.append(in_ch)
        if level < len(chs) - 1:
            total += conv(in_ch, in_ch, h // 2, h // 2)   # downsample
            skip_chs.append(in_ch)
    h_mid = hw0 >> (len(chs) - 1)
    total += 2 * res_block(in_ch, in_ch, h_mid, h_mid)
    total += attn_block(in_ch, h_mid, h_mid)
    for level, out_ch in reversed(list(enumerate(chs))):
        h = hw0 >> level
        for _ in range(cfg.layers_per_block + 1):
            skip = skip_chs.pop()
            total += res_block(in_ch + skip, out_ch, h, h)
            if cfg.attention_levels[level]:
                total += attn_block(out_ch, h, h)
            in_ch = out_ch
        if level > 0:
            total += conv(in_ch, in_ch, 2 * h, 2 * h)     # upsample conv
    total += conv(chs[0], cfg.out_channels, hw0, hw0)
    return total


def bench_sdxl_unet(on_tpu, steps, warmup, peak_flops):
    """SDXL-geometry UNet denoising train step (BASELINE config 5).

    Reference posture: PaddleMIX SDXL — conv + GroupNorm + cross-attn
    through the compiler (CINN->StableHLO there, XLA here). Channel
    stack (320, 640, 1280), cross-attention dim 2048 and 64x64 latents
    are SDXL's own geometry (attention only at the 32x32/16x16 levels,
    like SDXL, so no O(4096^2) score matrices materialize). MFU uses
    XLA's post-fusion cost analysis of the model forward (x3 for
    fwd+bwd) — conv FLOPs are not well-served by the 6N rule.
    """
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import UNet2DConditionModel, UNetConfig

    paddle.seed(0)
    if on_tpu:
        # SDXL channel stack / attention placement / context width at
        # layers_per_block=1 (SDXL uses 2): the identical compiler path
        # (same conv/GroupNorm/cross-attn shapes) at half the XLA graph
        # (compile time of the full-depth graph on this chip: not
        # measured)
        config = UNetConfig(
            in_channels=4, out_channels=4, sample_size=64,
            block_out_channels=(320, 640, 1280), layers_per_block=1,
            attention_levels=(False, True, True), num_attention_heads=10,
            cross_attention_dim=2048, norm_num_groups=32,
        )
        # measured batch scaling (2026-07-31): 49.9 img/s at bs=4 ->
        # 75.5 at 8 -> 90.0 at 16 -> 99.8 at 32 (+51/+19/+11%): the
        # latent convs need deep batches to fill the MXU rows
        batch, ctx_len = 32, 77
    else:
        config = UNetConfig.tiny()
        batch, ctx_len = 2, 8

    model = UNet2DConditionModel(config)
    n_params = model.num_parameters()
    if on_tpu:
        model.bfloat16()
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          multi_precision=on_tpu)

    hw = config.sample_size
    rng = np.random.RandomState(0)
    dtype = "bfloat16" if on_tpu else "float32"
    noisy = paddle.to_tensor(
        rng.randn(batch, config.in_channels, hw, hw).astype("float32")
        .astype(dtype))
    eps = paddle.to_tensor(
        rng.randn(batch, config.out_channels, hw, hw).astype("float32")
        .astype(dtype))
    tsteps = paddle.to_tensor(
        rng.randint(0, 1000, (batch,)).astype("int64"))
    context = paddle.to_tensor(
        rng.randn(batch, ctx_len, config.cross_attention_dim)
        .astype("float32").astype(dtype))

    @paddle.jit.to_static(full_graph=True)
    def train_step(x, t, ctx, target):
        pred = model(x, t, ctx)
        loss = ((pred.astype("float32") - target.astype("float32")) ** 2
                ).mean()
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    for _ in range(warmup):
        loss = train_step(noisy, tsteps, context, eps)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step(noisy, tsteps, context, eps)
    float(loss)
    dt = time.perf_counter() - t0

    ips = batch * steps / dt
    # analytic forward FLOPs (structural mirror of the model's forward;
    # see _unet_fwd_flops_analytic for why not XLA cost analysis);
    # training ~= 3x forward
    fwd_flops = _unet_fwd_flops_analytic(config, batch, ctx_len)
    mfu = ips / batch * 3 * fwd_flops / peak_flops
    _emit(f"sdxl-unet {n_params / 1e6:.0f}M denoise train images/sec/chip "
          f"(bs={batch} latents {hw}x{hw}, ctx {ctx_len}x"
          f"{config.cross_attention_dim}, mfu={mfu:.3f}; mfu from "
          f"analytic conv+attn flops)", ips, "images/sec/chip", mfu)


def bench_decode(on_tpu, steps, warmup, peak_flops):
    """llama-645M incremental GREEDY decode (the serving path): bs=8,
    128-token prompt + 128 new tokens through models/generation.py's
    single-jit KV-cache scan.

    The bar is NOT MFU — single-token decode is memory-bandwidth bound
    (every generated token re-reads all params + the KV cache), so
    ``vs_baseline`` is the fraction of the HBM roofline achieved:
    roofline ms/token = (param_bytes + batch * kv_bytes_read) / HBM_BW.
    Reference posture: tools/ci_op_benchmark.sh:131 gates per-config;
    the reference's serving numbers come from the paged/mmha decode ops
    this repo also ships (incubate/nn/functional/inference_attention).
    """
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    if on_tpu:
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=10, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
        )
        batch, prompt, new = 8, 128, 128
        from paddle_tpu.device.chip import chip_peaks

        hbm_bw = chip_peaks()["hbm_bytes_per_sec"]
        reps = 5
    else:
        config = LlamaConfig.tiny()
        batch, prompt, new = 2, 8, 8
        hbm_bw = 100e9
        reps = 2

    model = LlamaForCausalLM(config)
    n_params = model.num_parameters()
    if on_tpu:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(1, config.vocab_size, (batch, prompt)).astype("int64"))

    # two signatures: full decode and 1-token (prefill-only proxy) so the
    # prefill cost can be subtracted out of the per-token latency
    out = model.generate(ids, max_new_tokens=new)          # compile full
    np.asarray(out._value)
    out1 = model.generate(ids, max_new_tokens=1)           # compile 1-tok
    np.asarray(out1._value)

    t0 = time.perf_counter()
    for _ in range(reps):
        out = model.generate(ids, max_new_tokens=new)
    np.asarray(out._value)
    t_full = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        out1 = model.generate(ids, max_new_tokens=1)
    np.asarray(out1._value)
    t_one = (time.perf_counter() - t0) / reps

    per_token_s = max(t_full - t_one, 1e-9) / (new - 1)
    tok_s = batch / per_token_s

    dtype_bytes = 2 if on_tpu else 4
    L = config.num_hidden_layers
    nkv = config.num_key_value_heads
    dh = config.hidden_size // config.num_attention_heads
    avg_s = prompt + new // 2
    param_bytes = n_params * dtype_bytes
    kv_bytes = 2 * L * nkv * dh * avg_s * dtype_bytes      # per sequence
    roofline_s = (param_bytes + batch * kv_bytes) / hbm_bw
    frac = roofline_s / per_token_s
    print(json.dumps({
        "metric": f"llama-{n_params / 1e6:.0f}M greedy decode "
                  f"tokens/sec/chip (bs={batch}, {prompt}+{new} tokens, "
                  f"{per_token_s * 1e3:.2f} ms/token vs "
                  f"{roofline_s * 1e3:.2f} ms HBM roofline at "
                  f"{hbm_bw / 1e9:.0f} GB/s — vs_baseline is the "
                  f"fraction of the memory-bandwidth bound achieved; "
                  f"prefill {t_one * 1e3:.0f} ms excluded)",
        "value": round(float(tok_s), 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(float(frac), 3),
    }), flush=True)


def bench_serve(on_tpu, steps, warmup, peak_flops):
    """Continuous-batching serving engine under Poisson load
    (paddle_tpu/serve): N requests with mixed prompt/output lengths
    arrive at a live ServeEngine; the BENCH record is aggregate
    tokens/sec with p50/p99 TTFT (queue wait included) in the metric
    text and, under --metrics, in the serve_* roll-up keys.

    The engine exists for its scheduling semantics (admission FIFO,
    youngest-first preemption, one persistent compiled decode step —
    see serve/engine.py); per-token throughput still trails the dense
    single-jit scan the `decode` config measures, because each engine
    step is a host round-trip. vs_baseline is the fraction of the
    dense decode path's per-token budget achieved at the same batch
    width (engine tokens/sec / dense-scan tokens/sec), measured here.
    """
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serve import ServeEngine, run_load
    from paddle_tpu.serve.load import default_serving_setup, warm_engine

    paddle.seed(0)
    # shared with tools/serve_load.py — ONE serving shape for the BENCH
    # record and the CLI
    config, sp = default_serving_setup(on_tpu)
    slots, blocks, bs, msl = (sp["slots"], sp["num_blocks"],
                              sp["block_size"], sp["max_seq_len"])
    rate, n_req = sp["rate"], sp["requests"]
    plen, mnew = sp["prompt_len"], sp["max_new"]

    model = LlamaForCausalLM(config)
    n_params = model.num_parameters()
    if on_tpu:
        model.bfloat16()
    model.eval()

    engine = ServeEngine(model, max_slots=slots, block_size=bs,
                         num_blocks=blocks, max_seq_len=msl,
                         name="bench")
    warm_engine(engine)     # decode step + every prefill bucket
    res = run_load(engine, rate=rate, n_requests=n_req,
                   prompt_len=plen, max_new=mnew, seed=0)

    # dense-scan reference at the same batch width: the engine's bar
    dense_ids = paddle.to_tensor(
        np.random.RandomState(0).randint(
            1, config.vocab_size, (slots, plen[1])).astype("int64"))
    n_new = mnew[1]
    out = model.generate(dense_ids, max_new_tokens=n_new)   # compile
    np.asarray(out._value)
    t0 = time.perf_counter()
    out = model.generate(dense_ids, max_new_tokens=n_new)
    np.asarray(out._value)
    dense_tok_s = slots * n_new / (time.perf_counter() - t0)
    frac = res.tokens_per_sec / dense_tok_s if dense_tok_s else 0.0

    print(json.dumps({
        "metric": f"llama-{n_params / 1e6:.0f}M continuous-batching "
                  f"serve tokens/sec ({n_req} Poisson reqs @ "
                  f"{rate:.0f}/s, {slots} slots, {blocks}x{bs} KV "
                  f"blocks; TTFT p50 {res.ttft_p50 * 1e3:.1f} ms / "
                  f"p99 {res.ttft_p99 * 1e3:.1f} ms, "
                  f"{res.preemptions} preemptions; vs_baseline is "
                  f"engine throughput / dense-scan throughput at the "
                  f"same batch width, {dense_tok_s:.0f} tok/s)",
        "value": round(float(res.tokens_per_sec), 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(float(frac), 3),
    }), flush=True)

    # tracing-overhead guard: the identical load replayed with request-
    # lifecycle tracing ON (same seed -> same arrivals/prompts) must hold
    # tokens/sec within the PTL402 budget — a tracer that costs real
    # throughput is a tracer nobody leaves enabled. This pass also
    # populates the trace.* series behind the serve_queue_seconds_p99 /
    # serve_prefill_seconds_p99 / serve_decode_gap_seconds roll-up keys.
    from paddle_tpu.observability.tracing import check_tracing_overhead

    traced = ServeEngine(model, max_slots=slots, block_size=bs,
                         num_blocks=blocks, max_seq_len=msl,
                         name="bench_traced", trace=True)
    warm_engine(traced)
    res_tr = run_load(traced, rate=rate, n_requests=n_req,
                      prompt_len=plen, max_new=mnew, seed=0)
    guard = check_tracing_overhead(
        res_tr.tokens_per_sec, res.tokens_per_sec, tolerance_pct=3.0,
        engine="bench_traced")
    overhead = (100.0 * (res.tokens_per_sec - res_tr.tokens_per_sec)
                / res.tokens_per_sec) if res.tokens_per_sec else 0.0
    print(json.dumps({
        "metric": f"serve tracing overhead pct (traced replay "
                  f"{res_tr.tokens_per_sec:.0f} tok/s vs untraced "
                  f"{res.tokens_per_sec:.0f} tok/s; PTL402 above 3%; "
                  f"vs_baseline is traced/untraced throughput)",
        "value": round(float(overhead), 2),
        "unit": "pct",
        "vs_baseline": round(float(res_tr.tokens_per_sec
                                   / res.tokens_per_sec), 3)
        if res.tokens_per_sec else 0.0,
    }), flush=True)
    for d in guard:
        print(json.dumps({"diagnostic": d.render()}), flush=True)

    # fused-decode-burst comparison: the IDENTICAL Poisson load (same
    # seed -> same arrivals/prompts/output lengths) run one-dispatch-
    # per-token (burst=1) and as 8-step fused scans (burst=8). The
    # solo-equivalence suite pins the token streams byte-identical;
    # this record measures what the fusion buys: scheduler host
    # round-trips per generated token and aggregate tokens/sec. A
    # longer fixed generation (32 tokens) keeps the pow2 burst
    # schedule's tail (8+8+8+4+2+1) from dominating the ratio.
    bp = dict(sp)
    bp["max_new"] = (32, 32)
    if not on_tpu:      # default 24x8 pool can't seat 3x(12+32)-token
        bp.update(block_size=16, num_blocks=30, max_seq_len=80)
    burst_res = {}
    for nburst in (1, 8):
        eng = ServeEngine(model, max_slots=bp["slots"],
                          block_size=bp["block_size"],
                          num_blocks=bp["num_blocks"],
                          max_seq_len=bp["max_seq_len"],
                          name=f"bench_burst{nburst}",
                          decode_burst=nburst)
        warm_engine(eng)
        burst_res[nburst] = run_load(
            eng, rate=rate, n_requests=n_req, prompt_len=bp["prompt_len"],
            max_new=bp["max_new"], seed=0)
    r1, r8 = burst_res[1], burst_res[8]
    print(json.dumps({
        "metric": f"serve fused-decode host round-trips per token, "
                  f"burst=8 vs burst=1 at equal load ({n_req} reqs x "
                  f"{bp['max_new'][1]} tokens: {r8.host_roundtrips} vs "
                  f"{r1.host_roundtrips} dispatches for "
                  f"{r8.total_tokens} tokens each, "
                  f"{r1.host_roundtrips / max(r8.host_roundtrips, 1):.1f}x "
                  f"fewer; {r8.tokens_per_sec:.0f} vs "
                  f"{r1.tokens_per_sec:.0f} tok/s; vs_baseline is "
                  f"burst=8 / burst=1 throughput)",
        "value": round(r8.host_roundtrips / max(r8.total_tokens, 1), 4),
        "unit": "roundtrips/token",
        "vs_baseline": round(r8.tokens_per_sec / r1.tokens_per_sec, 3)
        if r1.tokens_per_sec else 0.0,
    }), flush=True)

    # prefix-cache comparison: the shared-system-prompt workload (a
    # 3-block synthetic prefix on 70% of requests) against a cold
    # engine and a prefix-cache one. blocks_saved counts physical KV
    # blocks mounted from the cache instead of re-prefilled;
    # prefill_tokens is what each engine actually computed.
    shared_tok = 3 * bs
    pref_res = {}
    for on in (False, True):
        eng = ServeEngine(model, max_slots=slots, block_size=bs,
                          num_blocks=blocks, max_seq_len=msl,
                          name=f"bench_prefix_{'warm' if on else 'cold'}",
                          prefix_cache=on or None)
        warm_engine(eng)
        pref_res[on] = run_load(
            eng, rate=rate, n_requests=n_req, prompt_len=plen,
            max_new=(mnew[0], mnew[0]), seed=0,
            shared_prefix_tokens=shared_tok, shared_prefix_frac=0.7)
    cold, warm = pref_res[False], pref_res[True]
    print(json.dumps({
        "metric": f"serve prefix-cache blocks saved under a shared "
                  f"{shared_tok}-token system prompt (70% of {n_req} "
                  f"reqs; {warm.prefix_hits} hits, prefill "
                  f"{warm.prefill_tokens} vs {cold.prefill_tokens} "
                  f"cold tokens; {warm.tokens_per_sec:.0f} vs "
                  f"{cold.tokens_per_sec:.0f} tok/s; vs_baseline is "
                  f"warm/cold prefilled tokens — lower is better)",
        "value": warm.prefix_blocks_shared,
        "unit": "blocks",
        "vs_baseline": round(warm.prefill_tokens / cold.prefill_tokens, 3)
        if cold.prefill_tokens else 0.0,
    }), flush=True)


def _run_isolated(config: str, args) -> int:
    """Run one bench config in its own subprocess.

    Each config gets a fresh process (and therefore a fresh TPU client):
    a previous config's live buffers — e.g. MoE's 6.6 GB of params+opt
    state — can never OOM the next one, and a crash in one config still
    lets the others print their JSON line (round-2's BENCH record lost
    the flagship Llama metric to exactly that cascade).
    """
    import subprocess
    import sys

    cmd = [sys.executable, os.path.abspath(__file__), "--config", config]
    if args.steps:
        cmd += ["--steps", str(args.steps)]
    if args.profile and config == "llama":
        cmd += ["--profile"]
    if args.opprof and config == "llama":
        cmd += ["--opprof"]
    if args.metrics:
        cmd += ["--metrics"]
    env = dict(os.environ, PADDLE_TPU_BENCH_CHILD="1")
    proc = subprocess.run(cmd, env=env)
    if proc.returncode != 0:
        print(f"bench config {config!r} FAILED rc={proc.returncode}",
              file=sys.stderr, flush=True)
    return proc.returncode


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="all",
                    choices=["llama", "resnet", "moe", "bert", "sdxl",
                             "decode", "serve", "all"])
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--opprof", action="store_true",
                    help="run the op-level execution profiler over the "
                         "bench llama train program and append a BENCH "
                         "line with the amortized profiling overhead "
                         "pct and top-3 op step-share")
    ap.add_argument("--metrics", action="store_true",
                    help="enable paddle_tpu.observability and append a "
                         "metrics JSON line per config")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()

    if args.config == "all":
        # flagship (llama) runs and prints LAST: the driver's summary
        # parses the final JSON line as the headline metric
        rcs = [_run_isolated(c, args)
               for c in ("resnet", "bert", "sdxl", "moe", "decode",
                         "serve", "llama")]
        raise SystemExit(sum(1 for rc in rcs if rc != 0))

    from paddle_tpu.device import chip

    # persistent compile cache: the large graphs (sdxl UNet fwd+bwd) take
    # minutes to compile; cache hits make reruns start in seconds
    chip.setup_compile_cache()
    device = chip.device_info()
    print(json.dumps({"device": device}), flush=True)  # once per process
    on_tpu = device["platform"] == "tpu"
    # an unknown chip is an error; 1e12 is the nominal figure the CPU
    # test runs divide by (their numbers are not device metrics)
    peak_flops = chip.chip_peaks()["bf16_flops_per_sec"] if on_tpu else 1e12
    steps = args.steps or (20 if on_tpu else 3)
    warmup = 3 if on_tpu else 1

    global _BENCH_CONFIG
    _BENCH_CONFIG = args.config

    if args.metrics:
        import paddle_tpu.observability as obs

        obs.enable()

    if args.config == "resnet":
        bench_resnet(on_tpu, steps, warmup, peak_flops)
    elif args.config == "moe":
        bench_moe(on_tpu, steps, warmup, peak_flops)
    elif args.config == "bert":
        bench_bert(on_tpu, steps, warmup, peak_flops)
    elif args.config == "sdxl":
        bench_sdxl_unet(on_tpu, steps, warmup, peak_flops)
    elif args.config == "decode":
        bench_decode(on_tpu, steps, warmup, peak_flops)
    elif args.config == "serve":
        bench_serve(on_tpu, steps, warmup, peak_flops)
    elif args.config == "llama":
        bench_llama(on_tpu, steps, warmup, peak_flops, profile=args.profile)
        if args.metrics:
            # after the timed window: prove the lint->rewrite loop on
            # the bench llama program so the opt. counters land in the
            # roll-up below
            bench_optimize(on_tpu)
        if args.opprof:
            # also after the timed window: the profiled replay must
            # never tax the headline tokens/sec measurement
            bench_opprof()

    if args.metrics:
        _emit_metrics_block()
        _maybe_run_bench_compare()


def _maybe_run_bench_compare():
    """Non-fatal regression check over the two newest BENCH_r*.json
    records (tools/bench_compare.py). Skipped inside the per-config
    subprocesses of --config all (the parent already isolated us) so
    the comparison prints once per invocation, and never changes the
    bench exit code — CI gates by running the tool directly."""
    if os.environ.get("PADDLE_TPU_BENCH_CHILD"):
        return
    try:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import bench_compare

        records = bench_compare.latest_bench_records(
            os.path.dirname(os.path.abspath(__file__)))
        if len(records) < 2:
            return
        rows = bench_compare.compare_docs(
            bench_compare._load(records[-2]),
            bench_compare._load(records[-1]))
        print(f"bench_compare ({os.path.basename(records[-2])} -> "
              f"{os.path.basename(records[-1])}):", flush=True)
        print(bench_compare.render_rows(
            rows, bench_compare.DEFAULT_NOISE_PCT), flush=True)
    except Exception as e:  # telemetry must never fail the bench
        print(f"bench_compare skipped: {e}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
