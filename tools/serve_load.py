#!/usr/bin/env python
"""Synthetic heavy-traffic load test against a local ServeEngine.

Builds a Llama model, stands up a continuous-batching
``paddle_tpu.serve.ServeEngine`` and drives it with Poisson arrivals of
mixed prompt/output lengths (``paddle_tpu/serve/load.py``), then prints
one JSON line with exact sample-based p50/p99 TTFT (queue wait
included), aggregate tokens/sec, preemption and step counts.

Run::

    python tools/serve_load.py --rate 300 --requests 32
    python tools/serve_load.py --metrics    # + observability roll-up
                                            # (same keys as bench.py)
    python tools/serve_load.py --trace-out /tmp/serve_trace \
        --slo '[{"name":"ttft","kind":"ttft_p99","threshold":0.2}]'

``--trace-out DIR`` runs the engine with request-lifecycle tracing and
writes three artifacts into DIR: ``serve_requests.json`` (the
``serve_trace`` dump — per-request span trees, per-phase breakdowns,
decode-step records, tail exemplars; render with
``tools/metrics_report.py --serve-trace DIR``), ``serve_chrome.json``
(one lane per decode slot in ``chrome://tracing`` format, mergeable
into a fleet timeline by ``fleet.merge_chrome_trace_files``) and
``tail_report.txt`` (the worst-TTFT / worst-latency exemplar
breakdowns as text). ``--slo`` attaches SLO rules (inline JSON or a
rules-file path, same syntax as ``PADDLE_TPU_SLO``); breaches print
and, when ``PADDLE_TPU_FLIGHT_DIR`` is set, dump flight recorders
with the exemplars attached.

``bench.py --config serve --metrics`` produces the canonical BENCH
record with the same generator; this CLI is the knob-turning surface
(rate sweeps, pool-pressure experiments via --num_blocks, sampled
streams via --temperature).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Poisson load test against a local ServeEngine")
    ap.add_argument("--rate", type=float, default=None,
                    help="mean arrival rate, requests/sec "
                         "(default: 300 CPU / 30 TPU)")
    ap.add_argument("--requests", type=int, default=None,
                    help="number of requests (default: 16 CPU / 48 TPU)")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slots (continuous-batching width)")
    ap.add_argument("--num_blocks", type=int, default=None,
                    help="KV pool size in blocks (small values force "
                         "queueing + preemption)")
    ap.add_argument("--block_size", type=int, default=None)
    ap.add_argument("--max_seq_len", type=int, default=None)
    ap.add_argument("--prompt_len", type=int, nargs=2, default=None,
                    metavar=("LO", "HI"))
    ap.add_argument("--max_new", type=int, nargs=2, default=None,
                    metavar=("LO", "HI"))
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples every stream")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable cross-request KV prefix sharing "
                         "(PADDLE_TPU_PREFIX_CACHE)")
    ap.add_argument("--decode-burst", type=int, default=1,
                    help="fuse up to N decode steps into one on-chip "
                         "scan dispatch (PADDLE_TPU_DECODE_BURST; "
                         "default 1 = one round-trip per token)")
    ap.add_argument("--shared-prefix-tokens", type=int, default=0,
                    metavar="N",
                    help="prepend one synthetic N-token system prompt "
                         "to a fraction of requests (the prefix-cache "
                         "workload); report blocks-saved in the record")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    metavar="P",
                    help="fraction of requests sharing the synthetic "
                         "system prompt (0.0 .. 1.0)")
    ap.add_argument("--metrics", action="store_true",
                    help="enable observability and print the serve_* "
                         "roll-up keys (bench.py --metrics parity)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="trace every request and write "
                         "serve_requests.json + serve_chrome.json + "
                         "tail_report.txt into DIR")
    ap.add_argument("--slo", default=None, metavar="RULES",
                    help="SLO rules: inline JSON list or a JSON file "
                         "path (PADDLE_TPU_SLO syntax); breaches print "
                         "after the run")
    args = ap.parse_args(argv)

    import paddle_tpu as paddle
    from paddle_tpu.device import chip
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serve import ServeEngine, run_load
    from paddle_tpu.serve.load import default_serving_setup, warm_engine

    if args.metrics or args.trace_out or args.slo:
        import paddle_tpu.observability as obs

        obs.enable()

    chip.setup_compile_cache()
    device = chip.device_info()
    print(json.dumps({"device": device}), flush=True)
    on_tpu = device["platform"] == "tpu"
    paddle.seed(0)
    # defaults shared with bench.py --config serve (ONE serving shape)
    config, defaults = default_serving_setup(on_tpu)

    def pick(cli_value, key):
        # explicit `is None` check: `--rate 0` must reach the engine
        # (and fail its own validation) rather than silently running
        # the default load
        return defaults[key] if cli_value is None else cli_value

    rate = pick(args.rate, "rate")
    n_req = pick(args.requests, "requests")
    slots = pick(args.slots, "slots")
    num_blocks = pick(args.num_blocks, "num_blocks")
    block_size = pick(args.block_size, "block_size")
    max_seq_len = pick(args.max_seq_len, "max_seq_len")
    plen = tuple(pick(args.prompt_len, "prompt_len"))
    mnew = tuple(pick(args.max_new, "max_new"))
    if rate <= 0:
        ap.error(f"--rate must be > 0 requests/sec, got {rate}")

    model = LlamaForCausalLM(config)
    if on_tpu:
        model.bfloat16()
    model.eval()
    if args.shared_prefix_frac and not 0.0 <= args.shared_prefix_frac <= 1.0:
        ap.error(f"--shared-prefix-frac must be in [0, 1], got "
                 f"{args.shared_prefix_frac}")
    engine = ServeEngine(model, max_slots=slots, block_size=block_size,
                         num_blocks=num_blocks, max_seq_len=max_seq_len,
                         name="serve_load",
                         trace=bool(args.trace_out) or None,
                         slo=args.slo,
                         prefix_cache=args.prefix_cache or None,
                         decode_burst=args.decode_burst)
    warm_engine(engine)     # decode + burst scans + every prefill bucket

    res = run_load(engine, rate=rate, n_requests=n_req, prompt_len=plen,
                   max_new=mnew, temperature=args.temperature,
                   seed=args.seed,
                   shared_prefix_tokens=args.shared_prefix_tokens,
                   shared_prefix_frac=args.shared_prefix_frac)
    record = {"load": res.to_dict()}
    record["load"].update(
        rate_rps=rate, slots=slots, num_blocks=num_blocks,
        block_size=block_size, decode_traces=engine.decode_traces,
        prefill_traces=engine.prefill_traces,
        pool_blocks_leaked=engine.pool.used_blocks,
        prefix_cache=bool(args.prefix_cache),
        decode_burst=args.decode_burst,
        shared_prefix_tokens=args.shared_prefix_tokens,
        shared_prefix_frac=args.shared_prefix_frac)
    if engine.slo is not None:
        record["load"]["slo_breaches"] = list(engine.slo.breaches)
    if args.trace_out:
        out = args.trace_out
        os.makedirs(out, exist_ok=True)
        tracer = engine.tracer
        paths = {
            "requests": tracer.dump(
                os.path.join(out, "serve_requests.json")),
            "chrome": tracer.write_chrome_trace(
                os.path.join(out, "serve_chrome.json")),
        }
        tail = os.path.join(out, "tail_report.txt")
        with open(tail, "w") as f:
            f.write(tracer.exemplars.render() + "\n")
        paths["tail"] = tail
        record["trace_out"] = paths
    print(json.dumps(record), flush=True)
    if args.metrics:
        from bench import _emit_metrics_block

        _emit_metrics_block()
    return 0


if __name__ == "__main__":
    sys.exit(main())
