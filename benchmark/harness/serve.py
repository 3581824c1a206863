"""The one serve driver: `serve.ServeEngine` driven through `submit` and
`step` from this one thread, in a closed loop (each client sends its next
request when the last is answered) or an open loop (arrivals on a
schedule, latency counted from when a request was due)."""
from __future__ import annotations

import time

import numpy as np

from . import check, common, spec as _spec, weights as _weights
from .common import clock, log, span
from .traffic import Traffic


class Rec:
    """One request as the harness sees it from outside."""

    __slots__ = ("req", "idx", "due", "submitted", "client", "seen",
                 "refused", "last_token")

    def __init__(self, idx, due, client=None):
        self.idx, self.due, self.client = idx, due, client
        self.req = self.submitted = None
        self.seen = 0           # output tokens counted so far
        self.last_token = None  # when the last of them was there
        self.refused = False


def build_engine(sp: _spec.Spec, seed: int):
    from paddle_tpu.serve import ServeEngine

    cfg, geo = sp.config, sp.cell["engine"]
    stack = sp.stack
    model = stack.build_model(cfg)
    log("the program's model object built (its own initialisation)")
    w = _weights.make_weights(stack.leaf_specs(cfg), seed, cfg["dtype"])
    for n, p in model.named_parameters():
        if tuple(p.shape) != tuple(w[n].shape):
            raise ValueError(f"leaf {n}: program {p.shape}, seeded "
                             f"{w[n].shape}")
        p._replace_value(w[n])
    del w
    model.eval()
    log("seeded weights in place")
    engine = ServeEngine(
        model, max_slots=geo["max_slots"], block_size=geo["block_size"],
        num_blocks=geo["num_blocks"], max_seq_len=geo["max_seq_len"],
        prefix_cache=geo["prefix_cache"], decode_burst=geo["decode_burst"],
        name=sp.name, trace=False, slo=False)
    return model, engine, stack


class Load:
    """The arrival loop (the benchmark's own; `serve/load.py:run_load`
    counts from `submit`, draws uniform lengths and reports no lateness)."""

    def __init__(self, sp, engine, traffic: Traffic):
        self.sp, self.engine, self.traffic = sp, engine, traffic
        self.cell = sp.cell
        self.closed = self.cell["loop"] == "closed"
        self.recs, self.live = [], []
        self.steps = []       # (t_a, t_b, n_active, decoded, sum_ctx, [prefilled n])
        self.gaps = []        # (when a token was there, seconds since the one before)
        self.next_idx = 0
        self.next_due = None
        self.fin_ptr = len(engine.finished)
        self.by_id = {}

    # -- submission -----------------------------------------------------------
    def _submit(self, rec: Rec, prompt, n_out):
        with span("bench.submit"):
            rec.submitted = clock()
            try:
                rec.req = self.engine.submit(
                    prompt, max_new_tokens=n_out,
                    temperature=self.cell["temperature"])
            except ValueError:
                rec.refused = True
                return
        self.by_id[rec.req.id] = rec
        self.live.append(rec)

    def _new(self, due, client=None, first=False):
        """The next request of the traffic; a closed loop's client starts
        (`first`) with request `client`, at its seeded point of that
        request's life, and later requests follow from `clients` on."""
        if first:
            idx = client
        else:
            idx, self.next_idx = self.next_idx, self.next_idx + 1
        prompt, n_out, _ = self.traffic.request(idx)
        if first:
            n_out = max(2, int(np.ceil(n_out * self.traffic.phase(idx))))
        rec = Rec(idx, due, client)
        self.recs.append(rec)
        self._submit(rec, prompt, n_out)
        return rec

    def start(self, now):
        """The ramp (set-up). Closed loop: the clients start a few a step
        (`ramp_batch`), each at a seeded point of its first request's life
        (first outputs shortened), so that no step of the ramp prefills
        them all at once; the window opens `ramp_seconds` after the last
        has started. Open loop: arrivals start `ramp_seconds` early."""
        self.ramp_end = now + self.cell["ramp_seconds"]
        if self.closed:
            self.unstarted = list(range(self.cell["clients"]))
            self.next_idx = self.cell["clients"]
        else:
            self.next_due = now + self.traffic.request(0)[2]

    def arrivals(self, now, accepting: bool):
        """Open loop: everything due by `now`. Closed loop: the ramp's next
        clients (later requests follow their client's last answer)."""
        if self.closed:
            for c in self.unstarted[:self.cell["ramp_batch"]]:
                self._new(now, client=c, first=True)
                self.ramp_end = now + self.cell["ramp_seconds"]
            del self.unstarted[:self.cell["ramp_batch"]]
            return
        while accepting and self.next_due <= now:
            self._new(self.next_due)
            self.next_due += self.traffic.request(self.next_idx)[2]

    # -- one engine step, seen from outside -------------------------------------
    def step(self, accepting: bool):
        eng = self.engine
        with span("bench.step"):
            t_a = clock()
            n_active = eng.step()
            t_b = clock()
        decoded = sum_ctx = 0
        prefilled = []
        still = []
        for rec in self.live:
            r = rec.req
            new = r.n_generated - rec.seen
            if new > 0:
                if rec.seen == 0:
                    prefilled.append(r.n_prompt)
                    rec.last_token = r.first_token_time
                    new -= 1
                if new > 0:
                    # one decode step a call (`decode_burst` 1); a burst's
                    # tokens would share the time since the last call
                    decoded += new
                    sum_ctx += len(r.ids) - 1
                    self.gaps += [(t_b, (t_b - rec.last_token) / new)] * new
                    rec.last_token = t_b
                rec.seen = r.n_generated
            if r.finish_time is None:
                still.append(rec)
        self.live = still
        self.steps.append((t_a, t_b, n_active, decoded, sum_ctx, prefilled))
        if self.closed:
            done = eng.finished[self.fin_ptr:]
            self.fin_ptr = len(eng.finished)
            for r in done:
                rec = self.by_id.get(r.id)
                if rec is not None and accepting:
                    self._new(t_b, client=rec.client)

    def shift(self, seconds: float):
        """Put off every arrival still to come by `seconds`."""
        if self.next_due is not None:
            self.next_due += seconds

    def idle(self, now):
        """Nothing to step: wait for the next arrival."""
        with span("bench.idle"):
            time.sleep(min(max(self.next_due - now, 0.0), 0.002)
                       if self.next_due is not None else 0.002)

    def tokens(self):
        return sum(rec.req.n_generated for rec in self.recs
                   if rec.req is not None)


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def check_sample(sp, recs, seed: int):
    """The requests whose served tokens the reference reads: the longest
    that finished in the window and `check_requests - 1` more, drawn from
    the seed."""
    if not recs:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])
    order = sorted(recs, key=lambda r: (-len(r.req.ids), r.idx))
    pick = [order[0]]
    rest = order[1:]
    k = min(sp.cell["check_requests"] - 1, len(rest))
    pick += [rest[i] for i in sorted(rng.choice(len(rest), k, replace=False))]
    return pick


def served_gap(sp, seed: int, sample, control=None):
    """The widest gap, over the sample's served tokens, by which a served
    token's logit lies below the reference's best (and how many tokens)."""
    cfg = sp.config
    stack = sp.stack
    ref = _spec.load_by_name("reference", stack.REFERENCE)
    w = _weights.make_weights(stack.leaf_specs(cfg), seed, cfg["dtype"])
    params = ref.stack_params(w, cfg)
    del w
    worst, n = 0.0, 0
    for prompt, served in sample:
        gaps = ref.served_gaps(params, cfg, prompt, served,
                               sp.cell["engine"]["max_seq_len"],
                               control=control)
        worst, n = max(worst, float(gaps.max())), n + len(gaps)
    return worst, n


def run(sp: _spec.Spec, seed: int, seconds: float, trace_on: bool,
        device: dict, t_start: float, trace_seconds: float = 5.0,
        controls=()) -> dict:
    from paddle_tpu.serve.load import warm_engine

    from . import peaks as _peaks

    common.setup_program_cache()
    cell, cfg = sp.cell, sp.config
    log(f"{clock() - t_start:.1f} s since the process began: imports, "
        f"the look for the chip")
    model, engine, stack = build_engine(sp, seed)
    log(f"{clock() - t_start:.1f} s: engine built "
        f"({engine.attention_backend}) on {device}")
    warm_engine(engine)
    log(f"{clock() - t_start:.1f} s: warmed {engine.decode_traces} decode, "
        f"{engine.prefill_traces} prefill programs")
    load = Load(sp, engine, Traffic(cell, seed, stack.vocab_size(cfg)))
    tracer = common.Tracer(sp.name)

    # ---- ramp (set-up), window, drain ------------------------------------------
    load.start(clock())
    t_open = t_close = None
    tok_open = tok_close = 0
    stall_s = 0.0
    traces_open = None
    deadline_drain = None
    while True:
        now = clock()
        if t_open is None and now >= load.ramp_end:
            setup_s = now - t_start
            t_open = clock()
            tok_open = load.tokens()
            traces_open = (engine.decode_traces, engine.prefill_traces)
            preempts_open = getattr(engine, "_n_preempts", 0)
            now = t_open
        if t_open is not None and t_close is None:
            # a traced run traces the last `trace_seconds` of its window
            if trace_on and tracer.t0 is None \
                    and now - t_open >= max(seconds - trace_seconds, 0.0):
                # starting the profiler stops this thread for seconds:
                # the window and the arrivals wait that long, so that the
                # traced part is as steady as the rest
                tracer.start()
                stall_s = clock() - now
                load.shift(stall_s)
                now = clock()
            if now - t_open >= seconds + stall_s:
                # the loop sees the close only between two steps: what
                # fell due in the window's last step is still sent, late
                # as it is, and is one of the window's requests
                t_close = now
                backlog = len(engine.queue)
                due_close = t_open + seconds + stall_s
                load.arrivals(due_close, True)
                tok_close = load.tokens()
                traces_close = (engine.decode_traces, engine.prefill_traces)
                deadline_drain = now + 60.0
                if tracer.running:
                    tracer.stop()
        accepting = t_close is None
        if not accepting:
            waiting = [r for r in load.recs
                       if r.req is not None and r.due >= t_open
                       and r.req.first_token_time is None]
            if not waiting or now > deadline_drain:
                break
        load.arrivals(now, accepting)
        if engine.has_work:
            load.step(accepting)
        else:
            load.idle(now)
    if tracer.running:
        tracer.stop()
    window_s = t_close - t_open - stall_s
    peak_bytes = common.memory_peak_bytes()

    # ---- end-to-end numbers -------------------------------------------------------
    due_in = [r for r in load.recs if t_open <= r.due < due_close]
    unanswered = [r for r in due_in
                  if r.refused or r.req.first_token_time is None]
    ttft = [r.req.first_token_time - r.due for r in due_in
            if r not in unanswered]
    if unanswered and ttft:
        ttft += [max(max(ttft), clock() - t_open)] * len(unanswered)
    finished_in = [r for r in load.recs if r.req is not None
                   and r.req.finish_time is not None
                   and t_open <= r.req.finish_time <= t_close]
    tpot = [gap for t, gap in load.gaps if t_open <= t - gap and t <= t_close]
    out_tokens = tok_close - tok_open
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": out_tokens / window_s,
           "ttft_p95_ms": percentile(ttft, 95) * 1e3 if ttft else None,
           "ttft_mean_ms": float(np.mean(ttft)) * 1e3 if ttft else None,
           "tpot_p95_ms": percentile(tpot, 95) * 1e3 if tpot else None,
           "tpot_mean_ms": float(np.mean(tpot)) * 1e3 if tpot else None}
    log(f"window {window_s:.2f} s: {len(due_in)} due, {len(finished_in)} "
        f"finished, {out_tokens} tokens, {len(unanswered)} unanswered, "
        f"peak {peak_bytes / 1e9:.2f} GB; {e2e}")
    if ttft and tpot:
        log("ttft ms mean %.1f p50 %.1f p90 %.1f p99 %.1f; tpot ms p50 %.1f "
            "p90 %.1f over %d gaps" % (
                np.mean(ttft) * 1e3, *(percentile(ttft, q) * 1e3
                                       for q in (50, 90, 99)),
                *(percentile(tpot, q) * 1e3 for q in (50, 90)), len(tpot)))

    # ---- what the per-layer readers read ---------------------------------------------
    in_win = [s for s in load.steps if t_open <= s[0] and s[1] <= t_close]
    def prefill_flops(n):
        return stack.prefill_flops(cfg, n)

    def flops_of(steps):
        return sum(stack.decode_flops(cfg, s[3], s[4])
                   + sum(prefill_flops(n) for n in s[5]) for s in steps)

    n_pre = sum(len(s[5]) for s in in_win)
    preempts = getattr(engine, "_n_preempts", 0) - preempts_open
    log(f"steps {len(in_win)}, {sum(s[1] - s[0] for s in in_win):.2f} s "
        f"inside them, mean active {np.mean([s[2] for s in in_win]):.1f}, "
        f"{n_pre} prefills of {sum(sum(s[5]) for s in in_win)} tokens in "
        f"{sum(1 for s in in_win if s[5])} steps, {preempts} preemptions")
    traced = None
    if trace_on:
        ts = [s for s in load.steps if tracer.t0 <= s[0] and s[1] <= tracer.t1]
        traced = {"decode_rows": sum(s[3] for s in ts),
                  "sum_ctx": sum(s[4] for s in ts),
                  "prefill_flops": sum(prefill_flops(n) for s in ts
                                       for n in s[5]),
                  "prefills": sum(len(s[5]) for s in ts)}
    ctx = {
        "spec": sp, "peaks": _peaks.peaks_for(device["kind"]),
        "chips": sp.chips, "window_s": window_s, "tracer": tracer,
        "spans": {"step": [s[1] - s[0] for s in in_win], "ttft": ttft,
                  "tpot": tpot},
        "late": [r.submitted - r.due for r in due_in
                 if r.submitted is not None] if not load.closed else [],
        "model_flops": flops_of(in_win), "traced": traced,
    }

    # ---- the comparison, once the engine is freed ---------------------------------------
    sample = [(np.asarray(r.req.prompt), np.asarray(r.req.output_ids))
              for r in check_sample(sp, finished_in, seed)]
    compiles = sum(traces_close) - sum(traces_open)
    load.engine = None
    del engine, model, load
    common.free_device()
    log(f"freed: {common.bytes_in_use() / 1e9:.2f} GB still in use")
    t = clock()
    gap, n_tok = served_gap(sp, seed, sample) if sample else (float("nan"), 0)
    log(f"reference: {clock() - t:.1f} s over {len(sample)} requests, "
        f"{n_tok} served tokens")
    control = {c: served_gap(sp, seed, sample, control=c)[0]
               for c in controls}
    ok, compared = check.verdict(
        {"served_logit_gap": gap, "compiles_in_window": compiles},
        {**cell["limits"], "compiles_in_window": 0},
        extra_ok=not unanswered)
    return {
        "correct": ok, "attempted": len(due_in), "failed": len(unanswered),
        "end_to_end": e2e, "memory_peak_bytes": peak_bytes, "ctx": ctx,
        "compared": compared, "control": control, "backlog": backlog,
        "notes": {"served_logit_gap": f"{n_tok} served tokens of "
                                      f"{len(sample)} requests"},
    }
