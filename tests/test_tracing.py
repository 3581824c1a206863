"""Request-lifecycle tracing, SLO guardrails and the serve-trace lint
(paddle_tpu/observability/tracing.py + slo.py,
static/analysis/serve_trace_lint.py).

Unit-level companions to the engine-integration gates in test_serve.py:
span trees tile submit->finish exactly (loss-free attribution by
construction), validate_trace catches out-of-order hook damage
(PTL403), check_tracing_overhead enforces the instrumentation budget
(PTL402), the SloMonitor latches one breach per excursion (PTL401) and
ships exemplars on the flight dump, and lint_serve_trace reads decode
gaps (PTL404) and preemption thrash (PTL405) off the dump a ServeTracer
writes. Everything runs on a FakeClock — no wall-clock dependence.
"""
import json

import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.observability import slo as slo_mod
from paddle_tpu.observability import tracing as tr_mod
from paddle_tpu.observability.tracing import (
    RequestTrace, ServeTracer, TailExemplars, check_tracing_overhead,
    render_phase_table, render_serve_trace, validate_trace)
from paddle_tpu.serve.engine import Request
from paddle_tpu.static.analysis import (SERVE_TRACE_LINT_CODES,
                                        lint_serve_trace)


def _codes(report):
    return sorted({d.code for d in report})


class TestRequestTrace:
    def test_phases_tile_the_root_exactly(self):
        t = RequestTrace(7, 10.0)
        t.begin_phase("queue", 10.0)
        t.begin_phase("prefill", 10.4, slot=1)
        t.begin_phase("decode", 10.5, slot=1)
        t.finish(11.0, "eos")
        assert t.finished
        ph = t.phase_seconds()
        assert ph == pytest.approx(
            {"queue": 0.4, "prefill": 0.1, "decode": 0.5})
        # loss-free by construction: transitions share timestamps, so
        # the leaves sum to the root span exactly
        assert sum(ph.values()) == pytest.approx(t.root.seconds)
        assert t.root.attrs["finish_reason"] == "eos"

    def test_attributed_seconds_clips_to_first_token(self):
        t = RequestTrace(0, 0.0)
        t.begin_phase("queue", 0.0)
        t.begin_phase("prefill", 1.0)
        t.begin_phase("decode", 1.5)
        t.first_token_time = 1.5
        t.finish(3.0)
        ttft = t.attributed_seconds(upto=1.5)
        assert ttft == pytest.approx({"queue": 1.0, "prefill": 0.5})
        assert sum(ttft.values()) == pytest.approx(1.5)

    def test_mutators_are_noops_after_finish(self):
        t = RequestTrace(0, 0.0)
        t.begin_phase("queue", 0.0)
        t.finish(1.0)
        assert t.begin_phase("decode", 2.0) is None
        t.annotate(bucket=8)
        assert len(t.root.children) == 1
        assert "bucket" not in t.root.children[0].attrs
        t.finish(9.0)                       # idempotent
        assert t.root.end == 1.0

    def test_repeated_phases_accumulate(self):
        t = RequestTrace(0, 0.0)
        for i in range(3):
            t.begin_phase("decode", float(i), slot=0)
            t.begin_phase("preempt", i + 0.6)
        t.finish(3.0)
        ph = t.phase_seconds()
        assert ph["decode"] == pytest.approx(0.6 * 3)
        assert ph["preempt"] == pytest.approx(0.4 * 3)


class TestValidateTrace:
    """PTL403: structural damage from out-of-order hooks is named with
    a machine-readable reason slug."""

    def _doc(self, children, end=5.0):
        return {"id": 1, "spans": {"name": "request", "start": 0.0,
                                   "end": end, "children": children}}

    def test_well_formed_tree_is_clean(self):
        doc = self._doc([
            {"name": "queue", "start": 0.0, "end": 1.0},
            {"name": "prefill", "start": 1.0, "end": 2.0},
            {"name": "decode", "start": 2.0, "end": 5.0}])
        assert not validate_trace(doc).diagnostics

    @pytest.mark.parametrize("children,end,reason", [
        ([], 5.0, "no_phases"),
        ([{"name": "queue", "start": 0.0, "end": 1.0}], None, "root_open"),
        ([{"name": "teleport", "start": 0.0, "end": 1.0}],
         5.0, "unknown_phase"),
        ([{"name": "decode", "start": 1.0, "end": None}],
         5.0, "phase_open"),
        ([{"name": "decode", "start": 2.0, "end": 1.0}],
         5.0, "negative_span"),
        ([{"name": "queue", "start": -1.0, "end": 1.0}],
         5.0, "outside_root"),
        ([{"name": "queue", "start": 0.0, "end": 6.0}],
         5.0, "outside_root"),
        ([{"name": "queue", "start": 0.0, "end": 2.0},
          {"name": "prefill", "start": 1.0, "end": 3.0}],
         5.0, "overlap"),
    ])
    def test_damage_is_coded_with_reason(self, children, end, reason):
        report = validate_trace(self._doc(children, end))
        assert _codes(report) == ["PTL403"]
        assert reason in [(d.suggestion or {}).get("reason")
                          for d in report]


class TestTracingOverheadGuard:
    def test_within_budget_is_clean(self):
        assert not check_tracing_overhead(
            98.0, 100.0, tolerance_pct=3.0, engine="g1").diagnostics
        assert obs.registry.get("trace.overhead_pct").value(
            engine="g1") == pytest.approx(2.0)

    def test_over_budget_emits_ptl402(self):
        report = check_tracing_overhead(90.0, 100.0, tolerance_pct=3.0,
                                        engine="g2")
        assert _codes(report) == ["PTL402"]
        (d,) = list(report)
        assert d.suggestion["overhead_pct"] == pytest.approx(10.0)

    def test_zero_baseline_is_not_judged(self):
        assert not check_tracing_overhead(5.0, 0.0).diagnostics


class TestServeTracerHooks:
    """Drive the tracer through a synthetic request lifecycle on a
    FakeClock — no engine, no model, pure hook-ordering checks."""

    def _req(self, clk, rid=0):
        r = Request(id=rid, prompt=np.arange(1, 5, dtype=np.int32),
                    max_new_tokens=4, submit_time=clk.time())
        r.ids = [int(x) for x in r.prompt]
        return r

    def test_preempted_lifecycle_builds_the_canonical_chain(self):
        clk = obs.FakeClock(tick=0.001)
        tr = ServeTracer("t1", clk, max_slots=2)
        req = self._req(clk)
        tr.on_submit(req)
        tr.on_admit(req, 0, resumed=False)
        tr.on_prefill(req, bucket=8, tokens=4)
        tr.on_first_token(req, clk.time())
        req.first_token_time = req.trace.first_token_time
        tr.on_decode_begin(req)
        req.ids.append(5)
        tr.on_preempt(req)
        req.preemptions += 1
        tr.on_admit(req, 1, resumed=True)
        tr.on_prefill(req, bucket=8, tokens=4)   # resume -> recompute
        tr.on_decode_begin(req)
        req.finish_time = clk.time()
        req.finish_reason = "max_new_tokens"
        tr.on_finish(req)
        (doc,) = list(tr.requests)
        names = [c["name"] for c in doc["spans"]["children"]]
        assert names == ["queue", "prefill", "decode", "preempt",
                         "resume", "recompute", "decode"]
        assert not doc.get("malformed")
        assert doc["ttft_attributed_pct"] == pytest.approx(100.0)
        assert doc["latency_attributed_pct"] == pytest.approx(100.0)
        # the recompute span carries the slot it resumed into
        rec = [c for c in doc["spans"]["children"]
               if c["name"] == "recompute"]
        assert rec[0]["attrs"]["bucket"] == 8
        assert tr.n_traced == 1

    def test_decode_gap_counts_only_runnable_slots(self):
        clk = obs.FakeClock()
        tr = ServeTracer("t2", clk, max_slots=1)
        tr.on_decode_step(0.0, 0.01, active_after=1, queued=0)
        tr.on_decode_step(0.05, 0.06, active_after=0, queued=0)  # 40ms gap
        tr.on_decode_step(0.50, 0.51, active_after=1, queued=2)  # idle: no gap
        assert tr.total_decode_gap == pytest.approx(0.04)
        assert obs.registry.get("trace.decode_gap_seconds").value(
            engine="t2") == pytest.approx(0.04)

    def test_a_step_queued_behind_the_last_is_no_gap(self):
        # the engine's steady order: a program is dispatched before the
        # one before it is read, so it starts before that one ends
        clk = obs.FakeClock()
        tr = ServeTracer("t2b", clk, max_slots=1)
        tr.on_decode_step(0.000, 0.020, active_after=1, queued=0)
        tr.on_decode_step(0.004, 0.040, active_after=1, queued=0)  # queued
        tr.on_decode_step(0.024, 0.060, active_after=1, queued=0)  # queued
        assert tr.total_decode_gap == 0.0
        # after a drain the next program starts late: that is a stall
        tr.on_decode_step(0.065, 0.085, active_after=1, queued=0)
        assert tr.total_decode_gap == pytest.approx(0.005)

    def test_chrome_export_lanes_and_merge(self, tmp_path):
        clk = obs.FakeClock(tick=0.001)
        tr = ServeTracer("t3", clk, max_slots=2)
        req = self._req(clk)
        tr.on_submit(req)
        tr.on_admit(req, 1, resumed=False)
        req.slot = 1
        tr.on_prefill(req, bucket=8, tokens=4)
        tr.on_decode_begin(req)
        req.finish_time = clk.time()
        tr.on_finish(req)
        tr.on_decode_step(clk.time(), clk.time(), active_after=0, queued=0)
        d = tr.chrome_trace_dict()
        assert set(d) == {"traceEvents", "displayTimeUnit"}
        xs = [e for e in d["traceEvents"] if e["ph"] == "X"]
        # queue on the wait lane 0, prefill/decode on slot lane 2,
        # decode_step on the engine lane above every slot
        by_name = {e["name"]: e["tid"] for e in xs}
        assert by_name["queue"] == 0
        assert by_name["prefill"] == 2 and by_name["decode"] == 2
        assert by_name["decode_step"] == 3
        names = {(e.get("tid"), e["args"]["name"])
                 for e in d["traceEvents"] if e["ph"] == "M"
                 and e["name"] == "thread_name"}
        assert (0, "queue/preempt wait") in names
        assert (2, "slot 1") in names
        # merges like any other rank trace (fleet plane compatibility)
        from paddle_tpu.observability.fleet import merge_chrome_trace_files

        p = tmp_path / "serve_chrome.json"
        tr.write_chrome_trace(str(p))
        merged_path = tmp_path / "merged.json"
        merged = merge_chrome_trace_files({0: str(p)},
                                          path=str(merged_path))
        assert len(merged["traceEvents"]) >= len(xs)
        assert all(e["pid"] == 0 for e in merged["traceEvents"])
        assert json.loads(merged_path.read_text())["traceEvents"]

    def test_malformed_hooks_are_counted_not_raised(self):
        clk = obs.FakeClock(tick=0.001)
        tr = ServeTracer("t4", clk)
        req = self._req(clk)
        tr.on_submit(req)
        # finish with the queue phase still open and no finish_time:
        # the doc is recorded, flagged PTL403, never raises
        req.finish_time = None
        tr.on_finish(req)
        (doc,) = list(tr.requests)
        assert doc["malformed"]
        assert obs.registry.get("trace.spans_malformed").value(
            engine="t4", reason="root_open") >= 1


class TestTailExemplars:
    def _doc(self, rid, ttft, latency):
        return {"id": rid, "ttft_seconds": ttft,
                "latency_seconds": latency, "preemptions": 0,
                "ttft_breakdown": {"queue": ttft},
                "breakdown": {"decode": latency}}

    def test_keeps_n_worst_sorted(self):
        ex = TailExemplars(2, engine="ex1")
        for rid, t in enumerate([0.1, 0.5, 0.3, 0.9]):
            ex.offer(self._doc(rid, t, t * 2))
        assert [d["id"] for d in ex.worst_ttft] == [3, 1]
        assert [d["id"] for d in ex.worst_latency] == [3, 1]
        assert obs.registry.get("trace.exemplars_kept").value(
            engine="ex1", kind="ttft") == 2
        text = ex.render()
        assert "worst TTFT" in text and "req 3" in text

    def test_unmeasured_requests_are_skipped(self):
        ex = TailExemplars(2, engine="ex2")
        ex.offer({"id": 9, "ttft_seconds": None, "latency_seconds": None})
        assert not ex.worst_ttft and not ex.worst_latency


class TestSloMonitor:
    def _rules(self, **over):
        base = dict(name="ttft", kind="ttft_p99", threshold=0.1,
                    window_seconds=100.0, min_samples=3)
        base.update(over)
        return [base]

    def test_parse_rules_json_file_and_env(self, tmp_path, monkeypatch):
        inline = '[{"name": "a", "kind": "ttft_p99", "threshold": 0.2}]'
        (r,) = slo_mod.parse_rules(inline)
        assert r.name == "a" and r.bound == "max"
        p = tmp_path / "rules.json"
        p.write_text(inline)
        assert slo_mod.parse_rules(str(p))[0].name == "a"
        monkeypatch.setenv(slo_mod.SLO_ENV, inline)
        assert slo_mod.rules_from_env()[0].name == "a"
        monkeypatch.delenv(slo_mod.SLO_ENV)
        assert slo_mod.rules_from_env() == []
        with pytest.raises(ValueError, match="unknown kind"):
            slo_mod.parse_rules([dict(name="x", kind="p95_vibes",
                                      threshold=1.0)])
        # tokens_per_sec defaults to a FLOOR
        (tps,) = slo_mod.parse_rules([dict(
            name="tps", kind="tokens_per_sec", threshold=10.0)])
        assert tps.bound == "min"

    def test_breach_latches_once_per_excursion(self):
        clk = obs.FakeClock()
        m = slo_mod.SloMonitor(self._rules(), engine="slo1", clock=clk)
        for _ in range(3):
            m.observe_ttft(0.5, now=clk.time())
        fired = m.on_step(tokens=5, now=clk.time())
        assert [b["rule"] for b in fired] == ["ttft"]
        # still out of bounds: same excursion, no second increment
        assert m.on_step(tokens=5, now=clk.time()) == []
        assert obs.registry.get("trace.slo_breaches").value(
            engine="slo1", rule="ttft") == 1
        assert _codes(m.report) == ["PTL401"]
        # recovery re-arms: a fresh excursion fires again
        m._ttfts.clear()
        for _ in range(3):
            m.observe_ttft(0.01, now=clk.time())
        assert m.on_step(now=clk.time()) == []
        for _ in range(3):
            m.observe_ttft(0.7, now=clk.time())
        assert [b["rule"] for b in m.on_step(now=clk.time())] == ["ttft"]
        assert obs.registry.get("trace.slo_breaches").value(
            engine="slo1", rule="ttft") == 2

    def test_min_samples_withholds_judgement(self):
        clk = obs.FakeClock()
        m = slo_mod.SloMonitor(self._rules(), engine="slo2", clock=clk)
        m.observe_ttft(9.0, now=clk.time())
        m.observe_ttft(9.0, now=clk.time())
        assert m.on_step(now=clk.time()) == []        # 2 < min_samples

    def test_tokens_per_sec_floor_and_pool_rate(self):
        clk = obs.FakeClock(tick=0.01)
        rules = [dict(name="tps", kind="tokens_per_sec", threshold=1e6,
                      window_seconds=100.0),
                 dict(name="pool", kind="pool_exhaustion_rate",
                      threshold=0.5, window_seconds=100.0)]
        m = slo_mod.SloMonitor(rules, engine="slo3", clock=clk)
        fired = []
        for _ in range(4):
            fired += m.on_step(tokens=3, preemptions=1, now=clk.time())
        assert {b["rule"] for b in fired} == {"tps", "pool"}
        tps = next(b for b in fired if b["rule"] == "tps")
        assert tps["bound"] == "min" and tps["value"] < 1e6
        assert tps["rule_kind"] == "tokens_per_sec"

    def test_breach_dump_carries_exemplars(self, tmp_path, monkeypatch):
        monkeypatch.setenv(obs.flight.FLIGHT_DIR_ENV, str(tmp_path))
        clk = obs.FakeClock()
        ex = TailExemplars(2, engine="slo4")
        ex.offer({"id": 1, "ttft_seconds": 0.4, "latency_seconds": 0.8,
                  "preemptions": 2, "ttft_breakdown": {"queue": 0.4},
                  "breakdown": {"decode": 0.8}})
        m = slo_mod.SloMonitor(self._rules(), engine="slo4", clock=clk,
                               exemplars=ex)
        for _ in range(3):
            m.observe_ttft(0.4, now=clk.time())
        assert m.on_step(now=clk.time())
        (p,) = sorted(tmp_path.glob("flight-*.json"))
        doc = json.loads(p.read_text())
        assert doc["reason"] == slo_mod.flight.REASON_SLO_BREACH
        assert doc["context"]["rule"] == "ttft"
        assert doc["context"]["exemplars"]["worst_ttft"][0]["id"] == 1


class TestServeTraceLint:
    """PTL404 decode-burst gaps + PTL405 preemption thrash off the
    serve_trace dump."""

    def _dump(self, steps=(), requests=()):
        return {"kind": "serve_trace", "version": 1, "engine": "lint",
                "requests_traced": len(requests),
                "decode_gap_seconds": 0.0,
                "requests": list(requests), "decode_steps": list(steps),
                "exemplars": {}}

    def _steps(self, n, dur=0.002, gap=0.0005, active=1):
        out, t = [], 0.0
        for _ in range(n):
            out.append({"start": t, "end": t + dur, "active": active,
                        "queued": 0})
            t += dur + gap
        return out

    def test_healthy_trace_is_clean(self):
        report = lint_serve_trace(self._dump(steps=self._steps(20)))
        assert not report.diagnostics

    def test_gap_with_runnable_slots_is_ptl404(self):
        steps = self._steps(5)
        stalled = dict(steps[-1])
        stalled["start"] = steps[-1]["end"] + 0.05     # 50 ms stall
        stalled["end"] = stalled["start"] + 0.002
        report = lint_serve_trace(self._dump(steps=steps + [stalled]))
        assert _codes(report) == ["PTL404"]
        (d,) = list(report)
        assert d.suggestion["gap_seconds"] == pytest.approx(0.05, rel=0.1)

    def test_overlapped_steps_are_not_flagged(self):
        # each step dispatched 4 ms into the one before, read 20 ms on:
        # negative gaps, however long the steps
        steps = [{"start": 0.020 * i, "end": 0.020 * i + 0.036,
                  "active": 2, "queued": 0} for i in range(12)]
        assert not lint_serve_trace(self._dump(steps=steps)).diagnostics
        # ... and a hole of most of a second after a drain still is
        late = [dict(s, start=s["start"] + 1.0, end=s["end"] + 1.0)
                for s in steps[:3]]
        report = lint_serve_trace(self._dump(steps=steps + late))
        assert _codes(report) == ["PTL404"]

    def test_gap_while_drained_is_not_flagged(self):
        steps = self._steps(5)
        steps[-1]["active"] = 0        # everyone finished: idle != stall
        stalled = {"start": steps[-1]["end"] + 5.0,
                   "end": steps[-1]["end"] + 5.002, "active": 1,
                   "queued": 0}
        report = lint_serve_trace(self._dump(steps=steps + [stalled]))
        assert not report.diagnostics

    def test_systemic_stall_is_truncated_with_note(self):
        # a gap after EVERY step: findings cap at 8 + one NOTE
        steps = self._steps(20, gap=0.06)
        report = lint_serve_trace(self._dump(steps=steps))
        warns = [d for d in report if d.severity.name == "WARNING"]
        notes = [d for d in report if d.severity.name == "NOTE"]
        assert len(warns) == 8 and len(notes) == 1
        assert notes[0].suggestion["suppressed"] == 19 - 8

    def test_preemption_thrash_is_ptl405(self):
        reqs = [{"id": 5, "preemptions": 4,
                 "breakdown": {"recompute": 0.12}},
                {"id": 6, "preemptions": 1, "breakdown": {}}]
        report = lint_serve_trace(self._dump(requests=reqs), thrash_k=3)
        assert _codes(report) == ["PTL405"]
        (d,) = list(report)
        assert d.suggestion == {"request": 5, "preemptions": 4}
        assert "recompute" in d.message

    def test_wrong_kind_raises(self):
        with pytest.raises(ValueError, match="serve_trace"):
            lint_serve_trace({"kind": "fleet_trace"})
        assert SERVE_TRACE_LINT_CODES == ("PTL404", "PTL405")


class TestRendering:
    def test_phase_table_and_serve_trace_render(self):
        docs = [{"id": i, "latency_seconds": 0.4,
                 "breakdown": {"queue": 0.1, "decode": 0.3}}
                for i in range(4)]
        table = render_phase_table(docs)
        assert "queue" in table and "p99 ms" in table and "share" in table
        dump = {"kind": "serve_trace", "engine": "r1",
                "requests_traced": 4, "decode_gap_seconds": 0.01,
                "requests": docs, "decode_steps": [],
                "exemplars": {"n": 2, "worst_ttft": [],
                              "worst_latency": []}}
        out = render_serve_trace(dump)
        assert "engine=r1" in out and "tail exemplars" in out
        with pytest.raises(ValueError, match="serve_trace"):
            render_serve_trace({"kind": "metrics"})

    def test_trace_env_gate(self, monkeypatch):
        for off in ("", "0", "false", "no", "off"):
            monkeypatch.setenv(tr_mod.TRACE_ENV, off)
            assert not tr_mod.trace_enabled_from_env()
        monkeypatch.setenv(tr_mod.TRACE_ENV, "1")
        assert tr_mod.trace_enabled_from_env()
