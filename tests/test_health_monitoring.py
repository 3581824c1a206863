"""Tests: continuous health monitoring — SeriesRecorder sampling
semantics (counter deltas, gauge levels, histogram window quantiles,
ring eviction), HealthMonitor detectors under FakeClock (drift PTL601,
leak PTL602, rate PTL603, malformed input PTL604, latch/re-arm), fleet
ship-and-merge lanes, bench_compare regression gating (PTL605), the
end-to-end creep drill, and solo equivalence (no ``health.``/``ts.``
footprint when monitoring is off).

Every clock in here is an ``obs.FakeClock`` — no wall-clock sleeps."""
import importlib.util
import json
import math
import os

import pytest

import paddle_tpu.observability as obs
from paddle_tpu.core import flags
from paddle_tpu.observability import fleet, health
from paddle_tpu.observability.timeseries import (SeriesRecorder,
                                                merge_timeseries)

REPO_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_{name}", os.path.join(REPO_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def obs_on():
    health.install(None)
    obs.reset()
    obs.enable()
    yield
    health.install(None)
    obs.disable()
    obs.reset()


@pytest.fixture
def flight_dir(tmp_path, monkeypatch):
    d = tmp_path / "flight"
    monkeypatch.setenv(obs.flight.FLIGHT_DIR_ENV, str(d))
    yield d


class TestSeriesRecorder:
    def test_counter_sampled_as_deltas_after_baseline(self, obs_on):
        c = obs.registry.counter("test.ts_requests", "probe")
        rec = SeriesRecorder(capacity=8, clock=obs.FakeClock(),
                             tracked=("test.ts_requests",))
        c.inc(3)
        rec.sample(now=0.0)     # baseline: the lifetime total is NOT
        assert rec.values("test.ts_requests") == []  # a window delta
        c.inc(2)
        rec.sample(now=1.0)
        c.inc(5)
        rec.sample(now=2.0)
        assert rec.window("test.ts_requests") == [(1.0, 2), (2.0, 5)]

    def test_gauge_sampled_as_level_max_across_labelsets(self, obs_on):
        g = obs.registry.gauge("test.ts_occupancy", "probe")
        g.set(10.0, pool="a")
        g.set(30.0, pool="b")
        rec = SeriesRecorder(capacity=8, clock=obs.FakeClock(),
                             tracked=("test.ts_occupancy",))
        rec.sample(now=0.0)
        rec.sample(now=1.0)     # levels repeat; no delta semantics
        assert rec.values("test.ts_occupancy") == [30.0, 30.0]

    def test_histogram_sampled_as_window_mean_and_p90(self, obs_on):
        h = obs.registry.histogram("test.ts_latency", "probe",
                                   buckets=(0.1, 0.2, 0.3))
        rec = SeriesRecorder(capacity=8, clock=obs.FakeClock(),
                             tracked=("test.ts_latency",))
        rec.sample(now=0.0)     # baseline with zero observations
        h.observe(0.1)
        h.observe(0.2)
        rec.sample(now=1.0)
        # window mean under the metric's own name...
        assert rec.values("test.ts_latency") == \
            [pytest.approx(0.15)]
        # ...and the interpolated window p90 under <name>.p90:
        # 2 obs, rank 1.8 lands 0.8 into the (0.1, 0.2] bucket
        assert rec.values("test.ts_latency.p90") == \
            [pytest.approx(0.18)]
        rec.sample(now=2.0)     # empty window: nothing recorded
        assert len(rec.values("test.ts_latency")) == 1

    def test_ring_evicts_at_the_flag_capacity(self, obs_on):
        orig = flags.get_flag("observability_ts_points")
        try:
            flags.set_flags({"FLAGS_observability_ts_points": 4})
            rec = SeriesRecorder(clock=obs.FakeClock())
            assert rec.capacity == 4
            for i in range(10):
                rec.record("test.ring", float(i), t=float(i))
            assert rec.values("test.ring") == [6.0, 7.0, 8.0, 9.0]
        finally:
            flags.set_flags({"FLAGS_observability_ts_points": orig})

    def test_points_counter_labeled_by_series(self, obs_on):
        rec = SeriesRecorder(capacity=8, clock=obs.FakeClock())
        for i in range(3):
            rec.record("test.ring", float(i), t=float(i))
        m = obs.registry.get("ts.points_recorded")
        assert m.value(series="test.ring") == 3

    def test_sample_probes_host_ring_lengths(self, obs_on):
        rec = SeriesRecorder(capacity=8, clock=obs.FakeClock(),
                             tracked=())
        obs.emit("probe.event")
        rec.sample(now=0.0)
        assert rec.values("host.events_ring_len") == [1]
        assert "host.flight_ring_len" in rec.names()


def _monitor(rules, clk):
    """A monitor over a manually-driven recorder (tracked=() so
    ``sample()`` only adds the host probes, never our test series)."""
    return health.HealthMonitor(
        rules, recorder=SeriesRecorder(capacity=32, clock=clk,
                                       tracked=()))


class TestDetectors:
    def test_stationary_series_stays_quiet(self, obs_on):
        clk = obs.FakeClock()
        mon = _monitor([health.HealthRule("d", "drift", "test.step")],
                       clk)
        for i in range(20):
            mon.recorder.record("test.step", 0.1, t=float(i))
            assert mon.on_step(now=float(i)) == []
        assert mon.alerts == []
        assert len(mon.report) == 0

    def test_drift_fires_ptl601_once(self, obs_on):
        clk = obs.FakeClock()
        mon = _monitor([health.HealthRule("d", "drift", "test.step")],
                       clk)
        fired = []
        for i in range(20):
            v = 0.1 if i < 12 else 0.2   # +100% step-time excursion
            mon.recorder.record("test.step", v, t=float(i))
            fired += mon.on_step(now=float(i))
        assert [f["code"] for f in fired] == ["PTL601"]
        assert fired[0]["rule"] == "d"
        assert fired[0]["rule_kind"] == "drift"
        m = obs.registry.get("health.alerts")
        assert m.value(rule="d", series="test.step") == 1
        assert mon.report.codes() == {"PTL601"}

    def test_down_drift_uses_ptl603(self, obs_on):
        # throughput going DOWN is the bad direction for */sec series
        rule = health.HealthRule("tps", "drift", "test.tps",
                                 direction="down")
        assert rule.code == "PTL603"
        clk = obs.FakeClock()
        mon = _monitor([rule], clk)
        fired = []
        for i in range(20):
            v = 1000.0 if i < 12 else 500.0
            mon.recorder.record("test.tps", v, t=float(i))
            fired += mon.on_step(now=float(i))
        assert [f["code"] for f in fired] == ["PTL603"]

    def test_leak_fires_ptl602_sawtooth_stays_quiet(self, obs_on):
        clk = obs.FakeClock()
        mon = _monitor(
            [health.HealthRule("leak", "leak", "test.watermark")], clk)
        # sawtooth: grows then FREES — an allocator doing its job
        for i, v in enumerate([100, 150, 200, 120, 180, 240, 130, 190,
                               250, 140]):
            mon.recorder.record("test.watermark", float(v), t=float(i))
            assert mon.on_step(now=float(i)) == []
        mon2 = _monitor(
            [health.HealthRule("leak", "leak", "test.watermark")], clk)
        fired = []
        for i in range(10):   # monotonic: never freed once
            mon2.recorder.record("test.watermark", 100.0 + 20 * i,
                                 t=float(i))
            fired += mon2.on_step(now=float(i))
        assert [f["code"] for f in fired] == ["PTL602"]
        # fires at min_points=8: monotonic 100 -> 240 is +140%
        assert fired[0]["growth_pct"] == pytest.approx(140.0)

    def test_rate_alarm_fires_ptl603_on_windowed_sum(self, obs_on):
        clk = obs.FakeClock()
        mon = _monitor([health.HealthRule(
            "lost", "rate", "test.lost", threshold=5.0,
            window_points=8)], clk)
        fired = []
        for i in range(6):    # per-step deltas of 1: sum crosses 5
            mon.recorder.record("test.lost", 1.0, t=float(i))
            fired += mon.on_step(now=float(i))
        assert [f["code"] for f in fired] == ["PTL603"]
        assert fired[0]["value"] == 5.0

    def test_malformed_series_files_ptl604_once(self, obs_on):
        clk = obs.FakeClock()
        mon = _monitor([health.HealthRule("d", "drift", "test.nan")],
                       clk)
        for i in range(10):
            mon.recorder.record("test.nan", 0.1, t=float(i))
        mon.recorder.record("test.nan", float("nan"), t=10.0)
        assert mon.on_step(now=10.0) == []   # PTL604 is a report, not
        assert mon.on_step(now=11.0) == []   # an alert — and only once
        assert [d.code for d in mon.report] == ["PTL604"]
        assert mon.alerts == []

    def test_latch_fires_once_per_excursion_and_rearms(self, obs_on):
        clk = obs.FakeClock()
        mon = health.HealthMonitor(
            [health.HealthRule("leak", "leak", "test.ring",
                               min_points=4, min_growth_pct=10.0)],
            recorder=SeriesRecorder(capacity=4, clock=clk, tracked=()))
        t = [0.0]

        def step(v):
            mon.recorder.record("test.ring", float(v), t=t[0])
            out = mon.on_step(now=t[0])
            t[0] += 1.0
            return out

        fired = []
        for v in (1, 2, 3, 4):     # first excursion: fires once
            fired += step(v)
        assert len(fired) == 1
        for v in (5, 6):           # still breaching: latched, silent
            assert step(v) == []
        assert step(3) == []       # recovery (a free): re-arms
        for v in (4, 5, 6):        # ring forgets the dip -> new
            fired += step(v)       # monotonic excursion fires again
        assert len(fired) == 2
        assert [f["code"] for f in fired] == ["PTL602", "PTL602"]

    def test_alert_dumps_flight_with_window(self, obs_on, flight_dir):
        clk = obs.FakeClock()
        mon = _monitor(
            [health.HealthRule("leak", "leak", "test.watermark")], clk)
        for i in range(10):
            mon.recorder.record("test.watermark", 100.0 + 20 * i,
                                t=float(i))
            mon.on_step(now=float(i))
        dumps = sorted(flight_dir.glob("flight-*.json"))
        assert len(dumps) == 1
        d = json.loads(dumps[0].read_text())
        assert d["reason"] == "health_alert"
        ctx = d["context"]
        assert ctx["code"] == "PTL602" and ctx["rule"] == "leak"
        # the post-mortem shows the trajectory, not just the trip:
        # the window as it stood when the rule fired (min_points=8)
        assert ctx["window"][0] == [0.0, 100.0]
        assert ctx["window"][-1] == [7.0, 240.0]


class TestFleetShipAndMerge:
    def test_snapshot_ships_series_and_merge_builds_lanes(self, obs_on):
        clk = obs.FakeClock()
        mon = health.install(_monitor([], clk))
        mon.recorder.record("train.step_seconds", 0.1, t=1.0)
        snap0 = fleet.snapshot_dict(0, 2)
        assert snap0["timeseries"]["series"]["train.step_seconds"] == \
            [[1.0, 0.1]]
        snap1 = {"rank": 1, "timeseries":
                 {"series": {"train.step_seconds": [[1.5, 0.3]]}}}
        merged = merge_timeseries([snap0, snap1])
        lanes = merged["train.step_seconds"]["lanes"]
        # ranks stay separate: a sick rank must not average away
        assert lanes["0"] == [[1.0, 0.1]]
        assert lanes["1"] == [[1.5, 0.3]]

    def test_snapshot_without_monitor_ships_none(self, obs_on):
        assert fleet.snapshot_dict(0, 1)["timeseries"] is None


class TestBenchCompare:
    def _write(self, tmp_path, name, rows):
        p = tmp_path / name
        p.write_text(json.dumps(rows))
        return str(p)

    def test_driver_record_pair_within_noise_passes(self, tmp_path,
                                                    capsys):
        """Two records in the driver's format — metric lines riding a
        truncated ``tail`` between log lines, a cell moving inside the
        noise band, a cell that only the newer record has — pass."""
        bc = _load_tool("bench_compare")

        def record(n, rows):
            lines = ['e": {"truncated head of an earlier line"}}']
            for metric, value, unit in rows:
                lines.append(json.dumps(
                    {"metric": metric, "value": value, "unit": unit,
                     "vs_baseline": 1.0}))
                lines.append("WARNING:2026-01-01 00:00:00,000:jax: a log "
                             "line between the metric lines")
            return self._write(tmp_path, f"BENCH_r{n:02d}.json", {
                "n": n, "cmd": "python bench.py", "rc": 0,
                "tail": "\n".join(lines)})

        old = record(4, [
            ("resnet50 train images/sec/chip (bs=256)", 2129.8,
             "images/sec/chip"),
            ("moe-474M step time (bs=4 seq=4096)", 165.8, "ms/step"),
            ("llama-645M pretrain tokens/sec/chip (bs=4)", 31386.0,
             "tokens/sec/chip")])
        new = record(5, [
            ("resnet50 train images/sec/chip (bs=256)", 2087.8,
             "images/sec/chip"),                          # -2.0%: noise
            ("moe-474M step time (bs=4 seq=4096)", 165.9, "ms/step"),
            ("decode-645M greedy tokens/sec/chip (bs=8)", 3995.0,
             "tokens/sec/chip"),                          # new cell
            ("llama-645M pretrain tokens/sec/chip (bs=4)", 31389.5,
             "tokens/sec/chip")])
        assert bc.latest_bench_records(str(tmp_path)) == [old, new]
        assert bc.main([old, new]) == 0
        assert "0 regression(s)" in capsys.readouterr().out

    def test_constructed_regression_exits_nonzero(self, tmp_path,
                                                  capsys):
        bc = _load_tool("bench_compare")
        base = self._write(tmp_path, "base.json", [
            {"metric": "bert-base tokens/sec/chip", "value": 100.0,
             "unit": "tokens/sec/chip"}])
        cur = self._write(tmp_path, "cur.json", [
            {"metric": "bert-base tokens/sec/chip", "value": 80.0,
             "unit": "tokens/sec/chip"}])
        assert bc.main([base, cur]) == 1
        out = capsys.readouterr().out
        assert "PTL605" in out and "-20.00%" in out

    def test_lower_is_better_direction_from_unit(self):
        bc = _load_tool("bench_compare")
        rows = bc.compare_docs(
            [{"metric": "llama ms/step", "value": 100.0,
              "unit": "ms/step"}],
            [{"metric": "llama ms/step", "value": 120.0,
              "unit": "ms/step"}])
        assert rows[0]["direction"] == "lower"
        assert rows[0]["status"] == "regressed"
        report = bc.regression_report(rows)
        assert [d.code for d in report] == ["PTL605"]

    def test_noise_band_and_dropped_config_do_not_fail(self):
        bc = _load_tool("bench_compare")
        rows = bc.compare_docs(
            [{"metric": "a x/sec", "value": 100.0, "unit": "x/sec"},
             {"metric": "b x/sec", "value": 100.0, "unit": "x/sec"}],
            [{"metric": "a x/sec", "value": 97.0, "unit": "x/sec"}])
        by = {r["config"]: r["status"] for r in rows}
        assert by == {"a": "ok", "b": "dropped"}  # -3% is jitter
        assert len(bc.regression_report(rows)) == 0

    def test_missing_baseline_passes(self, tmp_path, capsys):
        bc = _load_tool("bench_compare")
        cur = self._write(tmp_path, "cur.json", [
            {"metric": "a x/sec", "value": 1.0, "unit": "x/sec"}])
        assert bc.main([str(tmp_path / "nope.json"), cur]) == 0
        assert bc.main([cur, str(tmp_path / "nope.json")]) == 2


class TestEndToEndDrill:
    def test_creep_drill_fires_drift_and_leak(self, obs_on,
                                              flight_dir):
        # the whole loop on a FakeClock: stationary 0.1 s/step for 20
        # steps, then a creeping slowdown, while the kv pool leaks
        clk = obs.FakeClock()
        health.install(health.HealthMonitor(
            health.default_rules(),
            recorder=SeriesRecorder(capacity=64, clock=clk)))
        # the canonical definition site — registry.gauge() here would
        # register a second one and trip the lint's claim audit
        from paddle_tpu.serve.engine import _M_POOL_OCCUPANCY as pool
        for step in range(40):
            with obs.step_region("train", step=step, clock=clk):
                clk.advance(0.1 if step < 20
                            else 0.1 + 0.02 * (step - 20))
                pool.set(100.0 + 10.0 * step)
        mon = health.active_monitor()
        codes = {a["code"] for a in mon.alerts}
        assert {"PTL601", "PTL602"} <= codes
        rules = {a["rule"] for a in mon.alerts}
        assert {"step_time_drift", "kv_pool_leak"} <= rules
        assert obs.registry.get("health.alerts").total() >= 2
        # every alert left a windowed post-mortem
        dumps = [json.loads(p.read_text())
                 for p in sorted(flight_dir.glob("flight-*.json"))]
        reasons = {d["reason"] for d in dumps}
        assert reasons == {"health_alert"}
        assert all(len(d["context"]["window"]) >= 8 for d in dumps)
        # the dump renders with sparklines + the offending window
        out = obs.render_health(obs.dump_dict())
        assert "train.step_seconds" in out
        assert any(ch in out for ch in obs.report.SPARK_CHARS[1:])
        assert "health.alerts" in out
        flight_doc = next(d for d in dumps
                          if d["context"]["code"] == "PTL601")
        fout = obs.render_flight(flight_doc)
        assert "Offending window" in fout

    def test_metrics_report_health_renders_directory(self, obs_on,
                                                     flight_dir,
                                                     capsys):
        clk = obs.FakeClock()
        mon = health.install(_monitor(
            [health.HealthRule("leak", "leak", "test.watermark")],
            clk))
        for i in range(10):
            mon.recorder.record("test.watermark", 100.0 + 20 * i,
                                t=float(i))
            mon.on_step(now=float(i))
        mr = _load_tool("metrics_report")
        assert mr.main(["--health", str(flight_dir)]) == 0
        out = capsys.readouterr().out
        assert "HEALTH ALERT" in out and "test.watermark" in out

    def test_solo_equivalence_when_health_off(self, obs_on):
        def run(with_monitor):
            health.install(None)
            obs.reset()
            if with_monitor:
                health.install(health.HealthMonitor(
                    health.default_rules(),
                    recorder=SeriesRecorder(capacity=64,
                                            clock=obs.FakeClock())))
            clk = obs.FakeClock()
            for step in range(10):
                with obs.step_region("train", step=step, clock=clk):
                    clk.advance(0.1)   # stationary: no alerts
            d = obs.dump_dict()
            health.install(None)
            return d

        d_off, d_on = run(False), run(True)
        # off: no history keys, and the health./ts. series stay EMPTY
        assert "timeseries" not in d_off
        assert "health_alerts" not in d_off
        for name, m in d_off["metrics"].items():
            if name.startswith(("health.", "ts.")):
                assert m["series"] == [], name
        # on: history rides extra keys; everything else is identical
        assert d_on["timeseries"]["series"]
        assert d_on["health_alerts"] == []

        def strip(d):
            return {n: m for n, m in d["metrics"].items()
                    if not n.startswith(("health.", "ts."))}

        assert strip(d_off) == strip(d_on)
