"""Tests: profiler subsystem (SURVEY §5.1) + device management."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as profiler
from paddle_tpu.profiler import (Profiler, ProfilerState, ProfilerTarget,
                                 RecordEvent, make_scheduler)


class TestScheduler:
    def test_make_scheduler_windows(self):
        sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                               skip_first=1)
        states = [sched(i) for i in range(6)]
        assert states[0] == ProfilerState.CLOSED          # skip_first
        assert states[1] == ProfilerState.CLOSED          # closed
        assert states[2] == ProfilerState.READY
        assert states[3] == ProfilerState.RECORD
        assert states[4] == ProfilerState.RECORD_AND_RETURN
        assert states[5] == ProfilerState.CLOSED          # repeat exhausted

    def test_default_always_record(self):
        p = Profiler(targets=[ProfilerTarget.CPU])
        assert p._scheduler(0) == ProfilerState.RECORD
        assert p._scheduler(100) == ProfilerState.RECORD


class TestRecordEvent:
    def test_nested_spans_and_summary(self):
        p = Profiler(targets=[ProfilerTarget.CPU])
        p.start()
        with RecordEvent("outer"):
            with RecordEvent("inner"):
                _ = (paddle.ones([8, 8]) * 2).numpy()
        p.stop()
        names = [e.name for e in _flatten(p._events)]
        assert "outer" in names and "inner" in names
        table = p.get_summary()
        assert "outer" in table and "Calls" in table

    def test_decorator(self):
        @RecordEvent("decorated_fn")
        def f(x):
            return x + 1

        p = Profiler(targets=[ProfilerTarget.CPU])
        p.start()
        assert f(1) == 2
        p.stop()
        assert any(e.name == "decorated_fn" for e in _flatten(p._events))

    def test_chrome_export(self, tmp_path):
        p = Profiler(targets=[ProfilerTarget.CPU])
        p.start()
        with RecordEvent("span"):
            pass
        p.stop()
        path = str(tmp_path / "trace.json")
        p.export(path)
        data = profiler.load_profiler_result(path)
        assert any(ev["name"] == "span" for ev in data["traceEvents"])

    def test_scheduled_steps_with_on_trace_ready(self, tmp_path):
        done = []
        p = Profiler(targets=[ProfilerTarget.CPU],
                     scheduler=make_scheduler(closed=1, ready=0, record=2,
                                              repeat=1),
                     on_trace_ready=lambda prof: done.append(prof.step_num))
        p.start()
        for _ in range(5):
            with RecordEvent("work"):
                pass
            p.step()
        p.stop()
        assert done  # trace-ready fired when the record window closed

    def test_back_to_back_record_windows(self):
        # closed=0/ready=0/repeat=3: every period ends in RECORD_AND_RETURN
        # and must fire on_trace_ready once per window, not once at the end
        fired = []
        p = Profiler(targets=[ProfilerTarget.CPU],
                     scheduler=make_scheduler(closed=0, ready=0, record=2,
                                              repeat=3),
                     on_trace_ready=lambda prof: fired.append(prof._span_idx))
        p.start()
        for _ in range(6):
            with RecordEvent("w"):
                pass
            p.step()
        p.stop()
        assert len(fired) == 3
        assert fired == [0, 1, 2]

    def test_stop_bumps_span_idx(self, tmp_path):
        p = Profiler(targets=[ProfilerTarget.CPU],
                     on_trace_ready=profiler.export_chrome_tracing(
                         str(tmp_path), worker_name="w"))
        for _ in range(2):
            p.start()
            with RecordEvent("s"):
                pass
            p.stop()
        assert sorted(os.listdir(tmp_path)) == ["w_time_0.json",
                                                "w_time_1.json"]

    def test_timer_only_step_info(self):
        p = Profiler(timer_only=True)
        p.start()
        for _ in range(3):
            p.step(num_samples=4)
        info = p.step_info()
        p.stop()
        assert "avg_batch_cost" in info


class TestDevice:
    def test_device_queries(self):
        import paddle_tpu.device as device
        types = device.get_all_device_type()
        assert "cpu" in types
        assert device.get_available_device()
        device.synchronize()

    def test_memory_stats(self):
        import paddle_tpu.device as device
        _ = paddle.ones([64, 64]).numpy()
        stats = device.memory_stats()
        assert isinstance(stats, dict)
        assert device.memory_allocated() >= 0
        assert device.max_memory_allocated() >= device.memory_allocated() or \
            device.max_memory_allocated() == 0

    def test_stream_event_ordering(self):
        import paddle_tpu.device as device
        s = device.Stream()
        x = paddle.ones([32, 32])
        y = x.matmul(x)
        s.track(y._value)
        ev = s.record_event()
        ev.synchronize()
        assert ev.query()
        s.synchronize()
        assert s.query()

    def test_stream_guard(self):
        import paddle_tpu.device as device
        s = device.Stream()
        with device.stream_guard(s) as cur:
            assert device.current_stream() is s
        assert device.current_stream() is not s


from paddle_tpu.profiler.host_tracer import flatten_events as _flatten  # noqa: E402


def test_bench_profile_writes_trace(tmp_path):
    """bench.py --profile produces a parseable chrome trace (VERDICT item
    10: profiler smoke on the bench path)."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "/root/repo"
    out = subprocess.run(
        [sys.executable, "/root/repo/bench.py", "--config", "llama",
         "--profile", "--steps", "2"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    metrics = [json.loads(l) for l in lines]
    assert any("tokens/sec" in m.get("unit", "") for m in metrics)
    trace = tmp_path / "bench_trace.json"
    assert trace.exists()
    json.loads(trace.read_text())  # valid chrome trace JSON


class TestXplaneParser:
    """profiler/xplane.py: hand-rolled XSpace wire decoder used to merge
    XLA device events into the exported chrome trace."""

    @staticmethod
    def _varint(v):
        out = b""
        while True:
            b7 = v & 0x7F
            v >>= 7
            out += bytes([b7 | (0x80 if v else 0)])
            if not v:
                return out

    @classmethod
    def _field(cls, num, wt, payload):
        key = cls._varint((num << 3) | wt)
        if wt == 0:
            return key + cls._varint(payload)
        return key + cls._varint(len(payload)) + payload

    def test_decodes_device_plane_events(self):
        from paddle_tpu.profiler.xplane import parse_xspace

        f = self._field
        # XEventMetadata {id=7, name="fusion.3"}
        md = f(1, 0, 7) + f(2, 2, b"fusion.3")
        # map entry {key=7, value=md}
        entry = f(1, 0, 7) + f(2, 2, md)
        # XEvent {metadata_id=7, offset_ps=2_000_000, duration_ps=5_000_000}
        ev = f(1, 0, 7) + f(2, 0, 2_000_000) + f(3, 0, 5_000_000)
        # XLine {name="XLA Ops", timestamp_ns=1000, events=[ev]}
        line = f(2, 2, b"XLA Ops") + f(3, 0, 1000) + f(4, 2, ev)
        # XPlane {id=1, name="/device:TPU:0", lines=[line], event_metadata}
        plane = f(1, 0, 1) + f(2, 2, b"/device:TPU:0") + \
            f(3, 2, line) + f(4, 2, entry)
        space = f(1, 2, plane)

        evs = parse_xspace(space)
        assert len(evs) == 1
        e = evs[0]
        assert e["name"] == "fusion.3"
        assert e["cat"] == "device"
        assert e["pid"] == "/device:TPU:0"
        assert e["tid"] == "XLA Ops"
        # ts us = (1000ns + 2_000_000ps/1e3) / 1e3 = 3.0; dur us = 5.0
        assert abs(e["ts"] - 3.0) < 1e-9
        assert abs(e["dur"] - 5.0) < 1e-9

    def test_unknown_and_empty_input(self):
        from paddle_tpu.profiler.xplane import (
            device_trace_events, parse_xspace,
        )

        assert parse_xspace(b"") == []
        assert device_trace_events("/nonexistent/dir") == []


class TestDeviceStatistics:
    """Per-op device tables over xplane-decoded events (reference:
    profiler_statistic.py kernel/op summaries). Round-4 VERDICT #8."""

    def _synth(self):
        # shaped like xplane.py's chrome export: HLO names <op>.<id> on
        # the "XLA Ops" lane, async DMA on its own lane, plus host noise
        evs = []
        for i, dur in enumerate((100.0, 120.0, 80.0)):
            evs.append({"name": f"fusion.{i}", "ph": "X", "cat": "device",
                        "ts": i, "dur": dur, "tid": "XLA Ops"})
        evs.append({"name": "convolution_add_fusion.7", "ph": "X",
                    "cat": "device", "ts": 9, "dur": 50.0,
                    "tid": "XLA Ops"})
        evs.append({"name": "copy.3", "ph": "X", "cat": "device",
                    "ts": 10, "dur": 30.0, "tid": "XLA Ops"})
        evs.append({"name": "slice-start.4", "ph": "X", "cat": "device",
                    "ts": 11, "dur": 999.0, "tid": "Async XLA Ops"})
        evs.append({"name": "step", "ph": "X", "cat": "ProfileStep",
                    "ts": 0, "dur": 400.0, "tid": 1})
        return evs

    def test_per_op_aggregation_and_lane_filter(self):
        from paddle_tpu.profiler import collect_device_statistic

        items = collect_device_statistic(self._synth())
        assert set(items) == {"fusion", "convolution_add_fusion", "copy"}
        f = items["fusion"]
        assert f.calls == 3
        assert f.total_ns == int(300e3)
        # the async lane and host events never pollute the op table
        assert "slice-start" not in items

    def test_table_ranks_compute_on_top(self):
        from paddle_tpu.profiler import device_summary_table

        table = device_summary_table(self._synth())
        body = [l for l in table.splitlines()
                if l.startswith(("fusion", "conv", "copy"))]
        assert body[0].startswith("fusion")

    def test_op_class_buckets(self):
        from paddle_tpu.profiler import op_class

        assert op_class("convolution_add_fusion") == "convolution"
        assert op_class("fusion") == "fusion"
        assert op_class("dot_general") == "matmul"
        assert op_class("_flash_fwd_bhsd") == "custom-call (pallas)"
        for kernel in ("flash_fwd", "flash_bwd_dkv", "rms_norm_bwd",
                       "paged_decode", "flash_varlen_fwd"):
            assert op_class(kernel) == "custom-call (pallas)"
        assert op_class("copy-start") == "data-movement"
        assert op_class("all-reduce") == "collective"

    def test_real_bench_trace_when_present(self):
        """The recorded TPU bench trace (bench_trace.json) must yield a
        non-empty per-op table with a COMPUTE class (fusion / matmul /
        convolution / pallas custom-call) on top — not data movement."""
        import os

        from paddle_tpu.profiler import (collect_device_statistic,
                                         op_class, statistic_from_trace)

        path = os.path.join(os.path.dirname(__file__), "..",
                            "bench_trace.json")
        if not os.path.exists(path):
            pytest.skip("no recorded bench trace in this checkout")
        items = statistic_from_trace(path)
        assert items, "device op table empty"
        top = max(items.values(), key=lambda it: it.total_ns)
        assert op_class(top.name) in {
            "fusion", "matmul", "convolution", "custom-call (pallas)"}, \
            f"top device op is {top.name}"


class TestProfilerEdgeCases:
    """Empty traces and nested/unbalanced span closing (PR-2 satellites)."""

    def test_summary_table_on_empty_trace(self):
        from paddle_tpu.profiler import summary_table

        table = summary_table([])
        assert "Name" in table and "Calls" in table  # header renders

    def test_statistic_from_trace_on_empty_trace(self, tmp_path):
        from paddle_tpu.profiler import statistic_from_trace

        path = tmp_path / "empty_trace.json"
        path.write_text(json.dumps({"traceEvents": [],
                                    "displayTimeUnit": "ms"}))
        assert statistic_from_trace(str(path)) == {}
        # bare-list export shape is accepted too
        path.write_text("[]")
        assert statistic_from_trace(str(path)) == {}

    def test_nested_spans_close_in_order(self):
        from paddle_tpu.profiler.host_tracer import get_host_tracer

        tracer = get_host_tracer()
        tracer.start()
        outer = RecordEvent("outer")
        outer.begin()
        inner = RecordEvent("inner")
        inner.begin()
        inner.end()
        outer.end()
        (root,) = tracer.stop()
        assert root.name == "outer"
        (child,) = root.children
        assert child.name == "inner"
        assert child.children == []
        # the child closed before (or with) its parent, inside its window
        assert root.start_ns <= child.start_ns
        assert child.end_ns <= root.end_ns

    def test_unbalanced_close_does_not_corrupt_stack(self):
        """Closing the OUTER span while the inner is still open (the
        exception-path shape) must close the over-open inner span and
        leave the tracer stack reusable."""
        from paddle_tpu.profiler.host_tracer import get_host_tracer

        tracer = get_host_tracer()
        tracer.start()
        outer = RecordEvent("outer_unbalanced")
        outer.begin()
        inner = RecordEvent("inner_leaked")
        inner.begin()
        outer.end()  # inner never explicitly ended
        with RecordEvent("after"):
            pass
        roots = tracer.stop()
        names = [r.name for r in roots]
        assert names == ["outer_unbalanced", "after"]
        (leaked,) = roots[0].children
        assert leaked.name == "inner_leaked"

    def test_sorted_keys_exported(self):
        from paddle_tpu.profiler import SortedKeys

        assert "SortedKeys" in profiler.__all__
        assert SortedKeys.CPUTotal == 0 and SortedKeys.GPUMin == 7
