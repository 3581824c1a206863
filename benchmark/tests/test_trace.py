"""The reduction from trace to metrics: on hand-made events, and on a
small trace recorded on the chip (`tools/record_trace.py`)."""
import os

import pytest

from benchmark.harness import trace
from benchmark.harness.trace import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE
SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb")


def ev(plane, line, name, start_us, dur_us):
    return Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def test_instruction_names():
    assert trace.instr_name("%flash_fwd.3 = bf16[4,12]{1,0} custom-call(") \
        == "flash_fwd"
    assert trace.instr_name("flash_bwd_dkv.12") == "flash_bwd_dkv"
    assert trace.instr_name("fusion.1.2") == "fusion"
    assert trace.instr_name("all-reduce-start.1") == "all-reduce-start"
    assert trace.instr_name("paged_decode") == "paged_decode"


def test_union_and_subtract():
    u = trace.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [(0, 3), (5, 6)] and trace.length(u) == 4
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert trace.subtract([(0, 2), (3, 5)], []) == [(0, 2), (3, 5)]


def test_busy_is_the_union_averaged_over_chips():
    events = [ev(DEV0, OPS, "fusion.1", 0, 100),
              ev(DEV0, OPS, "fusion.2", 50, 100),     # overlaps: union 150
              ev(DEV0, MODS, "jit_step(1)", 0, 400),  # not an op
              ev(DEV1, OPS, "fusion.1", 0, 50)]
    assert trace.busy_seconds(events) == pytest.approx((150 + 50) / 2 * 1e-6)
    assert trace.busy_seconds([]) == 0.0


def test_kernel_and_module_seconds_by_name():
    events = [ev(DEV0, OPS, "%flash_fwd.1 = custom-call(", 0, 10),
              ev(DEV0, OPS, "flash_fwd.2", 20, 30),
              ev(DEV0, OPS, "flash_fwd_helper.2", 60, 5),
              ev(DEV0, OPS, "paged_decode", 70, 7),
              ev(DEV0, MODS, "jit__prefill_impl(123)", 0, 100),
              ev(DEV0, MODS, "jit__decode_impl(5)", 100, 50)]
    secs, calls = trace.kernel_seconds(events, ["flash_fwd", "flash_bwd_dq"])
    assert secs["flash_fwd"] == pytest.approx(40e-6) and calls == {
        "flash_fwd": 2, "flash_bwd_dq": 0}
    assert secs["flash_bwd_dq"] == 0.0
    assert trace.module_seconds(events, ["_prefill_impl"]) == (
        pytest.approx(100e-6), 1)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    events = [ev(DEV0, OPS, "fusion.1", 0, 10),
              ev(DEV0, OPS, "fusion.2", 40, 10),      # gap 10..40
              ev(DEV0, OPS, "fusion.3", 55, 5),       # gap 50..55
              ev(HOST, "python", "bench.step", 0, 12),
              ev(HOST, "python", "bench.submit", 12, 30),
              ev(HOST, "python", "other", 0, 100)]
    gaps = dict(trace.idle_gaps_by_host_span(events))
    assert gaps == {"bench.submit": pytest.approx(30e-6),
                    "(no span)": pytest.approx(5e-6)}
    top = trace.top_device_ops(events)
    assert top[0] == ["fusion", pytest.approx(25e-6)]


@pytest.mark.skipif(not os.path.exists(SAMPLE), reason="no recorded trace")
def test_recorded_trace_from_the_chip():
    events = trace.load(SAMPLE)
    planes = trace.device_planes(events)
    assert planes == ["/device:TPU:0"]
    secs, calls = trace.kernel_seconds(events, ["flash_fwd"])
    assert calls["flash_fwd"] == 3 and 0 < secs["flash_fwd"] < 1e-2
    busy = trace.busy_seconds(events)
    ops = trace.ops_of(events, planes[0])
    span = (ops[-1].end_ns - ops[0].start_ns) / 1e9
    assert 0 < busy < span                  # the host slept between steps
    _, runs = trace.module_seconds(events, ["step"])
    assert runs == 3
    gaps = dict(trace.idle_gaps_by_host_span(events))
    assert gaps.get("bench.idle", 0) > 3 * 0.004
