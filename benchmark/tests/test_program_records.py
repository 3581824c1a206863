"""The readers of the program's own step and request records, on hand-made
records: which records fall in the window, what each metric's file makes of
them, and that a program which keeps no such ring (the parent of the PR that
brought it) gives nothing to read and raises nothing."""
import json
import os
import types

import pytest

from benchmark.harness import spec
from benchmark.readers import request_record, step_record

METRICS = os.path.join(spec.BENCH_DIR, "metrics")
T1, WINDOW = 1000.0, 50.0


def params(metric):
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return dict(json.load(f), name=metric)


def ctx_for(cell):
    return {"spec": types.SimpleNamespace(name=cell), "window_s": WINDOW,
            "tracer": types.SimpleNamespace(t1=T1)}


STEP_PHASES = ("admit", "prefill", "ensure_blocks", "dispatch", "wait",
               "emit", "other")


def step(end, seconds, whole):
    secs = dict.fromkeys(STEP_PHASES, 0.0)
    secs.update(seconds)
    return {"begin": end - whole, "end": end, "seconds": secs}


@pytest.fixture
def tracing():
    from paddle_tpu.observability import tracing

    if not hasattr(tracing, "ring"):
        pytest.skip("this program keeps no records")
    tracing.clear_rings()
    yield tracing
    tracing.clear_rings()


def test_step_phases_of_the_records_that_end_in_the_window(tracing):
    ring = tracing.ring("cell-a", "steps")
    ring.append(step(T1 - 60.0, {"dispatch": 9.0, "wait": 9.0}, 20.0))  # before
    for k, (dispatch, wait, prefill) in enumerate(
            [(0.002, 0.100, 0.0), (0.003, 0.104, 0.0), (0.002, 0.102, 0.090)]):
        ring.append(step(T1 - 40.0 + k, {"dispatch": dispatch, "wait": wait,
                                         "prefill": prefill, "emit": 0.001},
                         dispatch + wait + prefill + 0.004))
    ring.append(step(T1 + 0.5, {"dispatch": 9.0, "wait": 9.0}, 20.0))   # after
    ctx = ctx_for("cell-a")
    # the host blocked on the device's answer: the wait alone, so that it
    # and the host's share below are disjoint and add up to a step that
    # holds no prefill
    assert step_record.read(params("serve.program_ms_p50.decode"), ctx) \
        == pytest.approx(102.0)
    # the whole step less its wait and its prefills: dispatch + 4 ms
    for metric in ("serve.host_ms_p50.decode", "serve.host_ms_p50.prefill"):
        assert step_record.read(params(metric), ctx) == pytest.approx(6.0)
    assert set(params("serve.program_ms_p50.decode")["sum"]).isdisjoint(
        set(STEP_PHASES) - set(params("serve.host_ms_p50.decode")[
            "whole_less"]))
    assert ctx["notes"]["serve.program_ms_p50.decode"].startswith(
        "3 records; mean ms of a step by phase: admit 0.000, prefill "
        "30.000, ensure_blocks 0.000, dispatch 2.333, wait 102.000")
    assert step_record.read(params("serve.host_ms_p50.decode"),
                            ctx_for("another-cell")) is None


def test_the_train_steps_calls_are_read_under_their_own_owner(tracing):
    p = params("jit.call_ms_p50")
    assert p["owner"] == "jit.train_step"   # harness/train.py's function
    ring = tracing.ring(p["owner"], "steps")
    for k, whole in enumerate([0.0021, 0.0025, 0.0040]):
        ring.append(step(T1 - 1.0 - k, {"dispatch": whole / 2}, whole))
    assert step_record.read(p, ctx_for("a-train-cell")) \
        == pytest.approx(2.5)


def test_request_intervals_over_first_tokens_in_the_window(tracing):
    ring = tracing.ring("cell-b", "requests")

    def request(submit, admit, first, warmup=False):
        return {"id": len(ring), "submit": submit, "admit": admit,
                "first_token": first, "finish": None, "warmup": warmup}

    ring.append(request(T1 - 70.0, T1 - 69.0, T1 - 68.0))       # before
    ring.append(request(T1 - 51.0, T1 - 50.5, T1 - 49.9))       # 500, 600 ms
    ring.append(request(T1 - 10.0, T1 - 9.9, T1 - 9.7))         # 100, 200 ms
    ring.append(request(T1 - 5.0, T1 - 4.0, T1 - 3.0, warmup=True))
    ring.append(request(T1 - 0.2, T1 - 0.1, None))              # not yet
    ring.append(request(T1 - 0.3, None, None))                  # still queued
    ctx = ctx_for("cell-b")
    assert request_record.read(params("serve.queue_wait_ms_mean"), ctx) \
        == pytest.approx(300.0)
    assert ctx["notes"]["serve.queue_wait_ms_mean"] == "2 requests"
    # with the harness's own view in hand, the note holds the other side
    ctx.update(spans={"ttft": [0.9, 0.8]}, late=[0.1, 0.2])
    assert request_record.read(params("serve.prefill_ms_mean"), ctx) \
        == pytest.approx(400.0)
    assert ctx["notes"]["serve.prefill_ms_mean"] == (
        "2 requests; harness: first token minus submit over 2 due in its "
        "window, mean 700.000 ms, largest 800.000, 600.000, mean lateness "
        "150.000 ms")


def test_a_program_without_rings_gives_nothing_and_raises_nothing(
        tracing, monkeypatch):
    tracing.ring("cell-c", "steps").append(
        step(T1 - 1.0, {"wait": 0.1}, 0.11))
    monkeypatch.delattr(tracing, "ring")
    for metric in ("serve.program_ms_p50.decode", "serve.host_ms_p50.decode",
                   "serve.host_ms_p50.prefill", "jit.call_ms_p50"):
        assert step_record.read(params(metric), ctx_for("cell-c")) is None
    for metric in ("serve.queue_wait_ms_mean", "serve.prefill_ms_mean"):
        assert request_record.read(params(metric), ctx_for("cell-c")) is None


def test_the_manifest_gives_each_new_metric_to_its_cell_alone():
    expect = {
        "cgpt590m-serve-decode-closed128": {"serve.program_ms_p50.decode",
                                            "serve.host_ms_p50.decode"},
        "cgpt590m-serve-prefill-open": {"serve.host_ms_p50.prefill",
                                        "serve.queue_wait_ms_mean",
                                        "serve.prefill_ms_mean"},
        "cgpt590m-train-2k": {"jit.call_ms_p50"}}
    new = set().union(*expect.values())
    for cell, names in expect.items():
        mine = {m["name"] for m, p in spec.Spec(cell).per_layer()
                if p["reader"] in ("step_record", "request_record")}
        assert mine == names and mine <= new
