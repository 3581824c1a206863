"""The join of the engine's own records with the device trace
(`readers/trace_join.py` and the two readers on it), on hand-made events and
records: the `bench.step` anchor recovers the offset between the two clocks
or says why it cannot, a decode program's record finds the module event that
ran it, a device-idle stretch is split over the phases that cover it by
seconds, and a program that keeps no such records (the parent of the PR
that brought them) gives nothing to read and raises nothing."""
import json
import os
import types

import pytest

from benchmark.harness import spec, trace
from benchmark.harness.trace import Event
from benchmark.readers import decode_program, idle_in_step, trace_join

METRICS = os.path.join(spec.BENCH_DIR, "metrics")
DEV0, HOST = "/device:TPU:0", "/host:CPU"
#: trace's clock = ring's clock + OFFSET; a step every 4 ms, 3 ms long
OFFSET, T0, PERIOD, STEP = -1234.5, 2000.0, 0.004, 0.003
NEW = {"serve.decode_device_ms_p50.decode": "serve_tokens_per_s",
       "serve.decode_device_ms_p50.prefill": "tpot_mean_ms",
       "serve.decode_handoff_ms_p50.prefill": "tpot_mean_ms",
       "device.idle_in_step_pct.prefill": "tpot_mean_ms"}


def params(metric):
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return dict(json.load(f), name=metric)


def ev(plane, line, name, start_s, end_s):
    """An event of the trace from times of the RING's clock."""
    return Event(plane, line, name, (start_s + OFFSET) * 1e9,
                 (end_s - start_s) * 1e9)


def ctx_for(cell, events, t0, t1):
    return {"spec": types.SimpleNamespace(name=cell), "events": events,
            "tracer": types.SimpleNamespace(t0=t0, t1=t1),
            "trace_window_s": t1 - t0}


@pytest.fixture
def tracing():
    from paddle_tpu.observability import tracing

    if not hasattr(tracing, "ring"):
        pytest.skip("this program keeps no records")
    tracing.clear_rings()
    yield tracing
    tracing.clear_rings()


def timeline(tracing, cell, n=12, early=0.0, module_s=0.0025):
    """`n` steps in the engine's steady order (step k dispatches program k,
    then reads program k - 1), their `bench.step` annotations opened
    `early` seconds before the step's first clock read, and each program's
    module 0.3 ms after its dispatch began. Returns the events."""
    events = []
    for k in range(n):
        b = T0 + k * PERIOD
        spans = [("admit", b + 0.0001, b + 0.0002),
                 ("ensure_blocks", b + 0.0003, b + 0.0004),
                 ("dispatch", b + 0.0005, b + 0.0010)]
        if k:
            spans += [("wait", b + 0.0012, b + 0.0020),
                      ("emit", b + 0.0021, b + 0.0022)]
            tracing.ring(cell, "programs").append({
                "kind": "decode", "step": k - 1, "read_step": k, "rows": 2,
                "dispatch": b - PERIOD + 0.0005,
                "dispatched": b - PERIOD + 0.0010, "read": b + 0.0012,
                "tokens": b + 0.0020, "overlapped": k > 1})
        tracing.ring(cell, "steps").append({
            "step": k, "begin": b, "end": b + STEP, "seconds": {},
            "spans": spans})
        events.append(ev(HOST, "python3", "bench.step", b - early,
                         b + STEP + early))
        events.append(ev(DEV0, trace.MODULES_LINE, f"jit__decode_impl({k})",
                         b + 0.0008, b + 0.0008 + module_s))
        events.append(ev(DEV0, trace.OPS_LINE, "%fusion.1 = f32[] fusion(",
                         b + 0.0008, b + 0.0008 + module_s))
    return events


# -- the anchor ---------------------------------------------------------------
@pytest.mark.parametrize("early", [0.0, 7e-6])
def test_the_anchor_recovers_the_offset_between_the_clocks(tracing, early):
    events = timeline(tracing, "cell-a", early=early)
    got = trace_join.anchor(ctx_for("cell-a", events, T0 - 1.0, T0 + 1.0))
    assert got["offset"] == pytest.approx(OFFSET - early, abs=1e-7)
    assert got["note"].startswith(
        "anchor: 12 of 12 steps paired with bench.step, residual spread "
        "0.0000 ms")
    # the ends err the other way: the two medians bracket the true offset
    assert f"bracket {2 * early * 1e3:.4f} ms" in got["note"]


def test_the_anchor_keeps_to_the_steps_of_the_traced_part(tracing):
    events = timeline(tracing, "cell-a")
    # the tracer started inside step 1 and stopped inside step 10: the
    # harness's spans of those two are not in the trace either
    inside = [e for e in events if e.name != "bench.step"
              or 2 <= round((e.start_ns / 1e9 - OFFSET - T0) / PERIOD) <= 9]
    got = trace_join.anchor(ctx_for(
        "cell-a", inside, T0 + PERIOD + 0.001, T0 + 10 * PERIOD + 0.001))
    assert got["offset"] == pytest.approx(OFFSET, abs=1e-7)
    assert "8 of 8 steps paired" in got["note"]


@pytest.mark.parametrize("drop, paired", [
    ([0], "11 of 12"),           # the head lost: the rest still agrees
    ([6], None),                 # one lost in the middle: half disagree
    (list(range(12)), None)])    # no host span at all
def test_the_anchor_pairs_from_the_end_or_says_why_not(tracing, drop,
                                                       paired):
    events = timeline(tracing, "cell-a")
    spans = [e for e in events if e.name == "bench.step"]
    events = [e for e in events if e not in [spans[i] for i in drop]]
    got = trace_join.anchor(ctx_for("cell-a", events, T0 - 1.0, T0 + 1.0))
    if paired:
        assert got["offset"] == pytest.approx(OFFSET, abs=1e-7)
        assert paired in got["note"]
    else:
        assert got["offset"] is None
        assert "too few pairs agree" in got["note"] \
            or "no anchor" in got["note"]
    for metric in NEW:
        reader = idle_in_step if "idle" in metric else decode_program
        ctx = ctx_for("cell-a", events, T0 - 1.0, T0 + 1.0)
        value = reader.read(params(metric), ctx)
        assert (value is None) == (paired is None)
        if paired is None:      # no value, and the note says why
            assert "anchor" in ctx["notes"][metric]


def test_a_jittery_anchor_gives_no_value(tracing):
    events = timeline(tracing, "cell-a")
    # every other annotation opened half a millisecond before its step
    events = [e._replace(start_ns=e.start_ns - 5e5 * (i % 2))
              if e.name == "bench.step" else e
              for i, e in enumerate(events)]
    ctx = ctx_for("cell-a", events, T0 - 1.0, T0 + 1.0)
    assert trace_join.anchor(ctx)["offset"] is None
    assert "the spread is over 0.2 ms" in trace_join.anchor(ctx)["note"]
    assert decode_program.read(
        params("serve.decode_device_ms_p50.decode"), ctx) is None


# -- a program and its module -------------------------------------------------
def test_a_programs_device_time_and_its_handoff(tracing):
    events = timeline(tracing, "cell-b")
    # a warm-up's program of the same name before the first dispatch, and
    # a prompt's program that keeps the device until program 5 is launched
    events.append(ev(DEV0, trace.MODULES_LINE, "jit__decode_impl(0)",
                     T0 - 0.5, T0 - 0.4))
    events.append(ev(DEV0, trace.MODULES_LINE, "jit__prefill_impl(9)",
                     T0 + 5 * PERIOD + 0.0002, T0 + 5 * PERIOD + 0.0007))
    # program 6 went out with nothing in flight, onto an idle device
    tracing.ring("cell-b", "programs")[6]["overlapped"] = False
    ctx = ctx_for("cell-b", events, T0 - 1.0, T0 + 1.0)
    for metric in ("serve.decode_device_ms_p50.decode",
                   "serve.decode_device_ms_p50.prefill"):
        assert decode_program.read(params(metric), ctx) \
            == pytest.approx(2.5)
    # 11 programs were read (the twelfth is in flight); the warm-up's
    # module and the twelfth's are nobody's
    assert ctx["notes"]["serve.decode_device_ms_p50.decode"].startswith(
        "11 pairs, 2 events unpaired, 0.0275 s on the device, 1.2500 ms a "
        "live row at the median; programs of the chip: jit__decode_impl 13 "
        "runs 0.1300 s, jit__prefill_impl 1 runs 0.0005 s; anchor: 12 of "
        "12 steps paired")
    # launch: 0.3 ms from the dispatch's start to the module's (0.1 ms for
    # program 5, which waited for the prompt's program, not for the host);
    # way back: the module ended before the host came to read, and the
    # read took 0.8 ms
    assert decode_program.read(
        params("serve.decode_handoff_ms_p50.prefill"), ctx) \
        == pytest.approx(0.3 + 0.8)
    # what no misplaced device clock can move: the whole of dispatch to
    # tokens less the module, for the two programs that found the device
    # idle (the first and the sixth); and the two least distances, which
    # cannot be negative
    note = ctx["notes"]["serve.decode_handoff_ms_p50.prefill"]
    assert note.startswith(
        "11 pairs; medians: launch 0.3000 ms, way back 0.8000 ms, dispatch "
        "to tokens 5.5000 ms; 2 programs found the device idle: dispatch to "
        "tokens less the module 3.0000 ms; a queued program starts ")
    assert ("least module start less dispatch 0.3000 ms, least tokens less "
            "module end 2.7000 ms; anchor") in note
    pairs, unpaired, _ = trace_join.programs_with_modules(
        ctx, ["_decode_impl"])
    assert [r["step"] for r, *_ in pairs] == list(range(11))
    for k, (r, start, end, free) in enumerate(pairs):
        assert r["dispatch"] <= start and end <= r["tokens"]
        assert start == pytest.approx(T0 + k * PERIOD + 0.0008, abs=1e-7)
    assert pairs[5][3] == pytest.approx(T0 + 5 * PERIOD + 0.0007, abs=1e-7)


def test_a_device_bound_program_is_launched_when_the_one_before_ends(
        tracing):
    # modules of 4 ms back to back: each starts as the one before it ends,
    # long after its dispatch; the host reads before the module ends
    tracing.ring("cell-b", "steps")
    events, programs = [], tracing.ring("cell-b", "programs")
    events.append(ev(DEV0, trace.MODULES_LINE, "jit__decode_impl(0)",
                     T0 - 0.5, T0 - 0.4))                    # a warm-up's
    for k in range(6):
        b = T0 + k * PERIOD
        tracing.ring("cell-b", "steps").append(
            {"step": k, "begin": b, "end": b + 0.0039, "spans": []})
        events.append(ev(HOST, "python3", "bench.step", b, b + 0.0039))
        # the first finds the device idle and starts 0.2 ms after its
        # dispatch span; the others lie enqueued until 1 ms after theirs
        events.append(ev(DEV0, trace.MODULES_LINE, f"jit__decode_impl({k})",
                         b + (0.002 if k else 0.0012), b + 0.006))
        programs.append({"kind": "decode", "step": k, "read_step": k + 1,
                         "rows": 4, "dispatch": b + 0.0005,
                         "dispatched": b + 0.001, "read": b + 0.0052,
                         "tokens": b + 0.0067, "overlapped": k > 0})
    ctx = ctx_for("cell-b", events, T0 - 1.0, T0 + 1.0)
    assert decode_program.read(
        params("serve.decode_device_ms_p50.decode"), ctx) \
        == pytest.approx(4.0)
    # no launch to see (the program was queued behind the one before), and
    # 0.7 ms from the module's end to the tokens
    assert decode_program.read(
        params("serve.decode_handoff_ms_p50.prefill"), ctx) \
        == pytest.approx(0.7)
    note = ctx["notes"]["serve.decode_handoff_ms_p50.prefill"]
    assert note.startswith("6 pairs; medians: launch ")
    assert "0.0000 ms, way back 0.7000 ms, dispatch to tokens 6.2000" in note
    # the host's slack, whatever the device's clock is taken to be
    assert ("1 programs found the device idle: dispatch to tokens less the "
            "module 1.4000 ms; a queued program starts 0.8000 ms later after "
            "its dispatch than one that found the device idle") in note


@pytest.mark.parametrize("cut", [0.0002, 0.0009, 0.0025])
def test_a_trace_that_begins_inside_a_program_loses_that_program_alone(
        tracing, cut):
    events = timeline(tracing, "cell-c")
    # the trace begins `cut` into step 3: before program 3's dispatch,
    # inside its module, after its module. Whatever began earlier is not
    # in it (a module cut at its head is dropped whole).
    begin = (T0 + 3 * PERIOD + cut + OFFSET) * 1e9
    events = [e for e in events if e.start_ns >= begin]
    ctx = ctx_for("cell-c", events, T0 + 3 * PERIOD + cut, T0 + 1.0)
    pairs, unpaired, _ = trace_join.programs_with_modules(
        ctx, ["_decode_impl"])
    first = 3 if cut < 0.0008 else 4
    assert [r["step"] for r, *_ in pairs] == list(range(first, 11))
    assert unpaired == 1                  # the twelfth's, still in flight
    for r, start, end, _ in pairs:
        assert start == pytest.approx(T0 + r["step"] * PERIOD + 0.0008,
                                      abs=1e-7)
    assert "8 of 8 steps paired" in trace_join.anchor(ctx)["note"]


# -- idle time inside a step --------------------------------------------------
def test_an_idle_stretch_is_split_over_the_phases_by_seconds(tracing):
    ms = 1e-3
    tracing.ring("cell-d", "steps").append({
        "step": 0, "begin": T0, "end": T0 + 3 * ms, "seconds": {},
        "spans": [("admit", T0, T0 + 0.4 * ms),
                  ("prefill", T0 + 0.1 * ms, T0 + 0.3 * ms),
                  ("dispatch", T0 + 0.5 * ms, T0 + 1.0 * ms),
                  ("wait", T0 + 1.2 * ms, T0 + 2.0 * ms)]})
    tracing.ring("cell-d", "steps").append({
        "step": 1, "begin": T0 + 4 * ms, "end": T0 + 5 * ms, "seconds": {},
        "spans": [("wait", T0 + 4.1 * ms, T0 + 4.9 * ms)]})

    def op(a, b):
        return ev(DEV0, trace.OPS_LINE, "fusion.1", T0 + a * ms, T0 + b * ms)

    events = [ev(HOST, "python3", "bench.step", T0, T0 + 3 * ms),
              ev(HOST, "python3", "bench.step", T0 + 4 * ms, T0 + 5 * ms),
              ev(HOST, "python3", "bench.idle", T0 + 3 * ms, T0 + 4 * ms),
              # idle 0..0.2 (admit 0.1, the prompt's dispatch inside it
              # 0.1), 0.9..1.5 (dispatch 0.1, between two spans 0.2, wait
              # 0.3), 2.5..4.5 (after the wait 0.5, outside 1.0, the next
              # step's head 0.1 and its wait 0.4), 5..6 outside
              op(0.2, 0.9), op(0.6, 0.7), op(1.5, 2.5), op(4.5, 5.0)]
    ctx = ctx_for("cell-d", events, T0 - 2 * ms, T0 + 6 * ms)
    value = idle_in_step.read(params("device.idle_in_step_pct.prefill"), ctx)
    inside = 0.1 + 0.1 + 0.1 + 0.2 + 0.3 + 0.5 + 0.1 + 0.4
    assert value == pytest.approx(100 * inside / 8.0)
    note = ctx["notes"]["device.idle_in_step_pct.prefill"]
    by_phase, outside, held, _ = note.split("; ", 3)
    assert by_phase.startswith("0.0018 s idle inside 2 steps: other 0.0008, "
                               "wait 0.0007, ")
    assert sorted(by_phase.split(", ")[2:]) == [
        "admit 0.0001", "dispatch 0.0001", "prefill 0.0001"]
    assert outside == ("0.0040 s idle outside every step (50.00% of the "
                       "window)")
    assert held == "the steps hold 0.0040 s"
    # with the outside's share it is the whole window's idle share, which
    # `device.idle_pct.*` reads from the same busy intervals
    busy = trace.busy_seconds(events)
    assert value + 50.0 == pytest.approx(100 * (1 - busy / 8e-3))
    # where the harness's own split gives each stretch between two
    # operations whole to one span: the 2 ms that hold the end of a step,
    # an idle spell and the head of the next step are all `bench.idle`'s
    assert dict(trace.idle_gaps_by_host_span(events)) == {
        "bench.step": pytest.approx(0.6e-3, abs=1e-9),
        "bench.idle": pytest.approx(2.0e-3, abs=1e-9)}


def test_a_steps_phases_tile_it_innermost_first():
    record = {"begin": 10.0, "end": 13.0, "spans": [
        ("admit", 10.0, 11.0), ("prefill", 10.2, 10.4),
        ("prefill", 10.5, 10.9), ("ensure_blocks", 11.0, 12.5),
        ("wait", 11.5, 12.0), ("emit", 12.0, 12.2)]}
    got = {}
    for phase, a, b in idle_in_step.phase_segments(record):
        got[phase] = got.get(phase, 0.0) + b - a
    assert got == {k: pytest.approx(v) for k, v in {
        "prefill": 0.6, "admit": 0.4, "wait": 0.5, "emit": 0.2,
        "ensure_blocks": 0.8, "other": 0.5}.items()}
    assert idle_in_step.busy_inside(
        [(1.0, 2.0), (4.0, 6.0)], [0.0, 1.5, 3.0, 5.0], [0.5, 4.5, 3.5, 9.0]
    ) == pytest.approx([0.0, 1.0, 0.0, 1.0])


# -- a program without the records --------------------------------------------
@pytest.mark.parametrize("parent", ["no ring function", "no programs ring",
                                    "steps without spans", "no trace"])
def test_a_program_without_the_records_gives_nothing_and_raises_nothing(
        tracing, monkeypatch, parent):
    events = timeline(tracing, "cell-e")
    if parent == "no ring function":
        monkeypatch.delattr(tracing, "ring")
    elif parent == "no programs ring":
        tracing.ring("cell-e", "programs").clear()
    elif parent == "steps without spans":
        for r in tracing.ring("cell-e", "steps"):
            del r["spans"]
    else:
        events = []
    ctx = ctx_for("cell-e", events, T0 - 1.0, T0 + 1.0)
    for metric in NEW:
        reader = idle_in_step if "idle" in metric else decode_program
        expect_value = (parent == "no programs ring") == ("idle" in metric) \
            and parent in ("no programs ring", "steps without spans")
        value = reader.read(params(metric), ctx)
        assert (value is not None) == expect_value, metric
    assert not ctx.get("notes") or all(
        "anchor: 12 of 12" in n for n in ctx["notes"].values())


# -- the manifest -------------------------------------------------------------
def test_the_manifest_holds_the_four_metrics_by_name():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    closed = [w["name"] for w in manifest["workloads"]
              if "serve" in w["name"] and "closed" in w["name"]]
    assert len(closed) == 4
    for name, moves in NEW.items():
        entry = entries[name]
        assert entry["moves"] == moves and entry["better"] == "lower"
        assert entry["workloads"] == (
            closed if name.endswith(".decode")
            else ["cgpt590m-serve-prefill-open"])
        assert params(name)["reader"] in ("decode_program", "idle_in_step")
        for cell in entry["workloads"]:
            assert name in {m["name"]
                            for m, _ in spec.Spec(cell).per_layer()}
    assert entries["device.idle_in_step_pct.prefill"]["unit"] == "%"
    assert {entries[n]["source"] for n in NEW} == {"device_trace",
                                                   "program_span"}
