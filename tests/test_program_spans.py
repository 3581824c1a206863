"""The tracing layer inside the two hot paths (ISSUE 25): the span
primitive reaches a trace that somebody else started, `ServeEngine.step`
and `to_static`'s call fill step and request records from one clock pair a
phase, layers run under `jax.named_scope`, and `profiler.scope_seconds`
joins a compiled program's text with traced seconds. No assertion here is
on wall-clock time: clocks are fake or counted."""
import gc
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
import paddle_tpu.optimizer as opt
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.observability import tracing
from paddle_tpu.profiler import scope_of, scope_seconds
from paddle_tpu.serve import ServeEngine
from paddle_tpu.serve.engine import STEP_PHASES


def _llama():
    paddle.seed(3)
    m = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))
    m.eval()
    return m


def _engine(name, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("max_seq_len", 32)
    return ServeEngine(_llama(), name=name, **kw)


def _gpt_step():
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64))
    optimizer = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())

    @paddle.jit.to_static(full_graph=True)
    def tiny_train_step(ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    ids = paddle.to_tensor(np.random.randint(0, 97, (2, 16)))
    return tiny_train_step, ids


class CountingClock:
    """`time.perf_counter` that counts its reads."""

    def __init__(self):
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return time.perf_counter()


# --------------------------------------------------------------------------
# 1. the span primitive reaches any live trace
# --------------------------------------------------------------------------
def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_program_spans_land_in_a_trace_started_from_outside(tmp_path):
    import jax

    eng = _engine("spans-xplane")
    step, ids = _gpt_step()
    float(step(ids, ids))                       # compiled outside the trace
    eng.submit(np.arange(1, 8), max_new_tokens=6)
    # a plain jax.profiler trace: no profiler.Profiler anywhere
    jax.profiler.start_trace(str(tmp_path))
    try:
        # admit and prefill; the first decode program goes out; from the
        # third on a step dispatches a program and reads the one before
        for _ in range(5):
            eng.step()
        float(step(ids, ids))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    by_name = {}
    for name, a, b, stats in events:
        by_name.setdefault(name, []).append((a, b, stats))
    steps = sorted(by_name["serve.step"])
    assert len(steps) == 5
    assert [s[2]["step"] for s in steps] == [0, 1, 2, 3, 4]
    assert steps[0][2]["n_active"] == 1

    def inside_a_step(span):
        return any(a <= span[0] and span[1] <= b for a, b, _ in steps)

    for name in ("serve.admit", "serve.prefill", "serve.ensure_blocks",
                 "serve.decode.dispatch", "serve.decode.wait",
                 "serve.decode.emit"):
        assert by_name.get(name), f"{name} is not in the host plane"
        assert all(inside_a_step(s) for s in by_name[name]), name
    # the prompt's dispatch, and the wait for its first token
    prefill, first = (s[2] for s in sorted(by_name["serve.prefill"]))
    assert prefill["bucket"] == 8 and prefill["tokens"] == 7
    assert first["first_token"] and "bucket" not in first
    assert len(by_name["serve.decode.dispatch"]) == 4
    assert len(by_name["serve.decode.wait"]) == 3
    # a program's dispatch lies before the read of the program before it
    for d, w in zip(sorted(by_name["serve.decode.dispatch"])[1:],
                    sorted(by_name["serve.decode.wait"])):
        assert d[1] <= w[0]
    (call,) = by_name["jit.call"]
    assert call[2]["fn"] == "tiny_train_step"
    for name in ("jit.lookup", "jit.state", "jit.dispatch",
                 "jit.writeback"):
        (child,) = by_name[name]
        assert call[0] <= child[0] and child[1] <= call[1], name


# --------------------------------------------------------------------------
# 2. step and request records of the engine
# --------------------------------------------------------------------------
def test_step_record_phases_sum_to_the_step_on_a_fake_clock():
    clock = obs.FakeClock(start=100.0, tick=0.001)
    eng = _engine("spans-fake", clock=clock, trace=True)
    reqs = [eng.submit(np.arange(1, n), max_new_tokens=k)
            for n, k in [(8, 5), (4, 7), (6, 3)]]
    eng.run(max_steps=200)
    records = list(tracing.ring("spans-fake", "steps"))
    assert len(records) == eng._n_steps and len(records) > 5
    for before, after in zip(records, records[1:]):
        assert before["end"] <= after["begin"]
    for r in records:
        secs = r["seconds"]
        assert set(r) == {"step", "begin", "end", "seconds", "spans"}
        assert tuple(secs) == STEP_PHASES
        assert all(v >= 0 for v in secs.values())
        assert sum(secs.values()) == pytest.approx(r["end"] - r["begin"],
                                                   abs=1e-9)
        # one clock pair round a decode's wait: one tick on this clock
        assert secs["wait"] in (0.0, pytest.approx(0.001))
    # one clock pair round a prefill's dispatch, another round the wait
    # for its logits, and the first token's read inside that: three ticks
    # on this clock, for each of the three prompts
    assert sum(r["seconds"]["prefill"] for r in records) == pytest.approx(
        3 * 0.003)
    assert all(q.finish_time is not None for q in reqs)
    # the registry's series are fed from the same measurements
    hist = obs.registry.get("serve.decode_step_seconds")
    n_decodes = sum(r["seconds"]["wait"] > 0 for r in records)
    assert hist.stats(engine="spans-fake")["count"] == n_decodes
    assert obs.registry.get("serve.host_roundtrips").value(
        engine="spans-fake") == n_decodes
    assert sum(r["seconds"]["dispatch"] > 0 for r in records) == n_decodes
    # a program's time runs from its dispatch to its tokens, which are
    # read a step later wherever another program was dispatched behind
    # it: the same pairs that the tracer's engine lane gets
    lane = list(eng.tracer.decode_steps)
    assert len(lane) == n_decodes
    assert hist.stats(engine="spans-fake")["sum"] == pytest.approx(
        sum(s["end"] - s["start"] for s in lane), abs=1e-6)
    assert hist.stats(engine="spans-fake")["sum"] > sum(
        r["seconds"]["dispatch"] + r["seconds"]["wait"] for r in records)
    assert any(b["start"] < a["end"] for a, b in zip(lane, lane[1:]))
    assert obs.registry.get("serve.prefill_seconds").stats(
        engine="spans-fake")["count"] == 3


def test_request_records_keep_their_order_through_a_preemption():
    clock = obs.FakeClock(start=5.0, tick=0.001)
    # the pool cannot hold all streams to their ends: someone is evicted
    eng = _engine("spans-preempt", num_blocks=7, max_seq_len=28,
                  clock=clock, trace=True)
    rng = np.random.RandomState(1)
    reqs = [eng.submit(rng.randint(1, 97, n), max_new_tokens=k)
            for n, k in [(10, 8), (9, 7), (5, 6)]]
    eng.run(max_steps=2000)
    records = list(tracing.ring("spans-preempt", "requests"))
    assert [r["id"] for r in records] == [q.id for q in reqs]
    assert sum(q.preemptions for q in reqs) > 0
    for r, q in zip(records, reqs):
        assert r is q.record
        assert r["submit"] <= r["admit"] <= r["first_token"] <= r["finish"]
        assert (r["submit"], r["admit"], r["first_token"], r["finish"]) == (
            q.submit_time, q.admit_time, q.first_token_time, q.finish_time)
        assert set(r) == {"id", "submit", "admit", "first_token",
                          "finish", "warmup"} and not r["warmup"]
        # the opt-in tracer's tree opens its prefill phase at the same read
        phases = q.trace.root.children
        assert [c.name for c in phases][:2] == ["queue", "prefill"]
        assert phases[1].start == r["admit"]
    steps = list(tracing.ring("spans-preempt", "steps"))
    for s in steps:
        assert sum(s["seconds"].values()) == pytest.approx(
            s["end"] - s["begin"], abs=1e-9)


# --------------------------------------------------------------------------
# 2b. a record a dispatched program, and a step's phases as intervals
# --------------------------------------------------------------------------
#: name -> (engine options, batches of (prompt length, outputs); the engine
#: runs dry between two batches, which drains its pipeline)
PROGRAM_CASES = {
    "one-stream": ({}, [[(8, 9)]]),
    "three-streams": ({}, [[(8, 5), (4, 7), (6, 3)]]),
    "drained": ({}, [[(8, 4)], [(6, 5), (5, 3)]]),
    "preempted": ({"num_blocks": 7, "max_seq_len": 28},
                  [[(10, 8), (9, 7), (5, 6)]]),
    "burst": ({"decode_burst": 4}, [[(8, 9), (5, 6)]]),
}


@pytest.fixture(scope="module", params=sorted(PROGRAM_CASES))
def ran(request):
    """(engine, step records, program records) of one case, run to its
    end on a clock that moves a millisecond a read."""
    options, batches = PROGRAM_CASES[request.param]
    name = f"programs-{request.param}"
    eng = _engine(name, clock=obs.FakeClock(start=50.0, tick=0.001),
                  **options)
    rng = np.random.RandomState(2)
    for batch in batches:
        for n, k in batch:
            eng.submit(rng.randint(1, 97, n), max_new_tokens=k)
        eng.run(max_steps=2000)
    return (eng, list(tracing.ring(name, "steps")),
            list(tracing.ring(name, "programs")))


def _counter(name, eng, **labels):
    return obs.registry.get(name).value(engine=eng.name, **labels)


def test_one_record_a_program_with_its_four_times_in_order(ran):
    eng, steps, programs = ran
    decodes = [p for p in programs if p["kind"] == "decode"]
    prefills = [p for p in programs if p["kind"] == "prefill"]
    assert len(decodes) + len(prefills) == len(programs)
    assert len(decodes) == _counter("serve.host_roundtrips", eng)
    assert sum(p.get("ticks", 1) for p in decodes) == _counter(
        "serve.decode_steps", eng)
    # a prompt's program, and one more each time a stream came back
    assert len(prefills) == len(eng.finished) + eng._n_preempts
    for p in decodes:
        assert list(p)[:9] == ["kind", "step", "read_step", "rows",
                               "dispatch", "dispatched", "read", "tokens",
                               "overlapped"]
        assert p["dispatch"] <= p["dispatched"] <= p["read"] <= p["tokens"]
        assert 1 <= p["rows"] <= eng.max_slots
    for p in prefills:
        assert list(p) == ["kind", "request", "bucket", "tokens", "step",
                           "dispatch", "dispatched", "read", "tokens_at"]
        assert p["tokens"] <= p["bucket"]
        if p["read"] is None:       # a resumed stream's: nobody reads it
            assert p["tokens_at"] is None and eng._n_preempts
        else:
            assert (p["dispatch"] <= p["dispatched"] <= p["read"]
                    <= p["tokens_at"])
    fresh = [p for p in prefills if p["read"] is not None]
    assert sorted(p["request"] for p in fresh) == sorted(
        r.id for r in eng.finished)
    # the pair that the histogram is fed from is the record's
    hist = obs.registry.get("serve.decode_step_seconds").stats(
        engine=eng.name)
    assert hist["sum"] == pytest.approx(
        sum(p["tokens"] - p["dispatch"] for p in decodes), abs=1e-6)


def test_overlapped_says_whether_a_program_went_out_behind_another(ran):
    eng, steps, programs = ran
    decodes = [p for p in programs if p["kind"] == "decode"]
    assert not decodes[0]["overlapped"]
    for before, after in zip(decodes, decodes[1:]):
        assert before["dispatch"] < after["dispatch"]
        # behind another: dispatched before that one's tokens were read
        assert after["overlapped"] == (after["dispatch"] < before["tokens"])
    assert sum(p["overlapped"] for p in decodes) == (
        _counter("serve.decode_overlapped", eng) or 0)
    drains = sum(_counter("serve.pipeline_drains", eng, reason=r) or 0
                 for r in ("preempt", "burst", "idle"))
    alone = sum(not p["overlapped"] for p in decodes)
    if eng.decode_burst > 1:
        assert alone == len(decodes) and not drains
    else:
        # the first program, and the first after each drain
        assert 1 <= alone <= 1 + drains
        assert len(decodes) > alone


def test_step_and_read_step_point_at_the_steps_that_hold_the_spans(ran):
    eng, steps, programs = ran
    assert [s["step"] for s in steps] == list(range(eng._n_steps))
    for p in programs:
        step = steps[p["step"]]
        assert step["begin"] <= p["dispatch"] <= p["dispatched"] \
            <= step["end"]
        if p["kind"] == "prefill":
            if p["read"] is not None:     # read at the end of its own step
                assert p["dispatched"] <= p["read"] <= p["tokens_at"] \
                    <= step["end"]
            continue
        # one program in flight: read by the step after the one that
        # dispatched it; a burst is read where it is dispatched
        assert p["read_step"] - p["step"] == (0 if "ticks" in p else 1)
        read = steps[p["read_step"]]
        assert read["begin"] <= p["read"] <= p["tokens"] <= read["end"]
        assert ("wait", p["read"], p["tokens"]) in read["spans"]
        assert ("dispatch", p["dispatch"], p["dispatched"]) in step["spans"]


def test_a_steps_spans_add_up_to_its_seconds_by_phase(ran):
    eng, steps, programs = ran
    for s in steps:
        spans = s["spans"]
        assert spans == sorted(spans, key=lambda x: x[1])
        assert spans[0][0] == "admit"
        by_phase = dict.fromkeys(STEP_PHASES, 0.0)
        for phase, start, end in spans:
            assert s["begin"] <= start <= end <= s["end"]
            by_phase[phase] += end - start
        _, a0, a1 = spans[0]
        # an admission's span holds the dispatches of its prompts
        by_phase["admit"] -= sum(
            end - start for phase, start, end in spans[1:]
            if phase == "prefill" and a0 <= start and end <= a1)
        assert by_phase.pop("other") == 0.0
        other = s["seconds"]["other"]
        assert {p: v for p, v in s["seconds"].items() if p != "other"} \
            == pytest.approx(by_phase, abs=1e-9)
        assert sum(by_phase.values()) + other == pytest.approx(
            s["end"] - s["begin"], abs=1e-9)
    n_prefill = sum(phase == "prefill" for s in steps
                    for phase, _, _ in s["spans"])
    fresh = sum(p["kind"] == "prefill" and p["read"] is not None
                for p in programs)
    assert n_prefill == len(programs) - sum(
        p["kind"] == "decode" for p in programs) + fresh


def test_the_counters_tool_names_the_longest_programs_and_their_steps(ran):
    from tools.serve_counters import programs, slowest

    eng, steps, records = ran
    out = programs(eng.name)
    decodes = [p for p in records if p["kind"] == "decode"]
    assert out["programs_in_ring"] == len(records)
    assert out["decode_programs"] == len(decodes)
    times = sorted(p["tokens"] - p["dispatch"] for p in decodes)
    assert out["dispatch_to_tokens_ms"]["p100"] == pytest.approx(
        times[-1] * 1e3, abs=1e-3)
    assert out["dispatch_to_tokens_ms"]["p5"] <= \
        out["dispatch_to_tokens_ms"]["p50"] <= \
        out["dispatch_to_tokens_ms"]["p99"]
    longest = out["longest_programs"]
    assert len(longest) == min(5, len(records))
    assert [p["ms"] for p in longest] == sorted(
        (p["ms"] for p in longest), reverse=True)
    for p in longest:
        assert 0 <= p["step"] <= p["read_step"] < len(steps)
        assert ("rows" in p) == (p["kind"] == "decode")
        assert ("request" in p) == (p["kind"] == "prefill")
    # the steps it points at are named by the same index in the other list
    assert {s["step"] for s in slowest(eng.name)["longest_steps"]} <= set(
        range(len(steps)))
    assert programs("no-such-engine") == {}


def test_rings_are_bounded_and_outlive_the_engine():
    ring = tracing.ring("spans-bounded", "steps")
    assert ring is tracing.ring("spans-bounded", "steps")
    assert ring is not tracing.ring("spans-bounded", "requests")
    for i in range(tracing.RING_LEN + 10):
        ring.append({"step": i})
    assert len(ring) == tracing.RING_LEN and ring[0]["step"] == 10
    # the benchmark's shortest steps: 6,267 in a window of the prefill
    # cell, 49 s of them back to back at 3 ms
    assert tracing.RING_LEN >= 2 * 6267 and tracing.RING_LEN * 0.003 > 49

    eng = _engine("spans-outlive")
    eng.submit(np.arange(1, 6), max_new_tokens=3, warmup=True)
    eng.run()
    n_steps = eng._n_steps
    del eng
    gc.collect()
    assert len(tracing.ring("spans-outlive", "steps")) == n_steps
    (request,) = tracing.ring("spans-outlive", "requests")
    assert request["warmup"] and request["finish"] is not None


def test_a_step_reads_its_clock_a_dozen_times():
    clock = CountingClock()
    eng = _engine("spans-count", clock=clock)
    eng.submit(np.arange(1, 8), max_new_tokens=12)
    eng.step()                       # admits and prefills
    eng.step()                       # the first decode program goes out
    for _ in range(3):
        before = clock.reads
        eng.step()                   # decodes only: nothing admitted or done
        # one pair each for serve.step, admit, ensure_blocks, dispatch,
        # wait and emit, and the health hook's one
        assert clock.reads - before == 13
    before = clock.reads
    eng.submit(np.arange(1, 5), max_new_tokens=4)    # submit time: 1
    eng.step()
    # the admission: admit time, the prefill's pair, the pair round the
    # wait for its logits, the first token: 6
    assert clock.reads - before == 1 + 13 + 6


def test_to_static_keeps_a_step_record_a_call():
    step, ids = _gpt_step()
    ring = tracing.ring("jit.tiny_train_step", "steps")
    ring.clear()
    for _ in range(3):
        float(step(ids, ids))
    records = list(ring)
    assert len(records) == 3
    for r in records:
        assert set(r) == {"begin", "end", "seconds"}
        assert tuple(r["seconds"]) == ("lookup", "state", "dispatch",
                                       "writeback", "other")
        assert all(v >= 0 for v in r["seconds"].values())
        assert sum(r["seconds"].values()) == pytest.approx(
            r["end"] - r["begin"], abs=1e-9)


# --------------------------------------------------------------------------
# 3. named scopes in the lowered programs, and the join
# --------------------------------------------------------------------------
def test_lowered_train_step_holds_layer_backward_and_optimizer_scopes():
    step, ids = _gpt_step()
    float(step(ids, ids))
    text = step.lowered()[0].as_text(debug_info=True)
    for scope in ("gptforcausallm/gpt/layers.0/attn/qkv_proj",
                  "gptforcausallm/gpt/layers.1/linear1",
                  "gptforcausallm/gpt/wte",
                  "bwd/gptforcausallm/gpt/layers.1/attn/out_proj",
                  "bwd/gptforcausallm/gpt/layers.0/linear2",
                  "optimizer/"):
        assert scope in text, scope
    assert "layers.2" not in text


def test_lowered_decode_and_prefill_programs_hold_the_hand_made_scopes():
    eng = _engine("spans-lowered")
    lowered = eng.lowered(prompt_lens=(3, 7, 12))
    assert sorted(lowered) == ["decode", "prefill.16", "prefill.8"]
    decode = lowered["decode"].as_text(debug_info=True)
    prefill = lowered["prefill.8"].as_text(debug_info=True)
    names = ["embed", "final_norm", "head"] + [
        f"layer{i}/{part}" for i in (0, 1)
        for part in ("qkv", "scatter_kv", "attn", "out", "ffn")]
    for text in (decode, prefill):
        for scope in names:
            assert f"/{scope}/" in text, scope
        assert "layer2/" not in text
    assert "/sample/" in decode


def test_a_list_names_its_layers_whenever_they_join_it():
    import jax
    import paddle_tpu.nn as nn

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.blocks = nn.LayerList()            # attached empty,
            for _ in range(3):                      # filled afterwards
                self.blocks.append(nn.Linear(4, 4))
            self.blocks.insert(0, nn.Linear(4, 4))

        def forward(self, x):
            for blk in self.blocks[1:]:             # a slice renames nothing
                x = blk(x)
            return x

    net = Net()
    text = jax.jit(lambda x: net(paddle.Tensor(x))._value).lower(
        np.zeros((2, 4), np.float32)).as_text(debug_info=True)
    for i in (1, 2, 3):
        assert f"net/blocks.{i}/" in text, i
    assert "blocks.0" not in text


HLO = """
HloModule jit__decode_impl, entry_computation_layout={()->()}

%fused_computation (param_0.9: bf16[12,94208,128]) -> bf16[12,94208,128] {
  %param_0.9 = bf16[12,94208,128]{2,0,1} parameter(0)
  ROOT %scatter.9 = bf16[12,94208,128]{2,0,1} scatter(%param_0.9), metadata={op_name="jit(_decode_impl)/jit(main)/layer0/scatter_kv/scatter"}
}

ENTRY %main.32 (caches_0__0_.1: bf16[12,736,128,128]) -> bf16[12,736,128,128] {
  %caches_0__0_.1 = bf16[12,736,128,128]{3,2,1,0} parameter(0), metadata={op_name="caches[0][0]"}
  %copy.32 = bf16[12,736,128,128]{3,0,2,1} copy(%caches_0__0_.1), metadata={op_name="caches[0][0]"}
  %bitcast.4 = bf16[12,94208,128]{2,0,1} bitcast(%copy.32), metadata={op_name="jit(_decode_impl)/jit(main)/layer0/scatter_kv/reshape"}
  %fusion.3 = bf16[12,94208,128]{2,0,1} fusion(%bitcast.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_decode_impl)/jit(main)/layer0/scatter_kv/scatter"}
  %bitcast.5 = bf16[12,736,128,128]{3,0,2,1} bitcast(%fusion.3)
  %copy.34 = bf16[12,736,128,128]{3,2,1,0} copy(%bitcast.5), backend_config={"x":"y"}
  %fusion.7 = bf16[128,6144]{1,0} fusion(%copy.34), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(_decode_impl)/jit(main)/layer1/ffn/dot_general"}
  ROOT %paged_decode.1 = bf16[128,12,128]{2,1,0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_impl)/jit(main)/layer1/attn/paged_decode/pallas_call"}
}
"""


def test_scope_of_cuts_wrappers_and_the_primitive():
    assert scope_of("jit(step)/jit(main)/gpt/layers.3/attn/dot_general") \
        == "gpt/layers.3/attn"
    assert scope_of("jit(f)/transpose(jvp(model))/blk/mul") == "blk"
    assert scope_of("jit(f)/jit(main)/add") == "(no scope)"
    assert scope_of("caches[3][0]") == "(caches[3][0])"


def test_scope_seconds_joins_traced_instructions_with_the_text():
    seconds = {"%copy.32": 0.040, "copy.34": 0.030, "%fusion.3": 0.002,
               "%fusion.7": 0.010, "%paged_decode.1": 0.020,
               "%copy.99": 0.001}
    assert scope_seconds(HLO, seconds) == {
        # the copy the compiler added with no metadata is billed to the
        # scope of the value it moves (bitcast.5 <- fusion.3)
        "(caches[0][0])": pytest.approx(0.040),
        "layer0/scatter_kv": pytest.approx(0.032),
        "layer1/attn/paged_decode": pytest.approx(0.020),
        "layer1/ffn": pytest.approx(0.010),
        "(no scope)": pytest.approx(0.001),
    }
    assert list(scope_seconds(HLO, seconds))[0] == "(caches[0][0])"
    copies = {k: v for k, v in seconds.items() if "copy" in k}
    assert scope_seconds(HLO, copies) == {
        "(caches[0][0])": pytest.approx(0.040),
        "layer0/scatter_kv": pytest.approx(0.030),
        "(no scope)": pytest.approx(0.001)}


def test_the_scope_tool_joins_traced_programs_with_their_texts():
    from benchmark.harness import trace
    from tools import scope_breakdown

    def ev(line, name, start, dur, plane="/device:TPU:0"):
        return trace.Event(plane, line, name, start, dur)

    ops, mods = trace.OPS_LINE, trace.MODULES_LINE
    events = [
        ev(mods, "jit__decode_impl(77)", 0, 100e6),
        ev(ops, "%copy.32 = bf16[12,736,128,128]{3,0,2,1} copy(...)", 1e6,
           40e6),
        ev(ops, "%fusion.3 = bf16[12,94208,128] fusion(...)", 41e6, 2e6),
        ev(ops, "%copy.34 = bf16[12,736,128,128] copy(...)", 43e6, 30e6),
        ev(mods, "jit__decode_impl(77)", 200e6, 100e6),
        ev(ops, "%copy.32 = bf16[12,736,128,128]{3,0,2,1} copy(...)", 201e6,
           40e6),
        ev(mods, "jit__other(5)", 400e6, 10e6),
        ev(ops, "%fusion.1 = f32[] fusion(...)", 401e6, 1e6),
        ev(ops, "%copy.32 = ...", 1e6, 40e6, plane="/device:TPU:1"),
        ev("python", "bench.step", 0, 500e6, plane="/host:CPU"),
    ]
    programs = scope_breakdown.by_program(events, trace)
    assert dict(programs["jit__decode_impl(77)"]) == {
        "copy.32": pytest.approx(0.080), "fusion.3": pytest.approx(0.002),
        "copy.34": pytest.approx(0.030)}
    out = scope_breakdown.join(programs, {"decode": HLO})
    assert list(out) == ["jit__decode_impl(77)"]       # no text for jit__other
    rep = out["jit__decode_impl(77)"]
    assert rep["text"] == "decode"
    assert rep["device_s"] == pytest.approx(0.112)
    assert rep["copy"] == {"(caches[*][*])": pytest.approx(0.080),
                           "layer*/scatter_kv": pytest.approx(0.030)}
    assert rep["fusion"] == {"layer*/scatter_kv": pytest.approx(0.002)}
    assert rep["by_scope"] == {"(caches[*][*])": pytest.approx(0.080),
                               "layer*/scatter_kv": pytest.approx(0.032)}


def test_record_event_annotates_without_the_programs_profiler():
    from paddle_tpu import profiler

    assert not profiler.in_profiler_mode()
    rec = profiler.RecordEvent("outside.any.profiler", attrs={"k": 1})
    rec.begin()
    assert rec._jax_ann is not None
    rec.annotate(late=2)
    rec.end()
    assert rec._jax_ann is None
    with obs.span("a.moment", clock=obs.FakeClock(tick=0.5), k=1) as sp:
        sp.note(late=2)
    assert (sp.start, sp.end, sp.seconds) == (0.0, 0.5, 0.5)
