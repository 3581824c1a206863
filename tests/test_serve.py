"""Continuous-batching serving engine (paddle_tpu/serve).

The gates here are the ISSUE 14 acceptance criteria: (1) N staggered
requests with mixed lengths each reproduce their SOLO ``generate()``
stream token-for-token while sharing slots and the paged pool; (2) the
persistent compiled decode step traces exactly ONCE while slots churn
(admission, completion, preemption are jit data, not jit shapes);
(3) pool exhaustion queues/preempts loudly instead of corrupting a
gather; (4) the ``serve.`` metric subsystem records the load story
(TTFT, queue depth, preemptions, batch fill).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serve import (BlockPool, PoolExhaustedError, Request,
                              ServeEngine, run_load)


def _model(**kw):
    paddle.seed(3)
    cfg = LlamaConfig.tiny(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _gpt():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(5)
    cfg = GPTConfig.tiny(vocab_size=83, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=4,
                         max_position_embeddings=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _solo(model, prompt, n_new, **kw):
    """The oracle: the same prompt through a solo generate() call."""
    out = model.generate(paddle.to_tensor(prompt[None].astype("int64")),
                         max_new_tokens=n_new, **kw).numpy()
    return out[0, len(prompt):].tolist()


def _forward_gap(model, prompt, served):
    """By served token, how far its logit lies under the best of the
    model's own forward over prompt + served (0 where it IS the best):
    the oracle of families that ``generate()`` cannot decode alone."""
    ids = np.concatenate([prompt, served])[None].astype("int64")
    at = model(paddle.to_tensor(ids)).numpy()[0][len(prompt) - 1:-1]
    return at.max(-1) - at[np.arange(len(served)), served]


class TestBlockPool:
    def test_alloc_free_roundtrip(self):
        pool = BlockPool(8, 16)
        a = pool.alloc(3)
        assert len(a) == 3 and len(set(a)) == 3
        assert pool.free_blocks == 5 and pool.used_blocks == 3
        assert pool.occupancy == pytest.approx(3 / 8)
        pool.free(a)
        assert pool.free_blocks == 8

    def test_exhaustion_raises_clear_error(self):
        pool = BlockPool(4, 16)
        pool.alloc(3)
        with pytest.raises(PoolExhaustedError, match="exhausted"):
            pool.alloc(2)
        # failed alloc is atomic: the 1 remaining block is still free
        assert pool.free_blocks == 1
        assert pool.alloc(1)

    def test_double_free_rejected(self):
        pool = BlockPool(4, 16)
        a = pool.alloc(2)
        pool.free(a[:1])
        with pytest.raises(ValueError, match="already free"):
            pool.free(a[:1])
        with pytest.raises(ValueError, match="outside the pool"):
            pool.free([99])
        # a duplicate WITHIN one call is the same corruption (the block
        # would land on the free list twice and serve two streams)
        with pytest.raises(ValueError, match="already free"):
            pool.free([a[1], a[1]])

    def test_blocks_for_tokens(self):
        pool = BlockPool(8, 4)
        assert [pool.blocks_for_tokens(n) for n in (1, 4, 5, 8, 9)] == \
            [1, 1, 2, 2, 3]


class TestRefcountPool:
    """PR 19: the pool refcounts blocks so streams can SHARE resident
    KV (prefix cache). Three states: free, referenced (refcount >= 1),
    cached (refcount 0 but retained for prefix reuse, evictable)."""

    def test_acquire_release_refcounting(self):
        pool = BlockPool(8, 16)
        a = pool.alloc(2)
        assert all(pool.refcount(b) == 1 for b in a)
        pool.acquire(a)                  # a second stream mounts them
        assert all(pool.refcount(b) == 2 for b in a)
        assert pool.release(a) == []     # first stream finishes
        assert pool.used_blocks == 2     # still referenced by stream 2
        cached = pool.release(a, retain=a)   # last ref -> prefix cache
        assert sorted(cached) == sorted(a)
        assert pool.used_blocks == 0 and pool.cached_blocks == 2
        assert all(pool.is_cached(b) for b in a)

    def test_release_without_retain_frees(self):
        pool = BlockPool(4, 16)
        a = pool.alloc(3)
        assert pool.release(a) == []
        assert pool.free_blocks == 4 and pool.cached_blocks == 0

    def test_refcount_underflow_is_double_free(self):
        pool = BlockPool(4, 16)
        a = pool.alloc(1)
        pool.acquire(a)
        # duplicate ids WITHIN one release must pre-validate against
        # the refcount: 3 releases of a refcount-2 block is underflow
        # and the call must not partially apply
        with pytest.raises(ValueError, match="underflow"):
            pool.release(a * 3)
        assert pool.refcount(a[0]) == 2
        pool.release(a * 2)              # exactly the refcount is fine
        assert pool.free_blocks == 4
        with pytest.raises(ValueError, match="already free"):
            pool.release(a)

    def test_acquiring_a_free_block_rejected(self):
        pool = BlockPool(4, 16)
        a = pool.alloc(1)
        pool.release(a)
        with pytest.raises(ValueError, match="unallocated"):
            pool.acquire(a)
        with pytest.raises(ValueError, match="outside the pool"):
            pool.acquire([99])

    def test_cached_blocks_revive_and_eviction_respects_refs(self):
        pool = BlockPool(4, 16)
        a = pool.alloc(2)
        pool.release(a, retain=a)        # both -> cached
        pool.acquire(a[:1])              # prefix hit revives one
        assert pool.refcount(a[0]) == 1 and not pool.is_cached(a[0])
        # eviction NEVER reclaims a referenced block
        with pytest.raises(ValueError, match="refcount-0"):
            pool.reclaim(a[:1])
        pool.reclaim(a[1:])              # the still-cached one may go
        assert pool.free_blocks == 3 and pool.cached_blocks == 0
        pool.release(a[:1])
        assert pool.free_blocks == 4

    def test_alloc_never_hands_out_cached_blocks_implicitly(self):
        # cached blocks hold reusable KV: alloc() draws from the free
        # list only and reports the cached count in the exhaustion
        # error — RECLAIMING them is the eviction policy's call
        pool = BlockPool(4, 16)
        a = pool.alloc(4)
        pool.release(a, retain=a)
        assert pool.free_blocks == 0 and pool.cached_blocks == 4
        with pytest.raises(PoolExhaustedError, match="cached"):
            pool.alloc(1)
        pool.reclaim(a[:2])
        assert pool.alloc(2)


class TestSubmitValidation:
    def test_request_longer_than_max_seq_len_rejected(self):
        eng = ServeEngine(_model(), max_slots=2, block_size=4,
                          num_blocks=16, max_seq_len=16, name="val1")
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(np.arange(1, 10), max_new_tokens=10)

    def test_request_bigger_than_whole_pool_rejected(self):
        eng = ServeEngine(_model(), max_slots=2, block_size=4,
                          num_blocks=3, max_seq_len=32, name="val2")
        with pytest.raises(ValueError, match="never be admitted"):
            eng.submit(np.arange(1, 14), max_new_tokens=8)
        assert obs.registry.get("serve.requests_rejected").value(
            engine="val2", reason="pool_too_small") == 1

    def test_empty_prompt_and_bad_max_new_rejected(self):
        eng = ServeEngine(_model(), max_slots=2, block_size=4,
                          num_blocks=8, max_seq_len=32, name="val3")
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.array([], dtype=np.int32))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.arange(1, 4), max_new_tokens=0)

    def test_moe_family_rejected(self):
        from paddle_tpu.models.ernie_moe import (ErnieMoeConfig,
                                                 ErnieMoeForCausalLM)

        cfg = ErnieMoeConfig.tiny()
        moe = ErnieMoeForCausalLM(cfg)
        # refused by mechanism (an FFN kind that is not per-row), not by
        # a list of families
        with pytest.raises(NotImplementedError,
                           match="row by row.*capacity_moe"):
            ServeEngine(moe, name="valmoe")


class TestContinuousBatching:
    """The e2e acceptance gate: staggered mixed-length streams ==
    their solo generate() decodes, ONE decode trace throughout."""

    def test_staggered_streams_match_solo_generate(self):
        model = _model()
        rng = np.random.RandomState(0)
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=40, max_seq_len=40, name="e2e")
        plans = [(rng.randint(1, 97, n), k) for n, k in
                 [(7, 6), (3, 9), (11, 5), (5, 8), (9, 4)]]
        # requests 0-2 fill every slot; 3 and 4 arrive mid-flight and
        # must prefill into slots freed by finished streams
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans[:3]]
        steps = 0
        pending = list(plans[3:])
        while eng.has_work or pending:
            if pending and steps >= 2:
                p, k = pending.pop(0)
                reqs.append(eng.submit(p, max_new_tokens=k))
            eng.step()
            steps += 1
        for r, (p, k) in zip(reqs, plans):
            assert r.state == "FINISHED"
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged from its solo decode"
        # slot churn (5 streams over 3 slots) retraced NOTHING:
        assert eng.decode_traces == 1
        assert obs.registry.get("serve.decode_traces").value(
            engine="e2e") == 1
        assert obs.registry.get("serve.requests_admitted").value(
            engine="e2e") == 5
        # the telemetry story of the same run: a TTFT per stream
        # (positive — queue wait included), fill/occupancy gauges
        # labeled by engine, pool fully drained at the end
        assert obs.registry.get("serve.ttft_seconds").stats(
            engine="e2e")["count"] == 5
        for r in reqs:
            assert r.ttft is not None and r.ttft > 0
        assert obs.registry.get("serve.batch_fill").value(
            engine="e2e") is not None
        assert obs.registry.get("serve.pool_occupancy").value(
            engine="e2e") == 0.0
        assert eng.pool.used_blocks == 0


def _value(metric, **labels):
    return obs.registry.get(metric).value(**labels) or 0


def _staggered(eng, plans, first=2, every=2, **submit_kw):
    """Submit ``first`` requests, then one more every ``every`` steps
    while the engine runs: admissions land on steps that hold a decode
    program in flight."""
    reqs = [eng.submit(p, max_new_tokens=k, **submit_kw)
            for p, k in plans[:first]]
    pending = list(plans[first:])
    steps = 0
    while eng.has_work or pending:
        if pending and steps and steps % every == 0:
            p, k = pending.pop(0)
            reqs.append(eng.submit(p, max_new_tokens=k, **submit_kw))
        eng.step()
        steps += 1
        assert steps < 3000
    return reqs


class TestOverlappedDecode:
    """ISSUE 30: one decode program in flight ahead of the host. Program
    N+1 is dispatched before program N's tokens are read; every case
    still serves its solo tokens through ONE single-tick program."""

    def _plans(self, seed, shapes, vocab=97):
        rng = np.random.RandomState(seed)
        return [(rng.randint(1, vocab, n), k) for n, k in shapes]

    def _eos_in_flight(self, name):
        # stream 0 hits its eos at a decode program while the next
        # program, which still holds a row for it, is already out
        model = _model()
        plans = self._plans(21, [(6, 12), (9, 10), (4, 9), (7, 8)])
        solo = _solo(model, *plans[0])
        eos = next(t for i, t in enumerate(solo)
                   if 3 <= i <= 8 and solo.index(t) == i)
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=24, max_seq_len=32, name=name)
        first = eng.submit(plans[0][0], max_new_tokens=plans[0][1],
                           eos_token_id=int(eos))
        rest = _staggered(eng, plans[1:], first=1)
        assert first.finish_reason == "eos"
        assert first.output_ids == solo[:solo.index(eos) + 1]
        for r, (p, k) in zip(rest, plans[1:]):
            assert r.output_ids == _solo(model, p, k)
        return eng

    def _max_new_before_block_edge(self, name):
        # each stream's K/V ends exactly at a block edge (prompt +
        # outputs - 1 = 12 or 16 positions) in a pool that holds those
        # blocks and not one more: a block taken for the token after a
        # stream's last would have to be preempted for
        model = _model()
        plans = self._plans(22, [(7, 6), (9, 8), (5, 8)])
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=3 + 4 + 3, max_seq_len=20, name=name)
        reqs = _staggered(eng, plans, first=1)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k)
        assert sum(r.preemptions for r in reqs) == 0
        assert _value("serve.pipeline_drains", engine=name,
                      reason="preempt") == 0
        return eng

    def _preempt_in_flight(self, name):
        # the pool runs dry while a program is in flight: it is read
        # first, then the youngest goes back to the queue whole
        model = _model()
        plans = self._plans(1, [(10, 8), (9, 7), (5, 6)])
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=7, max_seq_len=28, name=name)
        reqs = _staggered(eng, plans, first=2)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged after {r.preemptions} preemptions"
        assert sum(r.preemptions for r in reqs) > 0
        assert reqs[0].preemptions == 0
        assert _value("serve.pipeline_drains", engine=name,
                      reason="preempt") > 0
        return eng

    def _prefix_cache(self, name):
        # a shared system prompt mounted while programs are in flight,
        # and a block-aligned repeat that copies on write
        model = _model()
        rng = np.random.RandomState(23)
        sysp = rng.randint(1, 97, 12)
        plans = [(np.concatenate([sysp, rng.randint(1, 97, n)]), k)
                 for n, k in [(5, 6), (3, 7), (7, 5)]]
        plans += [(sysp.copy(), 6), (sysp.copy(), 4)]
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=40, max_seq_len=40, name=name,
                          prefix_cache=True)
        reqs = _staggered(eng, plans, first=1, every=3)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k)
        assert _value("serve.prefix_hits", engine=name) >= 4
        assert _value("serve.cow_copies", engine=name) >= 1
        return eng

    def _window_and_full(self, name):
        # two kinds of cache: rings of 3 x 4 that wrap, and the table.
        # generate() keeps no band, so the oracle is the model's own
        # forward over prompt + served: every served token is its best
        model, _ = _family("exaone")
        plans = self._plans(24, [(5, 20), (17, 12), (9, 16), (3, 14)],
                            vocab=128)
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=40, max_seq_len=48, name=name)
        assert eng.ring_blocks == 3
        reqs = _staggered(eng, plans, first=2)
        for r, (p, k) in zip(reqs, plans):
            assert len(r.output_ids) == k
            gap = _forward_gap(model, p, r.output_ids)
            assert gap.max() < 1e-3, gap
        assert eng.window_pool.used_blocks == 0
        return eng

    def _sampled_twice(self, name):
        # temperature over 0: the same engine seed serves the same
        # tokens, whichever program a stream's rows fall into
        model = _model()
        plans = self._plans(25, [(6, 9), (4, 7), (8, 6), (5, 8)])
        outs = []
        for trial in range(2):
            eng = ServeEngine(model, max_slots=2, block_size=4,
                              num_blocks=24, max_seq_len=32, seed=11,
                              name=f"{name}{trial}")
            reqs = _staggered(eng, plans, first=2, temperature=0.8)
            assert [len(r.output_ids) for r in reqs] == \
                [k for _, k in plans]
            outs.append([r.output_ids for r in reqs])
            assert eng.decode_traces == 1
        assert outs[0] == outs[1]
        greedy = [_solo(model, p, k) for p, k in plans]
        assert outs[0] != greedy      # (it did sample)
        return eng

    @pytest.mark.parametrize("case", [
        "eos_in_flight", "max_new_before_block_edge", "preempt_in_flight",
        "prefix_cache", "window_and_full", "sampled_twice"])
    def test_overlapped_streams_serve_their_solo_tokens(self, case):
        name = f"ovl-{case}"
        eng = getattr(self, f"_{case}")(name)
        assert eng.decode_traces == 1
        assert _value("serve.decode_traces", engine=eng.name) == 1
        assert _value("serve.decode_overlapped", engine=eng.name) > 0
        assert not eng.has_work and eng._inflight is None
        assert eng.pool.used_blocks == 0

    def test_a_steady_step_dispatches_before_it_reads(self):
        eng = ServeEngine(_model(), max_slots=2, block_size=4,
                          num_blocks=11, max_seq_len=44, name="ovl-order",
                          trace=True)
        rng = np.random.RandomState(26)
        eng.submit(rng.randint(1, 97, 5), max_new_tokens=36)
        eng.run()
        lane = list(eng.tracer.decode_steps)
        # 35 programs (the first token is the prefill's), each but the
        # first dispatched before the one before it was read
        assert len(lane) == 35 == _value("serve.decode_steps",
                                         engine="ovl-order")
        assert all(b["start"] < a["end"] for a, b in zip(lane, lane[1:]))
        assert _value("serve.decode_overlapped", engine="ovl-order") == 34
        assert 34 / 35 > 0.9
        # the last program has nothing dispatched behind it
        assert _value("serve.pipeline_drains", engine="ovl-order",
                      reason="idle") == 1
        # no gap between two programs is a host stall
        assert eng.tracer.total_decode_gap == 0.0
        # a second stream that cannot fit beside the first: a preemption
        # reads the program in flight first
        a = eng.submit(rng.randint(1, 97, 9), max_new_tokens=16)
        b = eng.submit(rng.randint(1, 97, 8), max_new_tokens=16)
        eng.run(max_steps=2000)
        assert a.preemptions + b.preemptions > 0
        assert _value("serve.pipeline_drains", engine="ovl-order",
                      reason="preempt") > 0
        # every program is either dispatched behind another or follows
        # a drain (or the start of a run)
        steps = _value("serve.decode_steps", engine="ovl-order")
        drains = sum(_value("serve.pipeline_drains", engine="ovl-order",
                            reason=r) for r in ("preempt", "idle", "burst"))
        assert steps - _value("serve.decode_overlapped",
                              engine="ovl-order") <= drains + 1
        assert not eng.has_work and eng._inflight is None
        assert not eng._first_tokens

    def test_a_block_freed_under_a_program_in_flight_serves_its_next_owner(
            self):
        # by the device's order of programs: each takes the pool the one
        # before it returns, so the row that the program in flight still
        # writes for a stream that hit eos lands BEFORE anything its
        # block's next owner writes or reads
        model = _model()
        rng = np.random.RandomState(27)
        p0, p1 = rng.randint(1, 97, 6), rng.randint(1, 97, 11)
        solo = _solo(model, p0, 10)
        eos = next(t for i, t in enumerate(solo)
                   if 3 <= i <= 8 and solo.index(t) == i)
        # the pool holds one stream's blocks: the second's prompt (three
        # blocks) can only be admitted into what the first gives back
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=4, max_seq_len=20, name="ovl-freed")
        r0 = eng.submit(p0, max_new_tokens=10, eos_token_id=int(eos))
        r1 = eng.submit(p1, max_new_tokens=6)
        held = set()
        while r0.state != "FINISHED":
            held |= set(r0.blocks)
            eng.step()
            assert r1.state == "QUEUED"
        # r0 finished on a read; a program that still computes its row
        # was dispatched before that read and is unread
        assert eng._inflight is not None and r0 in eng._inflight.reqs
        assert eng.pool.used_blocks == 0
        eng.step()
        assert r1.state == "RUNNING" and set(r1.blocks) & held
        eng.run()
        assert r0.output_ids == solo[:solo.index(eos) + 1]
        assert r1.output_ids == _solo(model, p1, 6)
        assert eng.decode_traces == 1


def _family(family):
    """(model, vocabulary) of a tiny engine of each kind of per-slot
    state: GPT (one table), Llama (one table, GQA and rope), EXAONE-MoE
    (table + rings + group sizes handed back), Granite hybrid (table +
    the state arrays' slot rows)."""
    if family == "gpt":
        return _gpt(), 83
    if family == "llama":
        return _model(), 97
    paddle.seed(5)
    if family == "exaone":
        from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                  ExaoneMoeForCausalLM)

        model = ExaoneMoeForCausalLM(ExaoneMoeConfig.tiny(
            num_hidden_layers=2, sliding_window=8,
            layer_types=("sliding_attention", "full_attention")))
    else:
        # (the family's own initialisation serves one token over and
        # over: the benchmark's seeded leaves, as its own tests take them)
        from test_granite_hybrid import seeded, tiny_cfg

        return seeded(tiny_cfg(), seed=9)[0], 128
    model.eval()
    return model, 128


FAMILIES = ["gpt", "llama", "exaone", "granite"]


def _uploads(name):
    return {w: _value("serve.decode_uploads", engine=name, what=w)
            for w in ("state", "tables", "temps")}


class TestResidentDecodeState:
    """ISSUE 32: a decode dispatch sends the device only what changed.
    The slot state a program leaves is the next one's as it lies on the
    device while the same streams decode; tables and temperatures go up
    when they were written; the sampling key is split inside the program.
    Every site that makes the device's copy stale still serves the solo
    tokens, through ONE single-tick program."""

    def _solo_of(self, family, model, geo):
        """plan -> the tokens the request is served alone. GPT and Llama:
        ``generate()``'s. The others (no dense oracle: a band, a
        recurrent state): a one-slot engine's, each of them checked to be
        the model's own forward's best at its position."""
        if family in ("gpt", "llama"):
            return lambda p, k: _solo(model, p, k)
        alone = ServeEngine(model, **{**geo, "max_slots": 1,
                                      "num_blocks": 16,
                                      "prefix_cache": False},
                            name=f"alone-{family}-{geo['num_blocks']}")

        def solo(p, k):
            r = alone.submit(p, max_new_tokens=k)
            alone.run()
            gap = _forward_gap(model, p, r.output_ids)
            assert gap.max() < 1e-3, gap
            return r.output_ids
        return solo

    def _check(self, eng, reqs, solos):
        for r, want in zip(reqs, solos):
            if r.eos_token_id is not None:
                want = want[:want.index(r.eos_token_id) + 1]
            assert r.output_ids == want, f"stream {r.id} diverged"
        steps = _value("serve.decode_steps", engine=eng.name)
        up = _uploads(eng.name)
        # some programs found the device's state good, some did not
        assert 0 < up["state"] < steps
        assert 0 < up["tables"] < steps and 0 < up["temps"] < steps
        # the single-tick program traced once (a burst's scans are their
        # own programs, one a length)
        assert eng.decode_traces == 1 + len(eng.burst_lens_used)
        assert not eng.has_work and eng._inflight is None
        assert eng.pool.used_blocks == 0

    @pytest.mark.parametrize("family", FAMILIES)
    def test_churn_bursts_and_idle_spells_serve_the_solo_tokens(
            self, family):
        # staggered admissions, a finish by max_new_tokens and one by eos
        # with a program in flight, block edges (blocks of 4), a burst
        # between two single steps, an idle spell and a restart
        model, vocab = _family(family)
        geo = dict(max_slots=3, block_size=4, num_blocks=40,
                   max_seq_len=48)
        solo = self._solo_of(family, model, geo)
        rng = np.random.RandomState(32)
        plans = [(rng.randint(1, vocab, n), k) for n, k in
                 [(6, 14), (9, 12), (5, 9), (7, 10), (4, 8), (8, 7),
                  (3, 9)]]
        solos = [solo(p, k) for p, k in plans]
        # the first stream whose solo text has a token that first shows
        # after its second ends on it, the next program out already
        late = [next((t for i, t in enumerate(s)
                      if i >= 2 and s.index(t) == i), None) for s in solos]
        ends = next(i for i in range(5) if late[i] is not None)
        name = f"res-churn-{family}"
        eng = ServeEngine(model, **geo, name=name)
        pending = [(p, k, late[i] if i == ends else None)
                   for i, (p, k) in enumerate(plans[:5])]
        reqs = []
        steps = 0
        while eng.has_work or pending:
            if pending and (steps < 2 or steps % 3 == 0):
                p, k, eos = pending.pop(0)
                reqs.append(eng.submit(p, max_new_tokens=k,
                                       eos_token_id=eos))
            # one fused burst between two single steps: it reads the
            # program in flight, decodes from the host's mirrors and
            # leaves them whole for the single step after it
            eng.decode_burst = 4 if steps == 7 else 1
            eng.step()
            steps += 1
            assert steps < 500
        assert reqs[ends].finish_reason == "eos"
        assert _value("serve.burst_tokens", engine=name) > 0
        for _ in range(3):            # an idle spell
            eng.step()
        assert eng._inflight is None
        reqs += [eng.submit(p, max_new_tokens=k) for p, k in plans[5:]]
        eng.run()
        self._check(eng, reqs, solos)
        assert _value("serve.pipeline_drains", engine=name,
                      reason="burst") >= 1
        assert _value("serve.pipeline_drains", engine=name,
                      reason="idle") >= 2

    @pytest.mark.parametrize("family", ["gpt", "exaone", "granite"])
    def test_a_preemption_serves_the_solo_tokens(self, family):
        # the pool runs dry under a program in flight: it is read, the
        # youngest goes back to the queue, and its slot's rows (table,
        # ring, state) are rebuilt when it returns (Llama's:
        # TestOverlappedDecode's preempt_in_flight, the same schedule)
        model, vocab = _family(family)
        geo = dict(max_slots=2, block_size=4, num_blocks=7, max_seq_len=28)
        solo = self._solo_of(family, model, geo)
        rng = np.random.RandomState(1)
        plans = [(rng.randint(1, vocab, n), k)
                 for n, k in [(10, 8), (9, 7), (5, 6)]]
        eng = ServeEngine(model, **geo, name=f"res-preempt-{family}")
        reqs = _staggered(eng, plans, first=2)
        assert sum(r.preemptions for r in reqs) > 0
        assert _value("serve.pipeline_drains", engine=eng.name,
                      reason="preempt") > 0
        self._check(eng, reqs, [solo(p, k) for p, k in plans])

    def test_a_prefix_hit_with_copy_on_write_serves_the_solo_tokens(self):
        # blocks mounted from the prefix cache are written into the table
        # at admission like any others; the copy-on-write's program runs
        # between two decode programs (GPT; Llama's is
        # TestOverlappedDecode's prefix_cache, the same schedule; the
        # other two kinds of state refuse the prefix cache)
        family = "gpt"
        model, vocab = _family(family)
        geo = dict(max_slots=3, block_size=4, num_blocks=40,
                   max_seq_len=40)
        rng = np.random.RandomState(23)
        sysp = rng.randint(1, vocab, 12)
        plans = [(np.concatenate([sysp, rng.randint(1, vocab, n)]), k)
                 for n, k in [(5, 6), (3, 7), (7, 5)]]
        plans += [(sysp.copy(), 6), (sysp.copy(), 4)]
        eng = ServeEngine(model, **geo, prefix_cache=True,
                          name=f"res-prefix-{family}")
        reqs = _staggered(eng, plans, first=1, every=3)
        assert _value("serve.prefix_hits", engine=eng.name) >= 4
        assert _value("serve.cow_copies", engine=eng.name) >= 1
        solo = self._solo_of(family, model, geo)
        self._check(eng, reqs, [solo(p, k) for p, k in plans])

    @pytest.mark.parametrize("family", FAMILIES)
    def test_uploads_rise_by_one_an_event_and_stay_flat_between(
            self, family):
        model, vocab = _family(family)
        name = f"res-count-{family}"
        eng = ServeEngine(model, max_slots=3, block_size=16, num_blocks=12,
                          max_seq_len=48, name=name)
        rng = np.random.RandomState(33)
        r0 = eng.submit(rng.randint(1, vocab, 5), max_new_tokens=30)
        eng.step()                    # the prompt; its first token
        assert _uploads(name) == dict(state=0, tables=0, temps=0)
        eng.step()                    # the first program sends all three
        want = dict(state=1, tables=1, temps=1)
        assert _uploads(name) == want
        for _ in range(3):            # nothing comes, goes or crosses
            eng.step()
            assert _uploads(name) == want
        # an admission writes the slot's table rows (and rings) and its
        # temperature: up they go with the step's program, whose rows are
        # still the ones before
        r1 = eng.submit(rng.randint(1, vocab, 3), max_new_tokens=4,
                        temperature=0.7)
        eng.step()
        want = dict(state=1, tables=2, temps=2)
        assert _uploads(name) == want
        eng.step()                    # the new stream's first row
        want["state"] += 1
        assert _uploads(name) == want
        while r1.state != "FINISHED":
            eng.step()
        # its last program but one dropped its row (state), and its
        # finish cleared the slot (table rows, temperature): those go up
        # with the program after
        want["state"] += 1
        assert _uploads(name) == want
        eng.step()
        want = dict(state=3, tables=3, temps=3)
        assert _uploads(name) == want
        assert eng._lens[r0.slot] < 16
        while eng._lens[r0.slot] < 16:    # up to the block's edge: flat
            assert _uploads(name) == want
            eng.step()
        eng.step()                    # the edge: one new table entry
        want["tables"] += 1
        assert _uploads(name) == want and len(r0.blocks) == 2
        eng.step()
        assert _uploads(name) == want
        eng.run()
        assert eng.decode_traces == 1
        steps = _value("serve.decode_steps", engine=name)
        assert steps == 29 and _uploads(name)["state"] == 3

    def test_the_kept_tables_do_not_follow_the_schedulers_writes(self):
        # on the CPU a device array made from an aligned numpy buffer may
        # alias it: the kept copy has to be a copy, of what was sent
        eng = ServeEngine(_model(), max_slots=3, block_size=4,
                          num_blocks=24, max_seq_len=32, name="res-alias")
        rng = np.random.RandomState(34)
        eng.submit(rng.randint(1, 97, 6), max_new_tokens=20)
        for _ in range(4):
            eng.step()
        sent, (kept,) = eng._sent["tables"]
        before = np.array(kept)
        assert (before == eng._tables).all() and (sent[0] == before).all()
        eng._tables[2, :] = 9         # the scheduler writes in place
        assert (np.asarray(kept) == before).all()
        assert (sent[0] == before).all()
        n = _uploads("res-alias")["tables"]
        eng.step()                    # ... and the next program gets it
        assert _uploads("res-alias")["tables"] == n + 1
        assert (np.asarray(eng._sent["tables"][1][0])[2] == 9).all()
        eng._tables[2, :] = 0
        eng.run()
        assert eng.decode_traces == 1

    def test_tables_go_up_as_they_stand_when_asked_for(self):
        import jax
        import jax.numpy as jnp

        # with the device busy, ``jnp.array`` of the live table (what
        # ``_table_args`` did) is a program that reads the buffer after
        # the scheduler's next write wherever numpy happened to hand out
        # a 64-byte aligned one, which the CPU's device then aliases: 8
        # tries of 8 on the parent commit with the table laid so. A slot
        # cleared straight after its prefill went out (a preemption)
        # then sent the prefill's rows into another stream's block.
        eng = ServeEngine(_model(), max_slots=128, block_size=4,
                          num_blocks=16, max_seq_len=64, name="res-race")
        raw = np.zeros(eng._tables.nbytes + 64, np.uint8)
        off = (-raw.ctypes.data) % 64
        eng._tables = raw[off:off + eng._tables.nbytes].view(
            np.int32).reshape(128, 16)
        big = jnp.ones((1200, 1200))
        busy = jax.jit(lambda x: (x @ x) @ x)
        busy(big).block_until_ready()
        for trial in range(8):
            eng._tables[:] = 5 + trial
            running = busy(big)
            (handed,) = eng._table_args()
            (row,) = eng._table_args(3)
            (kept,) = eng._resident("tables", *eng._host_tables())
            eng._tables[:] = 0            # the scheduler's next write
            for got in (handed, row, kept):
                assert (np.asarray(got) == 5 + trial).all()
            running.block_until_ready()

    def test_a_sampled_run_draws_the_parents_keys(self):
        import jax

        # the program's own split is the host's chain, a link a program:
        # after N programs the engine's key is N splits on, and the
        # tokens are the ones the parent commit served (its host split
        # the key and handed the program the other half)
        model = _gpt()
        rng = np.random.RandomState(25)
        plans = [(rng.randint(1, 83, n), k)
                 for n, k in [(6, 9), (4, 7), (8, 6)]]
        eng = ServeEngine(model, max_slots=2, block_size=4, num_blocks=24,
                          max_seq_len=32, seed=11, name="res-sampled")
        reqs = _staggered(eng, plans, first=2, temperature=4.0)
        key = jax.random.PRNGKey(11)
        for _ in range(int(_value("serve.decode_steps",
                                  engine="res-sampled"))):
            key, _ = jax.random.split(key)
        assert (np.asarray(eng._key) == np.asarray(key)).all()
        assert [r.output_ids for r in reqs] == PARENT_SAMPLED
        assert eng.decode_traces == 1


#: what the commit before PR 32 served in
#: ``test_a_sampled_run_draws_the_parents_keys`` (this host's CPU)
PARENT_SAMPLED = [[8, 8, 8, 51, 51, 51, 46, 46, 19],
                  [18, 29, 29, 70, 50, 50, 50],
                  [46, 46, 35, 35, 35, 35]]


class TestPagedPagesCounters:
    """``serve.paged_pages_live / serve.paged_pages_table``: the share of
    the block table that the streams hold, which is the share of a walk
    of the whole table that ``paged_decode`` now makes."""

    def test_share_is_the_requests_lengths_over_the_table(self):
        bs, slots, max_len = 4, 3, 40
        eng = ServeEngine(_model(), max_slots=slots, block_size=bs,
                          num_blocks=40, max_seq_len=max_len, name="pages")
        rng = np.random.RandomState(3)
        plans = [(7, 6), (3, 9), (12, 5), (5, 8), (9, 4)]
        for n, k in plans:       # 5 streams over 3 slots, no preemption
            eng.submit(rng.randint(1, 97, n), max_new_tokens=k)
        eng.run(max_steps=200)
        # a prompt of n tokens makes its first token in its prefill, then
        # decodes k - 1 times at lengths n + 1 .. n + k - 1
        live = sum(-(-(n + j) // bs)
                   for n, k in plans for j in range(1, k))
        value = lambda m: obs.registry.get(m).value(engine="pages")
        steps = value("serve.decode_steps")
        assert value("serve.paged_pages_live") == live
        assert value("serve.paged_pages_table") == \
            steps * slots * (max_len // bs)
        assert 0.1 < live / (steps * slots * (max_len // bs)) < 0.5


class TestPreemptionAndQueueing:
    def test_pool_pressure_preempts_youngest_and_still_matches_solo(self):
        model = _model()
        rng = np.random.RandomState(1)
        # pool deliberately too small for both streams' full working
        # sets: the youngest must be evicted at a block boundary and
        # recompute on re-admission
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=7, max_seq_len=28, name="press")
        plans = [(rng.randint(1, 97, n), k)
                 for n, k in [(10, 8), (9, 7), (5, 6)]]
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans]
        eng.run(max_steps=2000)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged after {r.preemptions} preemptions"
        assert obs.registry.get("serve.preemptions").value(
            engine="press", reason="pool_exhausted") > 0
        # the FIRST-admitted stream is never a victim (no-livelock)
        assert reqs[0].preemptions == 0
        assert eng.decode_traces == 1
        assert eng.pool.used_blocks == 0

    def test_exhausted_pool_queues_instead_of_erroring(self):
        model = _model()
        rng = np.random.RandomState(3)
        # pool holds ~one stream's working set: later submissions WAIT
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=4, max_seq_len=16, name="queue")
        plans = [(rng.randint(1, 97, 8), 6) for _ in range(3)]
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans]
        eng.step()
        eng.step()
        # only the head fits (the second's prompt did, until the head's
        # first decode step needed the block): the rest are queued,
        # nothing raised
        assert eng.n_active == 1
        assert len(eng.queue) == 2
        assert obs.registry.get("serve.admission_stalls").value(
            engine="queue", reason="no_free_blocks") > 0
        eng.run(max_steps=2000)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k)


class TestGptServe:
    def test_gpt_streams_match_solo_generate(self):
        model = _gpt()
        rng = np.random.RandomState(4)
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=24, max_seq_len=32, name="gpt")
        prompts = [rng.randint(1, 83, n) for n in (6, 9, 4)]
        reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
        eng.run()
        for r, p in zip(reqs, prompts):
            assert r.output_ids == _solo(model, p, 7)
        assert eng.decode_traces == 1

    def test_max_seq_len_beyond_position_table_rejected(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(5)
        cfg = GPTConfig.tiny(vocab_size=83, hidden_size=32,
                             num_hidden_layers=2, num_attention_heads=4,
                             max_position_embeddings=32)
        model = GPTForCausalLM(cfg)
        model.eval()
        with pytest.raises(ValueError, match="position"):
            ServeEngine(model, max_seq_len=64, name="gptlong")


class TestEosAndSampling:
    def test_eos_finishes_stream_early(self):
        model = _model()
        rng = np.random.RandomState(6)
        p = rng.randint(1, 97, 6)
        first = _solo(model, p, 1)[0]
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=16, max_seq_len=32, name="eos")
        r = eng.submit(p, max_new_tokens=10, eos_token_id=int(first))
        eng.run()
        assert r.finish_reason == "eos"
        assert r.output_ids == [int(first)]
        assert obs.registry.get("serve.requests_finished").value(
            engine="eos", reason="eos") == 1

    def test_sampled_stream_runs_and_is_engine_seed_reproducible(self):
        model = _model()
        rng = np.random.RandomState(7)
        p = rng.randint(1, 97, 5)
        outs = []
        for trial in range(2):
            eng = ServeEngine(model, max_slots=2, block_size=4,
                              num_blocks=16, max_seq_len=32,
                              seed=11, name=f"samp{trial}")
            r = eng.submit(p, max_new_tokens=4, temperature=0.8)
            eng.run()
            assert len(r.output_ids) == 4
            assert all(0 <= t < 97 for t in r.output_ids)
            outs.append(r.output_ids)
        assert outs[0] == outs[1], \
            "same engine seed must reproduce the sampled stream"


class TestPrefixCacheServing:
    """PR 19 tentpole (a): admission matches the longest resident
    block-aligned prefix, mounts those KV blocks read-only and
    prefills ONLY the suffix — token streams must stay byte-identical
    to a cold cache (and to solo generate())."""

    def test_shared_system_prompt_streams_match_solo(self):
        model = _model()
        rng = np.random.RandomState(11)
        sysp = rng.randint(1, 97, 12)     # 3 full blocks at bs=4
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=40, max_seq_len=40, name="pfx",
                          prefix_cache=True)
        plans = [(np.concatenate([sysp, rng.randint(1, 97, n)]), k)
                 for n, k in [(5, 6), (3, 7), (7, 5)]]
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans]
        eng.run(max_steps=2000)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged under prefix sharing"
        # streams 2-3 each mounted the 3 system-prompt blocks
        assert obs.registry.get("serve.prefix_hits").value(
            engine="pfx") == 2
        assert obs.registry.get("serve.prefix_blocks_shared").value(
            engine="pfx") == 6
        # ... and prefilled only their suffixes (the TTFT win)
        assert sum(r.prefilled_tokens for r in reqs) == \
            sum(len(p) for p, _ in plans) - 6 * 4
        # at rest every reference is dropped; shared blocks stay
        # CACHED (evictable), nothing leaks as used
        assert eng.pool.used_blocks == 0
        assert eng.pool.cached_blocks > 0
        assert eng._prefix.evictable_blocks == eng.pool.cached_blocks

    def test_block_aligned_full_match_cows_not_corrupts(self):
        model = _model()
        rng = np.random.RandomState(12)
        p = rng.randint(1, 97, 8)         # exactly 2 blocks at bs=4
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=24, max_seq_len=32, name="cow",
                          prefix_cache=True)
        r1 = eng.submit(p, max_new_tokens=6)
        eng.run(max_steps=500)
        # identical prompt, block-aligned: the last matched block is
        # copy-on-write'd (its KV slot 8 belongs to the new stream's
        # first generated position) — r2 must still match r1/solo
        # (mid-prefix divergence is the property drill's job)
        r2 = eng.submit(p.copy(), max_new_tokens=6)
        eng.run(max_steps=500)
        assert r1.output_ids == r2.output_ids == _solo(model, p, 6)
        assert obs.registry.get("serve.cow_copies").value(
            engine="cow") == 1
        assert r2.prefilled_tokens == 1   # logits source token only
        assert eng.pool.used_blocks == 0

    def test_random_prefix_structure_identical_to_cold_cache(self):
        # property drill: prompts assembled from a small chunk pool so
        # arbitrary shared-prefix structure arises; the warm engine
        # must reproduce the cold engine token-for-token
        model = _model()
        rng = np.random.RandomState(13)
        chunks = [rng.randint(1, 97, 4) for _ in range(3)]
        prompts, news = [], []
        for _ in range(6):
            parts = [chunks[i]
                     for i in rng.randint(0, 3, rng.randint(1, 4))]
            parts.append(rng.randint(1, 97, rng.randint(1, 6)))
            prompts.append(np.concatenate(parts))
            news.append(int(rng.randint(3, 7)))
        outs = {}
        for on in (False, True):
            eng = ServeEngine(model, max_slots=3, block_size=4,
                              num_blocks=48, max_seq_len=40,
                              name=f"prop{int(on)}",
                              prefix_cache=on or None)
            reqs = [eng.submit(p, max_new_tokens=k)
                    for p, k in zip(prompts, news)]
            eng.run(max_steps=3000)
            outs[on] = [r.output_ids for r in reqs]
        assert outs[True] == outs[False], \
            "prefix sharing must never change a token"
        assert obs.registry.get("serve.prefix_hits").value(
            engine="prop1") > 0

    def test_eviction_under_pressure_admits_and_stays_correct(self):
        # a pool too small to cache every finished stream's blocks:
        # admission must evict refcount-0 cached blocks (never
        # referenced ones) and every stream still matches solo
        model = _model()
        rng = np.random.RandomState(14)
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=8, max_seq_len=24, name="evict",
                          prefix_cache=True)
        for i in range(3):
            p = rng.randint(1, 97, 8)
            r = eng.submit(p, max_new_tokens=5)
            eng.run(max_steps=500)
            assert r.output_ids == _solo(model, p, 5)
        assert eng.pool.used_blocks == 0
        # the cache stayed within the pool and stayed consistent
        assert eng.pool.cached_blocks <= 8
        assert eng._prefix.evictable_blocks == eng.pool.cached_blocks


class TestDecodeBursts:
    """PR 19 tentpole (b): decode_burst=N runs N decode ticks as ONE
    compiled lax.scan dispatch (in-scan sampling, eos latch, length
    advance). The bar is the same solo-equivalence gate, plus a
    bounded compile budget: one trace per pow2 burst bucket."""

    def test_burst_streams_match_solo_one_trace_per_bucket(self):
        model = _model()
        rng = np.random.RandomState(15)
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=48, max_seq_len=40, name="burst",
                          decode_burst=8)
        plans = [(rng.randint(1, 97, n), k) for n, k in
                 [(7, 9), (3, 12), (11, 6)]]
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans]
        eng.run(max_steps=2000)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged under fused bursts"
        # compile budget: exactly one scan per distinct pow2 burst
        # length the adaptive scheduler actually picked
        assert eng.decode_traces == len(eng.burst_lens_used)
        assert eng.burst_lens_used <= {1, 2, 4, 8}
        # the point of the fusion: far fewer host round-trips than
        # generated tokens (burst=1 pays one per token)
        rts = obs.registry.get("serve.host_roundtrips").value(
            engine="burst")
        toks = sum(r.n_generated for r in reqs)
        assert 0 < rts < toks
        assert obs.registry.get("serve.burst_tokens").value(
            engine="burst") == toks - len(reqs)  # first tokens: prefill

    def test_burst_under_pool_pressure_preempts_and_matches_solo(self):
        model = _model()
        rng = np.random.RandomState(1)
        # the PR-14 preemption scenario, now at burst=8: lookahead
        # allocation must degrade to shorter bursts (not preempt) when
        # the pool can't fund the full window, and preemption itself
        # must replay through the same solo-equivalent recompute path
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=7, max_seq_len=28,
                          name="burst_press", decode_burst=8)
        plans = [(rng.randint(1, 97, n), k)
                 for n, k in [(10, 8), (9, 7), (5, 6)]]
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans]
        eng.run(max_steps=2000)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged after {r.preemptions} preemptions"
        assert obs.registry.get("serve.preemptions").value(
            engine="burst_press", reason="pool_exhausted") > 0
        assert reqs[0].preemptions == 0
        assert eng.pool.used_blocks == 0

    def test_prefix_cache_and_bursts_compose(self):
        model = _model()
        rng = np.random.RandomState(17)
        sysp = rng.randint(1, 97, 8)
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=48, max_seq_len=40, name="combo",
                          prefix_cache=True, decode_burst=4)
        plans = [(np.concatenate([sysp, rng.randint(1, 97, n)]), k)
                 for n, k in [(5, 8), (3, 9)]]
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans]
        eng.run(max_steps=2000)
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged with prefix+burst combined"
        assert obs.registry.get("serve.prefix_hits").value(
            engine="combo") == 1
        assert obs.registry.get("serve.host_roundtrips").value(
            engine="combo") < sum(r.n_generated for r in reqs)
        assert eng.pool.used_blocks == 0

    def test_sampled_streams_identical_across_burst_lengths(self):
        # the burst path pre-splits the SAME per-step key schedule the
        # unbursted loop draws, so sampling composes with fusion
        model = _model()
        rng = np.random.RandomState(18)
        prompts = [rng.randint(1, 97, 6)]
        outs = {}
        for nb in (1, 2):
            eng = ServeEngine(model, max_slots=2, block_size=4,
                              num_blocks=24, max_seq_len=32, seed=11,
                              name=f"sburst{nb}", decode_burst=nb)
            reqs = [eng.submit(p, max_new_tokens=6, temperature=0.8)
                    for p in prompts]
            eng.run(max_steps=500)
            outs[nb] = [r.output_ids for r in reqs]
        assert outs[1] == outs[2], \
            "burst length must not change sampled streams"

    def test_burst_ttft_attribution_on_fakeclock(self):
        # satellite 3: TTFT attribution under bursts. The first token
        # comes from the prefill dispatch in BOTH engines and the
        # FakeClock read sequences up to it differ by one span, so burst
        # TTFT == unbursted TTFT (well within the one-step bar). A
        # stream finishing mid-burst gets the interpolated IN-SCAN
        # step-boundary timestamp, not the burst-end host time.
        model = _model()
        rng = np.random.RandomState(16)
        p = rng.randint(1, 97, 6)
        solo = _solo(model, p, 9)
        # an eos that first fires on a mid-burst decode tick
        eos = next(t for i, t in enumerate(solo)
                   if 1 <= i <= 6 and solo.index(t) == i)
        runs = {}
        for nb in (1, 8):
            clk = obs.FakeClock(tick=1e-4)
            eng = ServeEngine(model, max_slots=1, block_size=4,
                              num_blocks=16, max_seq_len=32,
                              name=f"bttft{nb}", decode_burst=nb,
                              clock=clk, trace=True)
            r = eng.submit(p, max_new_tokens=9, eos_token_id=int(eos))
            eng.run(max_steps=200)
            assert r.finish_reason == "eos"
            runs[nb] = (r, eng)
        r1, rb = runs[1][0], runs[8][0]
        assert rb.output_ids == r1.output_ids
        # (the unbursted step reads the clock twice more before the
        # first token: its ensure_blocks span comes first)
        assert rb.ttft == pytest.approx(r1.ttft, abs=2.5e-4)
        # the finishing token's timestamp sits at its in-scan step
        # boundary strictly INSIDE the fused dispatch window
        eng8 = runs[8][1]
        burst = [s for s in eng8.tracer.decode_steps
                 if s["tokens"] > 1][-1]
        n_decode = len(rb.output_ids) - 1   # first token was prefill
        per = (burst["end"] - burst["start"]) / burst["tokens"]
        assert burst["start"] < rb.finish_time < burst["end"]
        assert rb.finish_time == pytest.approx(
            burst["start"] + per * n_decode)


def _kv_paths(engine):
    c = obs.registry.get("serve.kv_write_traces")
    return {p: c.value(engine=engine, path=p) or 0
            for p in ("rows", "blocks", "reference")}


class TestKvWriteBackends:
    """ISSUE 26: the in-place K/V write (ops/pallas/kv_write.py) under
    the Pallas interpreter serves the tokens the scatter serves, token
    for token, through every compiled program that writes the pool.
    Blocks of 16 rows in float32 are two tiles: prompts up to 8 tokens
    go in by rows, longer ones by blocks."""

    GEO = dict(block_size=16, max_seq_len=64)
    SCENARIOS = {
        # five streams over three slots, then a pool too small for two
        "churn": dict(max_slots=3, num_blocks=12,
                      plans=[(7, 6), (19, 9), (11, 5), (5, 8), (33, 4)]),
        "preempt": dict(max_slots=2, num_blocks=3,
                        plans=[(14, 12), (9, 10), (5, 6)]),
        "burst4": dict(max_slots=3, num_blocks=12, decode_burst=4,
                       plans=[(7, 9), (3, 12), (21, 6)]),
        # a shared 32-token head (suffix prefill), then the first
        # prompt again, block-aligned (copy-on-write)
        "prefix": dict(max_slots=2, num_blocks=12, prefix_cache=True,
                       plans=[(32, 5), (37, 6), (40, 4), (32, 5)],
                       shared=32),
    }

    def _serve(self, model, vocab, name, backend, *, plans, shared=0,
               **geo):
        rng = np.random.RandomState(21)
        head = rng.randint(1, vocab, shared)
        eng = ServeEngine(model, name=name, attention_backend=backend,
                          **self.GEO, **geo)
        reqs = []
        for n, k in plans:
            prompt = np.concatenate(
                [head, rng.randint(1, vocab, n - shared)]).astype(np.int64)
            reqs.append(eng.submit(prompt, max_new_tokens=k))
            eng.step()                    # staggered arrivals
        eng.run(max_steps=3000)
        assert all(r.state == "FINISHED" for r in reqs)
        return eng, reqs

    @pytest.mark.parametrize("scenario", list(SCENARIOS))
    @pytest.mark.parametrize("family", ["gpt", "llama_gqa"])
    def test_interpret_serves_the_reference_tokens(self, family, scenario):
        # the Llama has 4 q heads on 2 kv heads
        model, vocab = (_gpt(), 83) if family == "gpt" else (_model(), 97)
        out = {}
        for backend in ("reference", "interpret"):
            name = f"kvw-{family}-{scenario}-{backend}"
            eng, reqs = self._serve(model, vocab, name, backend,
                                    **self.SCENARIOS[scenario])
            out[backend] = [r.output_ids for r in reqs]
            paths = _kv_paths(name)
            programs = eng.decode_traces + eng.prefill_traces
            if backend == "reference":
                assert paths == {"rows": 0, "blocks": 0,
                                 "reference": programs}
            else:
                assert paths["reference"] == 0
                assert paths["rows"] + paths["blocks"] == programs
                assert paths["rows"] >= eng.decode_traces
                assert paths["blocks"] >= 1
        assert out["interpret"] == out["reference"]
        if scenario == "preempt":
            assert sum(r.preemptions for r in reqs) > 0
        if scenario == "prefix":
            assert obs.registry.get("serve.cow_copies").value(
                engine=name) >= 1
            assert obs.registry.get("serve.prefix_hits").value(
                engine=name) >= 2

    def test_each_program_counts_its_path(self):
        """One count a traced program: the decode step, a burst and a
        suffix prefill write by rows, and so does a cold prefill under a
        block; a cold prefill of a block or more writes by blocks."""
        eng = ServeEngine(_gpt(), name="kvw-paths", max_slots=2,
                          num_blocks=8, attention_backend="interpret",
                          **self.GEO)
        eng.lowered(prompt_lens=(5, 20, 40), suffix_lens=(20,),
                    bursts=(2,), cow=True)
        assert _kv_paths("kvw-paths") == {
            "rows": 1 + 1 + 1 + 1, "blocks": 2, "reference": 0}


class TestLoadGenerator:
    def test_poisson_load_reports_latency_stats(self):
        model = _model()
        # ONE FakeClock drives both the engine timestamps and the load
        # generator's arrival schedule: every timing figure below is
        # deterministic (the tick guarantees two reads never coincide),
        # so this test cannot flake under host-scheduling jitter
        clk = obs.FakeClock(tick=1e-4)
        eng = ServeEngine(model, max_slots=3, block_size=4,
                          num_blocks=32, max_seq_len=40, name="loadgen",
                          clock=clk)
        res = run_load(eng, rate=500.0, n_requests=6, prompt_len=(3, 8),
                       max_new=(3, 6), seed=0, clock=clk)
        assert res.n_requests == 6
        assert res.total_tokens == sum(r.n_generated for r in res.requests)
        assert 0 < res.ttft_p50 <= res.ttft_p99
        assert res.tokens_per_sec > 0
        assert obs.registry.get("serve.tokens_per_sec").value(
            engine="loadgen") is not None
        d = res.to_dict()
        assert {"ttft_p50_seconds", "ttft_p99_seconds",
                "tokens_per_sec", "preemptions"} <= set(d)
        # every stream matches its solo decode even under load
        for r in res.requests:
            assert r.output_ids == _solo(model, r.prompt, r.n_generated)


class TestRequestTracing:
    """ISSUE 17 gates: per-request span trees attribute TTFT/latency to
    named lifecycle phases (~100% by construction — transitions share
    timestamps), preemption cost shows up as preempt/resume/recompute
    spans, tracing never perturbs the decoded tokens or retraces the
    decode step, and SLO breaches leave a flight dump carrying the tail
    exemplars."""

    def test_preemption_attribution_under_pool_pressure(self):
        model = _model()
        rng = np.random.RandomState(1)
        clk = obs.FakeClock(tick=1e-4)
        # the PR-14 pool-pressure scenario, now traced: the pool is too
        # small for both streams' working sets, so the youngest must be
        # evicted and pay a recompute prefill on resume
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=7, max_seq_len=28, name="tr_press",
                          clock=clk, trace=True)
        plans = [(rng.randint(1, 97, n), k)
                 for n, k in [(10, 8), (9, 7), (5, 6)]]
        reqs = [eng.submit(p, max_new_tokens=k) for p, k in plans]
        eng.run(max_steps=2000)
        # tracing is an observer: solo equivalence and the one-trace
        # invariant hold exactly as they do untraced
        for r, (p, k) in zip(reqs, plans):
            assert r.output_ids == _solo(model, p, k), \
                f"stream {r.id} diverged with tracing enabled"
        assert eng.decode_traces == 1
        assert obs.registry.get("serve.decode_traces").value(
            engine="tr_press") == 1

        docs = {d["id"]: d for d in eng.tracer.requests}
        assert set(docs) == {r.id for r in reqs}
        preempted = [r for r in reqs if r.preemptions > 0]
        assert preempted, "scenario must actually preempt"
        for r in reqs:
            d = docs[r.id]
            assert not d.get("malformed")
            # leaf phases tile submit->finish exactly: the breakdown
            # sums to the request's latency and TTFT is fully
            # attributed to named phases
            assert sum(d["breakdown"].values()) == \
                pytest.approx(d["latency_seconds"], rel=1e-6)
            assert d["latency_attributed_pct"] == pytest.approx(100.0)
            assert d["ttft_attributed_pct"] == pytest.approx(100.0)
            assert sum(d["ttft_breakdown"].values()) == \
                pytest.approx(d["ttft_seconds"], rel=1e-6)
        for r in preempted:
            d = docs[r.id]
            # every preemption episode bills all three phases
            assert {"preempt", "resume", "recompute"} <= \
                set(d["breakdown"]), d["breakdown"]
            spans = [c["name"] for c in d["spans"]["children"]]
            i = spans.index("preempt")
            assert spans[i:i + 3] == ["preempt", "resume", "recompute"]
            assert d["preemptions"] == r.preemptions
        # phase histograms recorded under the engine+phase labels
        assert obs.registry.get("trace.phase_seconds").stats(
            engine="tr_press", phase="recompute")["count"] > 0
        assert obs.registry.get("trace.spans_recorded").value(
            engine="tr_press", phase="preempt") > 0

    def test_poisson_drill_slo_breach_with_exemplars(self, tmp_path,
                                                     monkeypatch):
        """The ISSUE 17 acceptance drill: Poisson load over a pool under
        pressure, tracing + SLO rules on — worst-case TTFT >= 90%
        attributed, the slo_breach flight dump fires with exemplars
        attached, decode still traces once."""
        import json

        monkeypatch.setenv(obs.flight.FLIGHT_DIR_ENV,
                           str(tmp_path / "flight"))
        model = _model()
        clk = obs.FakeClock(tick=1e-4)
        rules = [dict(name="ttft", kind="ttft_p99", threshold=3e-3,
                      window_seconds=1e9),
                 dict(name="pool", kind="pool_exhaustion_rate",
                      threshold=0.01, window_seconds=1e9)]
        eng = ServeEngine(model, max_slots=2, block_size=4,
                          num_blocks=7, max_seq_len=28, name="drill",
                          clock=clk, trace=True, slo=rules)
        res = run_load(eng, rate=400.0, n_requests=8,
                       prompt_len=(8, 10), max_new=(5, 8), seed=2,
                       clock=clk)
        assert res.preemptions > 0, "drill must run under pool pressure"
        assert eng.decode_traces == 1

        # every worst-case exemplar attributes >= 90% of its TTFT and
        # latency to named phases (exactly 100% here — the FakeClock
        # tree is contiguous by construction)
        ex = eng.tracer.exemplars
        assert ex.worst_ttft and ex.worst_latency
        for d in ex.worst_ttft:
            assert d["ttft_attributed_pct"] >= 90.0
        for d in ex.worst_latency:
            assert d["latency_attributed_pct"] >= 90.0

        # the TTFT rule must have latched (threshold 3 ms: an unqueued
        # first token takes 1 ms of this clock's reads, FakeClock queue
        # waits are far larger) and dumped a post-mortem with the
        # exemplars riding along (the pool rule latches at the first
        # preemption, before any request has finished to be one)
        assert any(b["rule"] == "ttft" for b in eng.slo.breaches)
        assert obs.registry.get("trace.slo_breaches").value(
            engine="drill", rule="ttft") == 1
        assert any(d.code == "PTL401" for d in eng.slo.report)
        dumps = sorted((tmp_path / "flight").glob("flight-*.json"))
        assert dumps, "slo_breach flight dump did not fire"
        docs = [json.loads(p.read_text()) for p in dumps]
        breach_docs = [d for d in docs if d["reason"] == "slo_breach"]
        assert breach_docs
        assert {d["context"]["rule"] for d in breach_docs} <= {"ttft",
                                                               "pool"}
        (doc,) = [d for d in breach_docs if d["context"]["rule"] == "ttft"]
        assert doc["context"]["exemplars"]["worst_ttft"], \
            "exemplar span trees must ride the breach dump"
        # the dump renders with the interpretation + exemplar block
        text = obs.render_flight(doc)
        assert "slo_breach" in text and "tail exemplars" in text

    def test_tracing_disabled_by_default_and_env_gated(self, monkeypatch):
        model = _model()
        monkeypatch.delenv("PADDLE_TPU_TRACE", raising=False)
        monkeypatch.delenv("PADDLE_TPU_SLO", raising=False)
        eng = ServeEngine(model, max_slots=1, block_size=4,
                          num_blocks=8, max_seq_len=16, name="notrace")
        assert eng.tracer is None and eng.slo is None
        monkeypatch.setenv("PADDLE_TPU_TRACE", "1")
        monkeypatch.setenv(
            "PADDLE_TPU_SLO",
            '[{"name": "t", "kind": "ttft_p99", "threshold": 5.0}]')
        eng2 = ServeEngine(model, max_slots=1, block_size=4,
                           num_blocks=8, max_seq_len=16, name="envtrace")
        assert eng2.tracer is not None
        assert eng2.slo is not None and eng2.slo.rules[0].name == "t"
        r = eng2.submit(np.arange(1, 5), max_new_tokens=2)
        eng2.run()
        assert r.trace is not None and r.trace.finished
        assert eng2.tracer.n_traced == 1
