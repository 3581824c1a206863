"""KV-cache incremental decoding (models/generation.py).

Reference model: PaddleNLP generate() over the serving decode ops the
core repo ships (masked_multihead_attention single-step decode). The
gate here: the cached single-jit scan must reproduce the MODEL'S OWN
full-prefix forward token for token — any drift between the decode
mirror and models/llama.py fails the greedy oracle test.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def _model(**kw):
    paddle.seed(3)
    cfg = LlamaConfig.tiny(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _oracle_greedy(model, ids_np, n_new):
    """Full-prefix recompute each step through the model's own forward."""
    ids = ids_np.copy()
    for _ in range(n_new):
        logits = model(paddle.to_tensor(ids)).numpy()
        nxt = logits[:, -1, :].argmax(-1).astype("int64")
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    return ids


class TestGreedyDecoding:
    def test_cached_logits_match_full_prefix_oracle(self):
        """Teacher-forced: at every step the cached single-token forward
        must reproduce the model's full-prefix logits (tolerance covers
        reduction-order noise; a wrong position/mask/cache slot shifts
        logits by O(1) and fails loudly). Token argmax is asserted
        whenever the oracle's top-2 margin clears the noise floor."""
        import jax.numpy as jnp

        from paddle_tpu.models.generation import _cached_forward

        model = _model()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 97, (2, 7)).astype("int64")
        n_new = 9
        oracle_ids = _oracle_greedy(model, ids, n_new)

        p = model.decode_view()
        s_max = ids.shape[1] + n_new
        caches = [(jnp.zeros((2, s_max, 2, 8), jnp.float32),
                   jnp.zeros((2, s_max, 2, 8), jnp.float32))
                  for _ in range(len(p["layers"]))]
        hid, caches = _cached_forward(
            p, jnp.asarray(ids, jnp.int32), caches, 0, s_max)
        for step in range(n_new):
            pos = ids.shape[1] + step
            ref = model(paddle.to_tensor(oracle_ids[:, :pos])).numpy()[:, -1]
            mine = np.asarray(hid @ p["head"])
            np.testing.assert_allclose(mine, ref, atol=0.05, rtol=0.02,
                                       err_msg=f"step {step}")
            srt = np.sort(ref, -1)
            margin = srt[:, -1] - srt[:, -2]
            clear = margin > 0.05
            if clear.any():
                np.testing.assert_array_equal(
                    mine.argmax(-1)[clear], ref.argmax(-1)[clear],
                    err_msg=f"step {step} argmax (clear margins)")
            # teacher-force the ORACLE token so divergence can't cascade
            tok = oracle_ids[:, pos].astype("int32")
            hid, caches = _cached_forward(
                p, jnp.asarray(tok[:, None]), caches, pos, s_max)

    def test_generate_multi_token_matches_oracle(self):
        """End-to-end generate(): EVERY generated token must match the
        full-prefix oracle wherever the oracle's top-2 margin clears the
        float-noise floor (an off-by-one in the decode position produced
        clear-margin divergence at token 3 — round-4 review catch)."""
        model = _model()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 97, (2, 7)).astype("int64")
        n_new = 8
        want = _oracle_greedy(model, ids, n_new)
        got = model.generate(paddle.to_tensor(ids),
                             max_new_tokens=n_new).numpy()
        assert got.shape == (2, 7 + n_new)
        walk = ids.copy()
        for step in range(n_new):
            logits = model(paddle.to_tensor(walk)).numpy()[:, -1]
            srt = np.sort(logits, -1)
            clear = (srt[:, -1] - srt[:, -2]) > 0.05
            pos = 7 + step
            if clear.any():
                np.testing.assert_array_equal(
                    got[clear, pos], want[clear, pos],
                    err_msg=f"token {step} (clear margin)")
            # continue the walk along the ORACLE sequence
            walk = want[:, :pos + 1]

    def test_generate_zero_new_tokens_returns_prompt(self):
        model = _model()
        ids = np.array([[1, 2, 3]], dtype="int64")
        out = model.generate(paddle.to_tensor(ids),
                             max_new_tokens=0).numpy()
        np.testing.assert_array_equal(out, ids)

    def test_gqa_and_single_batch(self):
        model = _model()
        ids = np.array([[5, 11, 3]], dtype="int64")
        want = _oracle_greedy(model, ids, 1)
        got = model.generate(paddle.to_tensor(ids), max_new_tokens=5).numpy()
        np.testing.assert_array_equal(got[:, :4], want)

    def test_eos_masks_tail(self):
        model = _model()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, 97, (1, 4)).astype("int64")
        # find the first greedy token and use IT as eos: everything
        # after must be eos too
        first = _oracle_greedy(model, ids, 1)[0, -1]
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             eos_token_id=int(first)).numpy()
        assert (out[0, 4:] == first).all()

    def test_prompt_is_preserved(self):
        model = _model()
        ids = np.array([[1, 2, 3, 4]], dtype="int64")
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=2).numpy()
        np.testing.assert_array_equal(out[:, :4], ids)
        assert out.shape == (1, 6)


class TestSampling:
    def test_seed_reproducible_and_temperature_valid(self):
        model = _model()
        ids = np.array([[9, 8, 7]], dtype="int64")
        a = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                           do_sample=True, temperature=1.3, seed=5).numpy()
        b = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                           do_sample=True, temperature=1.3, seed=5).numpy()
        c = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                           do_sample=True, temperature=1.3, seed=6).numpy()
        np.testing.assert_array_equal(a, b)
        assert (a >= 0).all() and (a < 97).all()
        assert not np.array_equal(a, c) or True  # different seed MAY differ

    def test_top_k_1_equals_greedy(self):
        model = _model()
        ids = np.array([[4, 4, 2, 30]], dtype="int64")
        greedy = model.generate(paddle.to_tensor(ids),
                                max_new_tokens=5).numpy()
        topk1 = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                               do_sample=True, top_k=1, seed=0).numpy()
        np.testing.assert_array_equal(greedy, topk1)

    def test_top_p_tiny_equals_greedy(self):
        model = _model()
        ids = np.array([[10, 20], [30, 40]], dtype="int64")
        greedy = model.generate(paddle.to_tensor(ids),
                                max_new_tokens=4).numpy()
        topp = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                              do_sample=True, top_p=1e-6, seed=0).numpy()
        np.testing.assert_array_equal(greedy, topp)

    def test_ragged_input_rejected(self):
        model = _model()
        with pytest.raises(ValueError, match="batch"):
            model.generate(paddle.to_tensor(
                np.array([1, 2, 3], dtype="int64")), max_new_tokens=2)


class TestGPTGeneration:
    """The family dispatch: GPT (learned positions, pre-LN, fused qkv,
    tied/untied head) decodes through the same single-jit scan."""

    def _gpt(self, tie=False):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(4)
        cfg = GPTConfig.tiny(vocab_size=89, hidden_size=32,
                             num_hidden_layers=2, num_attention_heads=4,
                             intermediate_size=64,
                             max_position_embeddings=64,
                             tie_word_embeddings=tie,
                             hidden_dropout_prob=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return m

    @pytest.mark.parametrize("tie", [False, True])
    def test_multi_token_matches_oracle(self, tie):
        model = self._gpt(tie)
        rng = np.random.RandomState(2)
        ids = rng.randint(0, 89, (2, 6)).astype("int64")
        n_new = 6
        want = _oracle_greedy(model, ids, n_new)
        got = model.generate(paddle.to_tensor(ids),
                             max_new_tokens=n_new).numpy()
        assert got.shape == (2, 6 + n_new)
        walk = ids.copy()
        for step in range(n_new):
            logits = model(paddle.to_tensor(walk)).numpy()[:, -1]
            srt = np.sort(logits, -1)
            clear = (srt[:, -1] - srt[:, -2]) > 0.05
            pos = 6 + step
            if clear.any():
                np.testing.assert_array_equal(
                    got[clear, pos], want[clear, pos],
                    err_msg=f"token {step} (clear margin)")
            walk = want[:, :pos + 1]

    def test_unsupported_family_rejected(self):
        from paddle_tpu.models import BertConfig, BertForPretraining

        m = BertForPretraining(BertConfig.tiny())
        from paddle_tpu.models.generation import generate

        with pytest.raises(TypeError, match="families"):
            generate(m, np.array([[1, 2]], dtype="int64"),
                     max_new_tokens=2)

    def test_position_table_overflow_rejected(self):
        model = self._gpt()
        ids = np.zeros((1, 60), dtype="int64")
        with pytest.raises(ValueError, match="position"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=32)


class TestRaggedPrompts:
    """Left-padded mixed-length prompts: each row must decode exactly as
    if it were generated ALONE with its unpadded prompt (per-row rope
    offsets + pad-aware visibility) — round-4 verdict Missing #3."""

    def _ragged_batch(self, model, pad=0, lens=(4, 7, 2), t0=7, n_new=6):
        rng = np.random.RandomState(5)
        rows, singles = [], []
        for i, ln in enumerate(lens):
            real = rng.randint(1, 97, (ln,)).astype("int64")
            rows.append(np.concatenate(
                [np.full(t0 - ln, pad, "int64"), real]))
            singles.append(real)
        return np.stack(rows), singles

    def test_each_row_matches_its_solo_decode(self):
        model = _model()
        pad = 0
        batch, singles = self._ragged_batch(model, pad=pad)
        n_new = 6
        out = model.generate(paddle.to_tensor(batch), max_new_tokens=n_new,
                             pad_token_id=pad).numpy()
        t0 = batch.shape[1]
        for i, real in enumerate(singles):
            solo = model.generate(paddle.to_tensor(real[None, :]),
                                  max_new_tokens=n_new).numpy()[0]
            np.testing.assert_array_equal(
                out[i, t0:], solo[len(real):],
                err_msg=f"row {i} (len {len(real)}) diverged from its "
                        f"solo decode")

    def test_ragged_sampling_runs_and_respects_seed(self):
        model = _model()
        batch, _ = self._ragged_batch(model)
        a = model.generate(paddle.to_tensor(batch), max_new_tokens=4,
                           pad_token_id=0, do_sample=True, seed=9).numpy()
        b = model.generate(paddle.to_tensor(batch), max_new_tokens=4,
                           pad_token_id=0, do_sample=True, seed=9).numpy()
        np.testing.assert_array_equal(a, b)

    def test_right_padding_rejected(self):
        model = _model()
        bad = np.array([[5, 6, 0, 0], [1, 2, 3, 4]], dtype="int64")
        with pytest.raises(ValueError, match="LEFT-padded"):
            model.generate(paddle.to_tensor(bad), max_new_tokens=2,
                           pad_token_id=0)

    def test_all_pad_row_rejected(self):
        model = _model()
        bad = np.array([[0, 0, 0], [1, 2, 3]], dtype="int64")
        with pytest.raises(ValueError, match="entirely padding"):
            model.generate(paddle.to_tensor(bad), max_new_tokens=2,
                           pad_token_id=0)

    def test_unpadded_batch_with_pad_id_matches_plain(self):
        """pad_token_id on a batch with no actual pads must be a no-op."""
        model = _model()
        ids = np.random.RandomState(6).randint(1, 97, (2, 5)).astype("int64")
        plain = model.generate(paddle.to_tensor(ids),
                               max_new_tokens=4).numpy()
        with_pad = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                                  pad_token_id=0).numpy()
        np.testing.assert_array_equal(plain, with_pad)


def _served(model, prompts, n_new, *, name, block_size=4, num_blocks=None,
            max_seq_len=32, eos=None, temperature=0.0, seed=0):
    """Each prompt as a request of its own through ``ServeEngine`` (the
    one paged decoder); returns the generated tokens a request."""
    from paddle_tpu.serve import ServeEngine

    per_seq = -(-max_seq_len // block_size)
    eng = ServeEngine(model, max_slots=len(prompts), block_size=block_size,
                      num_blocks=num_blocks or per_seq * len(prompts),
                      max_seq_len=max_seq_len, seed=seed, name=name)
    reqs = [eng.submit(np.asarray(p), max_new_tokens=n_new,
                       eos_token_id=eos, temperature=temperature)
            for p in prompts]
    eng.run(max_steps=500)
    return [r.output_ids for r in reqs]


def _left_padded(rng, lengths, t0, pad=0, vocab=97):
    """(the left-padded batch, each row's real tokens)"""
    reals = [rng.randint(1, vocab, (ln,)).astype("int64") for ln in lengths]
    batch = np.stack([np.concatenate(
        [np.full(t0 - len(r), pad, "int64"), r]) for r in reals])
    return batch, reals


class TestPagedDecode:
    """Paged decoding has one implementation, ``serve.ServeEngine``
    (ISSUE 29 took ``generate(paged=True)`` away): what that option's
    tests held — paged equals dense, token for token — is held here for
    the engine, against the dense ``generate()``."""

    def test_paged_equals_dense_greedy(self):
        model = _model()
        ids = np.random.RandomState(7).randint(1, 97, (2, 7)).astype("int64")
        dense = model.generate(paddle.to_tensor(ids),
                               max_new_tokens=6).numpy()
        paged = _served(model, list(ids), 6, name="gen-greedy")
        np.testing.assert_array_equal(np.asarray(paged), dense[:, 7:])

    def test_paged_ragged_equals_dense_ragged(self):
        """The ragged batch's rows as separate requests: a stream holds
        no pad, so each equals its row of the left-padded dense call."""
        model = _model()
        batch, reals = _left_padded(np.random.RandomState(8), (3, 6), 6)
        dense = model.generate(paddle.to_tensor(batch), max_new_tokens=5,
                               pad_token_id=0).numpy()
        paged = _served(model, reals, 5, name="gen-ragged")
        np.testing.assert_array_equal(np.asarray(paged), dense[:, 6:])

    def test_paged_eos_and_sampling(self):
        model = _model()
        ids = np.random.RandomState(9).randint(1, 97, (2, 4)).astype("int64")
        a = _served(model, list(ids), 4, name="gen-sample-a",
                    temperature=1.0, seed=3)
        b = _served(model, list(ids), 4, name="gen-sample-b",
                    temperature=1.0, seed=3)
        assert a == b
        # eos must actually FIRE on the paged path: pick the token the
        # model greedily emits second, make it eos; the dense path masks
        # the tail after its first occurrence to eos, a served stream
        # ends with it
        t0 = ids.shape[1]
        free = model.generate(paddle.to_tensor(ids),
                              max_new_tokens=6).numpy()
        eos = int(free[0, t0 + 1])
        dense = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                               eos_token_id=eos).numpy()
        paged = _served(model, list(ids), 6, name="gen-eos", eos=eos)
        assert paged[0][-1] == eos and len(paged[0]) < 6, paged[0]
        for row, out in zip(dense[:, t0:], paged):
            assert list(row[:len(out)]) == out
            assert (row[len(out):] == eos).all(), row

    def test_gpt_paged_equals_dense(self):
        """The engine serves GPT too: learned positions are added at
        the embedding by LOGICAL position and no layer rotates — greedy
        output (incl. a ragged batch's rows) must equal dense."""
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        gpt = GPTForCausalLM(GPTConfig.tiny(
            vocab_size=89, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=32, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        gpt.eval()
        ids = np.random.RandomState(11).randint(
            1, 89, (2, 6)).astype("int64")
        dense = gpt.generate(paddle.to_tensor(ids),
                             max_new_tokens=5).numpy()
        paged = _served(gpt, list(ids), 5, name="gen-gpt")
        np.testing.assert_array_equal(np.asarray(paged), dense[:, 6:])
        ragged = ids.copy()
        ragged[0, :2] = 0
        dr = gpt.generate(paddle.to_tensor(ragged), max_new_tokens=5,
                          pad_token_id=0).numpy()
        pr = _served(gpt, [ragged[0, 2:], ragged[1]], 5,
                     name="gen-gpt-ragged")
        np.testing.assert_array_equal(np.asarray(pr), dr[:, 6:])


class TestPagedBlockBoundaries:
    """ISSUE 14 satellite, through ``ServeEngine`` since ISSUE 29: paged
    == dense exactly at block-boundary prompt lengths (the off-by-one
    surface: a prompt that underfills, exactly fills, or just overflows
    its first block), for aligned AND ragged batches, plus the
    loud-failure contracts (pool exhaustion, refused constructions)."""

    BLOCK = 4

    @pytest.mark.parametrize("t0", [BLOCK - 1, BLOCK, BLOCK + 1])
    def test_boundary_prompt_lengths_match_dense(self, t0):
        model = _model()
        ids = np.random.RandomState(20 + t0).randint(
            1, 97, (2, t0)).astype("int64")
        dense = model.generate(paddle.to_tensor(ids),
                               max_new_tokens=6).numpy()
        paged = _served(model, list(ids), 6, name=f"gen-edge{t0}",
                        block_size=self.BLOCK)
        np.testing.assert_array_equal(np.asarray(paged), dense[:, t0:])

    def test_boundary_ragged_batches_match_dense(self):
        """Streams whose lengths straddle the block boundary, decoding
        side by side: block-1, block and block+1 real tokens, each
        against its row of the left-padded dense batch."""
        model = _model()
        t0 = self.BLOCK + 1
        batch, reals = _left_padded(
            np.random.RandomState(30), range(self.BLOCK - 1, t0 + 1), t0)
        dense = model.generate(paddle.to_tensor(batch), max_new_tokens=5,
                               pad_token_id=0).numpy()
        paged = _served(model, reals, 5, name="gen-edge-ragged",
                        block_size=self.BLOCK)
        np.testing.assert_array_equal(np.asarray(paged), dense[:, t0:])

    def test_pool_exhaustion_raises_clear_error(self):
        """A pool too small for a request's KV working set must fail
        LOUDLY at ``submit()`` naming required vs available blocks — the
        silent alternative was a clamped block table gathering another
        row's cache."""
        from paddle_tpu.serve import ServeEngine

        model = _model()
        ids = np.random.RandomState(40).randint(
            1, 97, (2, 6)).astype("int64")
        # 6 + 5 tokens, the last never written: ceil(10 / 4) = 3 blocks
        eng = ServeEngine(model, max_slots=2, block_size=4, num_blocks=2,
                          max_seq_len=32, name="gen-exhaust")
        with pytest.raises(ValueError, match="never be admitted") as ei:
            eng.submit(ids[0], max_new_tokens=5)
        assert "needs 3 KV blocks" in str(ei.value)
        assert "whole pool is 2" in str(ei.value)
        # an exactly-sized pool decodes identically to dense
        dense = model.generate(paddle.to_tensor(ids),
                               max_new_tokens=5).numpy()
        got = _served(model, list(ids), 5, name="gen-exact", num_blocks=6)
        np.testing.assert_array_equal(np.asarray(got), dense[:, 6:])

    def test_unsupported_combos_rejected_loudly(self):
        import inspect

        from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                  ExaoneMoeForCausalLM)
        from paddle_tpu.models.generation import generate
        from paddle_tpu.serve import ServeEngine

        model = _model()
        # the dense call has no paged options left to combine with
        assert not {"paged", "block_size", "num_blocks"} & set(
            inspect.signature(generate).parameters)
        assert not {"paged", "block_size", "num_blocks"} & set(
            inspect.signature(model.generate).parameters)
        # the engine's own refusals: no slot, no burst, a model that
        # hands over no decode view, a ring of window blocks shared
        with pytest.raises(ValueError, match="max_slots"):
            ServeEngine(model, max_slots=0, name="gen-bad0")
        with pytest.raises(ValueError, match="decode_burst"):
            ServeEngine(model, decode_burst=0, name="gen-bad1")
        with pytest.raises(TypeError, match="Llama, GPT and ERNIE-MoE"):
            ServeEngine(model.llama, name="gen-bad2")
        exa = ExaoneMoeForCausalLM(ExaoneMoeConfig.tiny(num_hidden_layers=2))
        with pytest.raises(NotImplementedError, match="ring"):
            ServeEngine(exa, prefix_cache=True, name="gen-bad3")


class TestGptRaggedPrompts:
    """The ragged path must also hold for learned-position models: the
    wpe row is the LOGICAL position (absolute minus pad run)."""

    def test_each_row_matches_its_solo_decode(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(4)
        gpt = GPTForCausalLM(GPTConfig.tiny(
            vocab_size=89, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=32, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        gpt.eval()
        rng = np.random.RandomState(10)
        t0, n_new, pad = 6, 5, 0
        rows, singles = [], []
        for ln in (2, 6, 4):
            real = rng.randint(1, 89, (ln,)).astype("int64")
            rows.append(np.concatenate(
                [np.full(t0 - ln, pad, "int64"), real]))
            singles.append(real)
        batch = np.stack(rows)
        out = gpt.generate(paddle.to_tensor(batch), max_new_tokens=n_new,
                           pad_token_id=pad).numpy()
        for i, real in enumerate(singles):
            solo = gpt.generate(paddle.to_tensor(real[None, :]),
                                max_new_tokens=n_new).numpy()[0]
            np.testing.assert_array_equal(
                out[i, t0:], solo[len(real):],
                err_msg=f"gpt row {i} (len {len(real)}) diverged")


class TestDtypeSwitch:
    def test_generate_after_dtype_cast_does_not_reuse_stale_closure(self):
        """The per-model jit cache keys on dtype: float32 generate →
        model.bfloat16() → generate again must retrace (the closed-over
        KV-cache dtype would otherwise mismatch the new k/v arrays)."""
        model = _model()
        ids = np.random.RandomState(12).randint(
            1, 97, (1, 4)).astype("int64")
        out32 = model.generate(paddle.to_tensor(ids),
                               max_new_tokens=3).numpy()
        model.bfloat16()
        out16 = model.generate(paddle.to_tensor(ids),
                               max_new_tokens=3).numpy()
        assert out32.shape == out16.shape == (1, 7)
        np.testing.assert_array_equal(out32[:, :4], out16[:, :4])


class TestBeamSearch:
    """num_beams decode (reference surface: nn/decode.py
    BeamSearchDecoder; ecosystem generate(decode_strategy=
    'beam_search')). The oracle is a NUMPY beam search driven by the
    model's own full-prefix forward — any drift in expansion order,
    cache reordering, or eos freezing diverges from it."""

    def _np_beam_oracle(self, model, ids_np, n_new, K, eos=-1):
        b, t0 = ids_np.shape
        out = []
        for r in range(b):
            logits = model(paddle.to_tensor(
                ids_np[r][None, :])).numpy()[0, -1]
            lp = logits - np.log(np.exp(logits - logits.max()).sum()) \
                - logits.max()
            order = np.argsort(-lp)[:K]
            beams = [(float(lp[t]), list(ids_np[r]) + [int(t)],
                      int(t) == eos) for t in order]
            for _ in range(n_new - 1):
                cand = []
                for score, seq, done in beams:
                    if done:
                        cand.append((score, seq + [eos], True))
                        continue
                    logits = model(paddle.to_tensor(
                        np.asarray(seq, "int64")[None, :])).numpy()[0, -1]
                    mx = logits.max()
                    lp = logits - (np.log(np.exp(logits - mx).sum()) + mx)
                    for t in np.argsort(-lp)[:K]:
                        cand.append((score + float(lp[t]),
                                     seq + [int(t)], int(t) == eos))
                cand.sort(key=lambda x: -x[0])
                beams = cand[:K]
            out.append(np.asarray(beams[0][1], "int64"))
        return np.stack(out)

    def test_matches_numpy_beam_oracle(self):
        model = _model()
        ids = np.random.RandomState(13).randint(
            1, 97, (2, 5)).astype("int64")
        n_new, K = 4, 3
        want = self._np_beam_oracle(model, ids, n_new, K)
        got = model.generate(paddle.to_tensor(ids), max_new_tokens=n_new,
                             num_beams=K).numpy()
        np.testing.assert_array_equal(got, want)

    def test_beam_1_equals_greedy(self):
        model = _model()
        ids = np.random.RandomState(14).randint(
            1, 97, (2, 4)).astype("int64")
        greedy = model.generate(paddle.to_tensor(ids),
                                max_new_tokens=5).numpy()
        beam1 = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                               num_beams=1).numpy()
        np.testing.assert_array_equal(beam1, greedy)

    def test_beam_score_at_least_greedy(self):
        """The winning beam's sum logprob must be >= the greedy
        sequence's (beam explores a superset of greedy's prefix)."""
        model = _model()
        ids = np.random.RandomState(15).randint(
            1, 97, (1, 5)).astype("int64")
        n_new = 5

        def seq_logprob(full):
            t0 = ids.shape[1]
            score = 0.0
            for i in range(n_new):
                logits = model(paddle.to_tensor(
                    full[:, :t0 + i])).numpy()[0, -1]
                mx = logits.max()
                lp = logits - (np.log(np.exp(logits - mx).sum()) + mx)
                score += float(lp[full[0, t0 + i]])
            return score

        greedy = model.generate(paddle.to_tensor(ids),
                                max_new_tokens=n_new).numpy()
        beam = model.generate(paddle.to_tensor(ids), max_new_tokens=n_new,
                              num_beams=4).numpy()
        assert seq_logprob(beam) >= seq_logprob(greedy) - 1e-4

    def test_eos_freezes_beam(self):
        """A beam that emits eos stays frozen (tail is all eos) and its
        score stops accumulating. Choosing eos = the GREEDY first token
        makes the frozen beam the GUARANTEED winner: its score is the
        maximal single-token logprob, and every competing beam's sum
        only adds non-positive terms to a smaller first term — so the
        assertion can never pass vacuously."""
        model = _model()
        ids = np.random.RandomState(16).randint(
            1, 97, (1, 4)).astype("int64")
        greedy = model.generate(paddle.to_tensor(ids),
                                max_new_tokens=1).numpy()
        eos = int(greedy[0, 4])  # argmax first token
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             num_beams=2, eos_token_id=eos).numpy()
        row = out[0, 4:]
        assert row[0] == eos, row
        assert (row == eos).all(), row

    def test_beam_rejects_sampling_and_ragged(self):
        model = _model()
        ids = np.array([[1, 2, 3]], dtype="int64")
        with pytest.raises(ValueError, match="do_sample"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                           num_beams=2, do_sample=True)
        ragged = np.array([[0, 2, 3]], dtype="int64")
        with pytest.raises(NotImplementedError, match="dense"):
            model.generate(paddle.to_tensor(ragged), max_new_tokens=2,
                           num_beams=2, pad_token_id=0)

    def test_gpt_beam_matches_numpy_oracle(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(6)
        gpt = GPTForCausalLM(GPTConfig.tiny(
            vocab_size=89, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=32, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        gpt.eval()
        ids = np.random.RandomState(17).randint(
            1, 89, (1, 4)).astype("int64")
        want = self._np_beam_oracle(gpt, ids, 3, 2)
        got = gpt.generate(paddle.to_tensor(ids), max_new_tokens=3,
                           num_beams=2).numpy()
        np.testing.assert_array_equal(got, want)


class TestGenerationKnobs:
    """repetition_penalty / min_length / beam length_penalty (reference
    ecosystem generate knobs)."""

    def test_repetition_penalty_matches_numpy_oracle(self):
        """Greedy with the CTRL penalty must equal a numpy loop applying
        the same transform to the model's full-prefix logits (prompt
        tokens count as seen)."""
        model = _model()
        ids = np.random.RandomState(21).randint(
            1, 97, (2, 5)).astype("int64")
        rep, n_new = 1.7, 5
        got = model.generate(paddle.to_tensor(ids), max_new_tokens=n_new,
                             repetition_penalty=rep).numpy()

        walk = ids.copy()
        seen = [set(r) for r in ids]
        for step in range(n_new):
            logits = model(paddle.to_tensor(walk)).numpy()[:, -1].copy()
            for r in range(len(walk)):
                for t in seen[r]:
                    logits[r, t] = (logits[r, t] / rep
                                    if logits[r, t] > 0
                                    else logits[r, t] * rep)
            nxt = logits.argmax(-1).astype("int64")
            for r, t in enumerate(nxt):
                seen[r].add(int(t))
            walk = np.concatenate([walk, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(got, walk)

    def test_repetition_penalty_changes_output(self):
        """Sanity: a strong penalty must break the untrained model's
        repeat loop somewhere."""
        model = _model()
        ids = np.random.RandomState(22).randint(
            1, 97, (1, 4)).astype("int64")
        plain = model.generate(paddle.to_tensor(ids),
                               max_new_tokens=8).numpy()
        pen = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                             repetition_penalty=5.0).numpy()
        assert not np.array_equal(plain, pen)
        # with a huge penalty, no generated token repeats a previous one
        row = pen[0, 4:]
        assert len(set(row.tolist())) == len(row), row

    def test_min_length_blocks_eos(self):
        model = _model()
        ids = np.random.RandomState(23).randint(
            1, 97, (1, 4)).astype("int64")
        greedy = model.generate(paddle.to_tensor(ids),
                                max_new_tokens=1).numpy()
        eos = int(greedy[0, 4])  # would fire immediately
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             eos_token_id=eos, min_length=3).numpy()
        row = out[0, 4:]
        assert (row[:3] != eos).all(), row

    def test_length_penalty_normalizes_beam_scores(self):
        """lp=0 keeps the raw-sum ranking (oracle default); a large lp
        divides by len**lp, boosting the short frozen beam IF its mean
        logprob wins — assert the selection follows the normalized
        oracle recomputed in numpy."""
        model = _model()
        ids = np.random.RandomState(24).randint(
            1, 97, (1, 5)).astype("int64")
        base = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                              num_beams=3).numpy()
        lp0 = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                             num_beams=3, length_penalty=0.0).numpy()
        np.testing.assert_array_equal(base, lp0)
        # with no eos every beam has the same length: normalization is
        # rank-preserving, so the output must be unchanged
        lp1 = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                             num_beams=3, length_penalty=1.0).numpy()
        np.testing.assert_array_equal(base, lp1)

    def test_knobs_rejected_off_dense_path(self):
        model = _model()
        ids = np.array([[1, 2, 3]], dtype="int64")
        with pytest.raises(NotImplementedError, match="greedy/sampling"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                           num_beams=2, min_length=2)
        with pytest.raises(ValueError, match="> 0"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                           repetition_penalty=0.0)

    def test_length_penalty_without_beams_rejected(self):
        model = _model()
        ids = np.array([[1, 2, 3]], dtype="int64")
        with pytest.raises(ValueError, match="length_penalty"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                           length_penalty=1.0)

    def test_min_length_without_eos_rejected(self):
        """min_length works by masking eos; with eos_token_id=None it was
        a silent no-op — the module's no-silently-ignored-arguments
        posture demands a ValueError instead (ADVICE round-5)."""
        model = _model()
        ids = np.array([[1, 2, 3]], dtype="int64")
        with pytest.raises(ValueError, match="min_length"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                           min_length=2, eos_token_id=None)


class TestErnieMoeGeneration:
    """The MoE family decodes through the same cached scan: per-step
    expert routing must reproduce the model's own full-prefix forward
    token for token (EVAL routing is deterministic)."""

    def _moe_model(self):
        from paddle_tpu.models import ErnieMoeConfig, ErnieMoeForCausalLM

        paddle.seed(8)
        m = ErnieMoeForCausalLM(ErnieMoeConfig.tiny())
        m.eval()
        return m

    def test_greedy_matches_full_prefix_oracle(self):
        model = self._moe_model()
        V = model.config.vocab_size
        ids = np.random.RandomState(31).randint(
            1, V, (2, 6)).astype("int64")
        n_new = 6
        want = _oracle_greedy(model, ids, n_new)
        got = model.generate(paddle.to_tensor(ids),
                             max_new_tokens=n_new).numpy()
        # assert on clear-margin positions like the llama oracle test
        walk = ids.copy()
        for step in range(n_new):
            logits = model(paddle.to_tensor(walk)).numpy()[:, -1]
            srt = np.sort(logits, -1)
            clear = (srt[:, -1] - srt[:, -2]) > 0.05
            pos = 6 + step
            if clear.any():
                np.testing.assert_array_equal(
                    got[clear, pos], want[clear, pos],
                    err_msg=f"moe token {step} (clear margin)")
            walk = want[:, :pos + 1]

    def test_sampling_and_beam_run(self):
        model = self._moe_model()
        V = model.config.vocab_size
        ids = np.random.RandomState(32).randint(
            1, V, (1, 4)).astype("int64")
        a = model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                           do_sample=True, seed=1).numpy()
        b = model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                           do_sample=True, seed=1).numpy()
        np.testing.assert_array_equal(a, b)
        beam = model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                              num_beams=2).numpy()
        assert beam.shape == (1, 7)
        assert (beam >= 0).all() and (beam < V).all()

    def test_unsupported_combos_rejected(self):
        from paddle_tpu.models.generation import generate

        model = self._moe_model()
        ids = np.array([[0, 2, 3]], dtype="int64")
        with pytest.raises(NotImplementedError, match="expert capacity"):
            generate(model, paddle.to_tensor(ids), max_new_tokens=2,
                     pad_token_id=0)
        # (capacity over the call's tokens: not served batched either)
        from paddle_tpu.serve import ServeEngine

        with pytest.raises(NotImplementedError, match="dense path"):
            ServeEngine(model, name="gen-moe")

    def test_train_eval_mode_changes_cache_key(self):
        """The GShard capacity factor depends on gate.training and is
        baked into the jitted closure: flipping train()/eval() between
        calls must RETRACE (new cache entry), not reuse the stale
        factor."""
        model = self._moe_model()
        V = model.config.vocab_size
        ids = np.random.RandomState(33).randint(
            1, V, (1, 4)).astype("int64")
        model.generate(paddle.to_tensor(ids), max_new_tokens=2)
        n1 = len(model._generation_jit_cache)
        model.train()
        try:
            model.generate(paddle.to_tensor(ids), max_new_tokens=2)
        finally:
            model.eval()
        assert len(model._generation_jit_cache) == n1 + 1


class TestSpeculativeDecoding:
    """Draft-and-verify greedy decoding: by the acceptance rule the
    output must EXACTLY equal the target's own greedy decode — for any
    draft model, any gamma. That equality is the whole test surface."""

    def _target(self):
        return _model()

    def _draft(self):
        paddle.seed(77)  # different weights: low acceptance
        cfg = LlamaConfig.tiny(
            vocab_size=97, hidden_size=16, intermediate_size=32,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=64)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    @pytest.mark.parametrize("gamma", [1, 3, 7])
    def test_equals_target_greedy_with_weak_draft(self, gamma):
        from paddle_tpu.models.generation import generate_speculative

        target, draft = self._target(), self._draft()
        ids = np.random.RandomState(50).randint(
            1, 97, (1, 6)).astype("int64")
        want = target.generate(paddle.to_tensor(ids),
                               max_new_tokens=9).numpy()
        got = generate_speculative(target, draft, paddle.to_tensor(ids),
                                   max_new_tokens=9, gamma=gamma).numpy()
        np.testing.assert_array_equal(got, want)

    def test_equals_target_greedy_with_perfect_draft(self):
        """draft == target: every draft token is accepted (the
        all-accept + bonus-token path), output still exact."""
        from paddle_tpu.models.generation import generate_speculative

        target = self._target()
        ids = np.random.RandomState(51).randint(
            1, 97, (1, 5)).astype("int64")
        want = target.generate(paddle.to_tensor(ids),
                               max_new_tokens=8).numpy()
        got = generate_speculative(target, target, paddle.to_tensor(ids),
                                   max_new_tokens=8, gamma=4).numpy()
        np.testing.assert_array_equal(got, want)

    def test_eos_equivalence(self):
        from paddle_tpu.models.generation import generate_speculative

        target, draft = self._target(), self._draft()
        ids = np.random.RandomState(52).randint(
            1, 97, (1, 4)).astype("int64")
        greedy1 = target.generate(paddle.to_tensor(ids),
                                  max_new_tokens=1).numpy()
        eos = int(greedy1[0, 4])
        want = target.generate(paddle.to_tensor(ids), max_new_tokens=7,
                               eos_token_id=eos).numpy()
        got = generate_speculative(target, draft, paddle.to_tensor(ids),
                                   max_new_tokens=7, gamma=3,
                                   eos_token_id=eos).numpy()
        np.testing.assert_array_equal(got, want)

    def test_short_horizon_and_bad_args(self):
        from paddle_tpu.models.generation import generate_speculative

        target, draft = self._target(), self._draft()
        ids = np.random.RandomState(53).randint(
            1, 97, (1, 4)).astype("int64")
        # max_new < gamma: overshoot rounds must clip correctly
        want = target.generate(paddle.to_tensor(ids),
                               max_new_tokens=2).numpy()
        got = generate_speculative(target, draft, paddle.to_tensor(ids),
                                   max_new_tokens=2, gamma=5).numpy()
        np.testing.assert_array_equal(got, want)
        with pytest.raises(ValueError, match="batch 1"):
            generate_speculative(
                target, draft,
                paddle.to_tensor(np.ones((2, 3), "int64")),
                max_new_tokens=2)
        with pytest.raises(ValueError, match="gamma"):
            generate_speculative(target, draft, paddle.to_tensor(ids),
                                 max_new_tokens=2, gamma=0)

    def test_moe_target_rejected(self):
        from paddle_tpu.models import ErnieMoeConfig, ErnieMoeForCausalLM
        from paddle_tpu.models.generation import generate_speculative

        paddle.seed(60)
        moe = ErnieMoeForCausalLM(ErnieMoeConfig.tiny())
        moe.eval()
        ids = np.array([[1, 2, 3]], dtype="int64")
        with pytest.raises(NotImplementedError, match="dense families"):
            generate_speculative(moe, self._draft(),
                                 paddle.to_tensor(ids), max_new_tokens=2)

    def test_draft_cache_has_no_hole_after_full_round(self):
        """Round-5 review catch: the draft scan alone writes k/v only
        for [pending, d_1..d_{gamma-1}]; a fully-accepted round then
        advances PAST slot P+gamma, leaving it an unwritten-but-visible
        hole that silently corrupts every later draft proposal. The fix
        forwards d_gamma too. White-box: emulate one draft phase with
        the module's own pieces and assert slot P+gamma is written."""
        import jax.numpy as jnp

        from paddle_tpu.models.decoder_stack import \
            head_logits as _head_logits
        from paddle_tpu.models.generation import _cached_forward

        model = self._draft()
        p = model.decode_view()
        ids = np.random.RandomState(55).randint(
            1, 97, (1, 5)).astype("int64")
        t0, gamma = 5, 3
        s_max = t0 + 10
        caches = [(jnp.zeros((1, s_max, 2, 8), jnp.float32),
                   jnp.zeros((1, s_max, 2, 8), jnp.float32))
                  for _ in range(len(p["layers"]))]
        hid, caches = _cached_forward(
            p, jnp.asarray(ids, jnp.int32), caches, 0, s_max)
        pending = jnp.argmax(_head_logits(p, hid), -1).astype(jnp.int32)
        tok = pending
        for i in range(gamma):
            hid, caches = _cached_forward(
                p, tok[:, None], caches, t0 + i, s_max)
            tok = jnp.argmax(_head_logits(p, hid), -1).astype(jnp.int32)
        # the FIX: d_gamma forwarded at P+gamma (mirrors the impl)
        _h, caches = _cached_forward(
            p, tok[:, None], caches, t0 + gamma, s_max)
        k0 = np.asarray(caches[0][0])
        assert np.abs(k0[0, t0 + gamma]).sum() > 0, \
            "slot P+gamma unwritten — draft cache hole"

    def test_cross_family_draft(self):
        """The acceptance rule is family-agnostic: a LLAMA draft
        proposing for a GPT target (same vocab) must still produce
        exactly the GPT target's greedy output — each model runs its
        own cached forward inside the same loop."""
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.models.generation import generate_speculative

        paddle.seed(21)
        gpt = GPTForCausalLM(GPTConfig.tiny(
            vocab_size=97, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        gpt.eval()
        draft = self._draft()          # llama family, same vocab 97
        ids = np.random.RandomState(56).randint(
            1, 97, (1, 5)).astype("int64")
        want = gpt.generate(paddle.to_tensor(ids),
                            max_new_tokens=8).numpy()
        got = generate_speculative(gpt, draft, paddle.to_tensor(ids),
                                   max_new_tokens=8, gamma=3).numpy()
        np.testing.assert_array_equal(got, want)
