"""Decode-specialized paged-attention kernel equivalence
(paddle_tpu/ops/pallas/paged_attention.py).

This is tools/paged_kernel_probe.py's kernel-vs-masked-softmax
equivalence check promoted to pytest (ISSUE 14 satellite): the
CPU-runnable tier-1 gates pin the jnp reference against an independent
numpy oracle AND against the existing ``block_mha_p`` gather path (the
reference's public serving op), so the kernel's
semantics oracle is itself oracle-pinned; the Pallas kernel comparison
runs the real kernel body under the interpreter at the probe's bf16
serving shapes and is marked ``slow`` (tier-1 runs ``-m 'not slow'``;
on TPU the same test exercises the compiled kernel).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.paged_attention import (
    paged_attention_decode, paged_attention_decode_kernel,
    paged_attention_decode_reference)


def _numpy_oracle(q, kp, vp, lens, tbl):
    """Independent fp64 masked-softmax oracle over the gathered pages."""
    b, nh, dh = q.shape
    kvh, _, page, _ = kp.shape
    pps = tbl.shape[1]
    s_pad = pps * page
    group = nh // kvh
    out = np.zeros((b, nh, dh), np.float64)
    for r in range(b):
        k_rows = kp[:, tbl[r]].transpose(1, 2, 0, 3).reshape(
            s_pad, kvh, dh)
        v_rows = vp[:, tbl[r]].transpose(1, 2, 0, 3).reshape(
            s_pad, kvh, dh)
        n = int(lens[r])
        if n == 0:
            continue
        for h in range(nh):
            kh = h // group
            s = (k_rows[:n, kh] @ q[r, h]) * dh ** -0.5
            s = s - s.max()
            p = np.exp(s)
            p /= p.sum()
            out[r, h] = p @ v_rows[:n, kh]
    return out


def _case(seed=0, b=3, nh=4, kvh=2, dh=16, page=8, pps=4, npages=16,
          dtype=np.float32):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, nh, dh)).astype(dtype)
    kp = rng.normal(size=(kvh, npages, page, dh)).astype(dtype)
    vp = rng.normal(size=(kvh, npages, page, dh)).astype(dtype)
    # ragged lengths incl. a zero-length (inactive-slot) row and a
    # block-boundary length; SHUFFLED physical pages
    lens = np.array([0, page, pps * page - 3][:b], np.int32)
    if b > 3:
        lens = np.concatenate(
            [lens, rng.integers(1, pps * page, size=b - 3)]).astype(
                np.int32)
    tbl = rng.permutation(npages)[:b * pps].reshape(b, pps).astype(
        np.int32)
    return q, kp, vp, lens, tbl


class TestReference:
    """The jnp reference path — what CPU CI (and the serve engine on
    CPU) actually executes."""

    @pytest.mark.parametrize("kvh", [4, 2, 1])
    def test_matches_numpy_oracle(self, kvh):
        q, kp, vp, lens, tbl = _case(seed=kvh, b=4, nh=4, kvh=kvh)
        out = paged_attention_decode_reference(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(lens), jnp.asarray(tbl))
        ref = _numpy_oracle(q, kp, vp, lens, tbl)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=2e-5, atol=2e-5)
        assert np.all(np.asarray(out)[lens == 0] == 0.0), \
            "zero-length rows must come back 0, not NaN"

    def test_matches_block_mha_gather_path(self):
        """Bit-compatibility with the EXISTING paged gather path: one
        decode step through ``_bmha_fwd`` (the block_mha_p program of
        the public serving op) equals the new decode attention
        on the same pool state."""
        from paddle_tpu.incubate.nn.functional.inference_attention import \
            _bmha_fwd

        b, nh, kvh, dh, bs, pps = 3, 4, 2, 16, 8, 3
        nb = b * pps
        rng = np.random.default_rng(7)
        # pool in KERNEL layout [KVH, NB, BS, DH]; lens counts the
        # context INCLUDING the token this step writes
        kp = rng.normal(size=(kvh, nb, bs, dh)).astype(np.float32)
        vp = rng.normal(size=(kvh, nb, bs, dh)).astype(np.float32)
        lens = np.array([2, bs + 1, 2 * bs], np.int32)
        tbl = rng.permutation(nb).reshape(b, pps).astype(np.int32)
        q = rng.normal(size=(b, nh, dh)).astype(np.float32)
        k_new = rng.normal(size=(b, kvh, dh)).astype(np.float32)
        v_new = rng.normal(size=(b, kvh, dh)).astype(np.float32)

        # --- block_mha_p decode branch: writes k/v at dec = lens-1 ---
        qkv = np.concatenate(
            [q.reshape(b, -1), k_new.reshape(b, -1),
             v_new.reshape(b, -1)], axis=1)
        out_bmha, _qkv, kc_out, _vc = _bmha_fwd(
            jnp.asarray(qkv),
            jnp.asarray(kp.transpose(1, 0, 2, 3)),   # [NB, KVH, BS, DH]
            jnp.asarray(vp.transpose(1, 0, 2, 3)),
            jnp.zeros((b,), jnp.int32),              # no prefill rows
            jnp.asarray(lens - 1),                   # decode position
            jnp.arange(b, dtype=jnp.int32),
            jnp.asarray(tbl), jnp.zeros((1,), jnp.float32),
            num_heads=nh, kv_num_heads=kvh, block_size=bs,
            max_seq_len=pps * bs, use_neox=True, use_rope=False)

        # --- new decode attention on the identically-updated pool ---
        bi = (lens - 1) // bs
        slot = tbl[np.arange(b), bi] * bs + (lens - 1) % bs
        kp_f = kp.reshape(kvh, nb * bs, dh)
        vp_f = vp.reshape(kvh, nb * bs, dh)
        kp_f[:, slot] = k_new.transpose(1, 0, 2)
        vp_f[:, slot] = v_new.transpose(1, 0, 2)
        out_new = paged_attention_decode(
            jnp.asarray(q), jnp.asarray(kp_f.reshape(kvh, nb, bs, dh)),
            jnp.asarray(vp_f.reshape(kvh, nb, bs, dh)),
            jnp.asarray(lens), jnp.asarray(tbl), backend="reference")

        np.testing.assert_allclose(
            np.asarray(out_new).reshape(b, nh * dh),
            np.asarray(out_bmha), rtol=2e-5, atol=2e-5)
        # and the bmha cache write landed where the block table says
        kc_np = np.asarray(kc_out).transpose(1, 0, 2, 3).reshape(
            kvh, nb * bs, dh)
        np.testing.assert_allclose(kc_np[:, slot],
                                   k_new.transpose(1, 0, 2), rtol=1e-6)

    def test_shape_validation(self):
        q, kp, vp, lens, tbl = _case()
        with pytest.raises(ValueError, match="lengths"):
            paged_attention_decode_reference(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(lens[:-1]), jnp.asarray(tbl))
        with pytest.raises(ValueError, match="multiple"):
            paged_attention_decode_reference(
                jnp.asarray(q[:, :3]), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(lens), jnp.asarray(tbl))
        with pytest.raises(ValueError, match="backend"):
            paged_attention_decode(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(lens), jnp.asarray(tbl), backend="cuda")


class TestKernel:
    """The Pallas kernel body itself. On CPU this runs under the
    interpreter (slow — excluded from tier-1; the fast jnp-reference
    gates above cover CI); on TPU it is the compiled kernel."""

    @pytest.mark.slow
    @pytest.mark.parametrize("kvh", [4, 2])
    def test_kernel_matches_reference(self, kvh):
        on_tpu = jax.default_backend() == "tpu"
        q, kp, vp, lens, tbl = _case(seed=10 + kvh, b=4, nh=4, kvh=kvh)
        out = paged_attention_decode_kernel(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(lens), jnp.asarray(tbl), interpret=not on_tpu)
        ref = paged_attention_decode_reference(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(lens), jnp.asarray(tbl))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_kernel_probe_shapes_bf16(self):
        """The probe's equivalence check verbatim: serving shapes
        (B=8/NH=16/DH=128, 128-token pages), bf16 pool, GQA off —
        matching tools/paged_kernel_probe.py's MEASURED setup."""
        on_tpu = jax.default_backend() == "tpu"
        b, nh, kvh, dh, page, pps = 8, 16, 16, 128, 128, 2
        npages = b * pps
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, nh, dh)) * 0.3, jnp.bfloat16)
        kp = jnp.asarray(rng.normal(size=(kvh, npages, page, dh)) * 0.3,
                         jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(kvh, npages, page, dh)) * 0.3,
                         jnp.bfloat16)
        lens = jnp.asarray(rng.integers(100, 250, size=(b,)), jnp.int32)
        tbl = jnp.asarray(np.arange(npages, dtype=np.int32)
                          .reshape(b, pps))
        out = paged_attention_decode_kernel(q, kp, vp, lens, tbl,
                                            interpret=not on_tpu)
        ref = paged_attention_decode_reference(q, kp, vp, lens, tbl)
        err = np.max(np.abs(np.asarray(out, np.float32)
                            - np.asarray(ref, np.float32)))
        assert err < 0.05, \
            f"kernel diverges from masked-softmax reference: {err}"


def _pool(seed, b, nh, kvh, dh, page, npages):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(b, nh, dh)).astype(np.float32),
            rng.normal(size=(kvh, npages, page, dh)).astype(np.float32),
            rng.normal(size=(kvh, npages, page, dh)).astype(np.float32))


def _tables(seed, lens, page, pps, npages, junk):
    """Each row's live entries are pages of its own, drawn without
    replacement; an entry past them is ``junk``, which a walk of the
    live pages alone never reads (out of the pool's range where the
    case says so)."""
    rng = np.random.default_rng(seed)
    free = iter(rng.permutation(npages))
    tbl = np.full((len(lens), pps), junk, np.int32)
    for r, n in enumerate(lens):
        held = -(-int(n) // page)
        tbl[r, :held] = [next(free) for _ in range(held)]
    return tbl


PAGE = 8
# what the loop over live pages can get wrong, by lengths and lower bounds
# of a batch over a table 6 wide (None: no lower bound)
LOOP_CASES = {
    "empty-row-between-live-rows": ([13, 0, 22, 0, 0, 5], None),
    "all-rows-empty": ([0, 0, 0], None),
    "first-and-last-rows-empty": ([0, 9, 17, 0], None),
    "on-a-page-edge-and-one-past": (
        [PAGE, PAGE + 1, 2 * PAGE, 2 * PAGE + 1, 1, PAGE - 1], None),
    "full-width-beside-one-page-rows": ([3, 6 * PAGE, 1, PAGE, 6 * PAGE - 1],
                                        None),
    "start-inside-first-live-page": ([20, 30, 48, 9], [3, 17, 1, 0]),
    "start-past-first-page": ([20, 30, 48, 41], [8, 16, 33, 40]),
    "start-at-or-past-length": ([20, 30, 0, 12], [20, 35, 0, 11]),
}


class TestLivePageLoop:
    """The kernel under the interpreter against the reference, in float32
    (so a page skipped, read twice or taken from a neighbour shows as a
    gross error)."""

    @staticmethod
    def _close(args, **kw):
        args = [jnp.asarray(a) for a in args]
        kw = {k: (jnp.asarray(v) if k == "starts" else v)
              for k, v in kw.items()}
        out = paged_attention_decode_kernel(*args, interpret=True, **kw)
        ref = paged_attention_decode_reference(*args, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        lens = np.asarray(args[3])
        dead = lens == 0
        if "starts" in kw:
            dead |= np.asarray(kw["starts"]) >= lens
        assert np.all(np.asarray(out)[dead] == 0.0)

    @pytest.mark.parametrize("case", sorted(LOOP_CASES))
    def test_table(self, case):
        lens, starts = LOOP_CASES[case]
        lens = np.asarray(lens, np.int32)
        q, kp, vp = _pool(len(case), len(lens), 4, 2, 16, PAGE, 40)
        # entries past a row's pages point out of the pool: the parent
        # clamped them away, the loop must never reach them
        tbl = _tables(1, lens, PAGE, 6, 40, junk=10 ** 6)
        kw = {} if starts is None else dict(
            starts=np.asarray(starts, np.int32))
        self._close((q, kp, vp, lens, tbl), **kw)

    @pytest.mark.parametrize("group", [1, 8])
    def test_gqa_groups(self, group):
        lens = np.array([0, 11, 48, PAGE, 25], np.int32)
        q, kp, vp = _pool(group, len(lens), 2 * group, 2, 16, PAGE, 40)
        self._close((q, kp, vp, lens, _tables(2, lens, PAGE, 6, 40, 0)))

    @pytest.mark.parametrize("ring,lens", [
        (2, [0, 1, 5, PAGE - 1, PAGE]),                 # under one page
        (2, [PAGE + 1, 2 * PAGE - 1, 2 * PAGE, 12, 0]),  # into the second
        (2, [2 * PAGE + 1, 5 * PAGE + 3, 9 * PAGE, 7 * PAGE - 1, 4]),
        (3, [0, 3 * PAGE + 1, 10 * PAGE + 5, 2 * PAGE, 7]),   # laps of 3
    ])
    def test_ring(self, ring, lens):
        lens = np.asarray(lens, np.int32)
        window = (ring - 1) * PAGE
        q, kp, vp = _pool(ring, len(lens), 8, 2, 16, PAGE, len(lens) * ring)
        rings = np.random.default_rng(3).permutation(
            len(lens) * ring).reshape(len(lens), ring).astype(np.int32)
        self._close((q, kp, vp, lens, rings),
                    starts=np.maximum(lens - window, 0), ring=True)

    def test_ring_with_a_bound_older_than_the_ring(self):
        """A lower bound that reaches behind what the ring still holds:
        only the newest lap's pages are read, as the reference has it."""
        lens = np.array([5 * PAGE + 2, 3 * PAGE, 2], np.int32)
        q, kp, vp = _pool(9, 3, 4, 2, 16, PAGE, 6)
        rings = np.arange(6, dtype=np.int32).reshape(3, 2)
        self._close((q, kp, vp, lens, rings),
                    starts=np.zeros(3, np.int32), ring=True)

    @pytest.mark.parametrize("start", [0, 5, PAGE, 2 * PAGE + 3])
    def test_suffix_prefill_shape(self, start):
        """Many rows over one shared table, row ``i`` at length
        ``start + i + 1``, the rows past the prompt empty."""
        rows, n = 24, 19
        lens = np.where(np.arange(rows) < n,
                        start + np.arange(rows) + 1, 0).astype(np.int32)
        q, kp, vp = _pool(start, rows, 4, 2, 16, PAGE, 12)
        row = np.random.default_rng(4).permutation(12)[:6].astype(np.int32)
        self._close((q, kp, vp, lens, np.broadcast_to(row, (rows, 6))))

    def test_bf16_pool(self):
        """The serving dtype: K and V at bf16, the softmax in float32."""
        lens = np.array([0, 19, 48, 8, 33], np.int32)
        q, kp, vp = (jnp.asarray(a, jnp.bfloat16)
                     for a in _pool(5, 5, 8, 2, 16, PAGE, 40))
        tbl = jnp.asarray(_tables(6, lens, PAGE, 6, 40, 0))
        out = paged_attention_decode_kernel(q, kp, vp, jnp.asarray(lens),
                                            tbl, interpret=True)
        ref = paged_attention_decode_reference(q, kp, vp, jnp.asarray(lens),
                                               tbl)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-2)
