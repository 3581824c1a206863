"""Pallas kernel numerics vs. the XLA composition oracle.

Runs the TPU kernels in interpret mode on the CPU backend (SURVEY §4: the
fake-device pattern) and checks forward values and analytic gradients against
the dense reference implementation.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor


def _t(a, stop_gradient=False):
    t = paddle.to_tensor(a)
    t.stop_gradient = stop_gradient
    return t


def _dense_attention(q, k, v, causal):
    # numpy oracle, fp32, GQA by repeat
    qh, kh = q.shape[2], k.shape[2]
    if kh != qh:
        rep = qh // kh
        k = np.repeat(k, rep, axis=2)
        v = np.repeat(v, rep, axis=2)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = np.einsum("bshd,bthd->bhst", q, k).astype(np.float64) * scale
    if causal:
        s, t = logits.shape[-2:]
        mask = np.tril(np.ones((s, t), bool), t - s)
        logits = np.where(mask, logits, -np.inf)
    logits -= logits.max(-1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", p, v).astype(np.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_flash_attention_forward(causal, kv_heads):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fused

    B, S, H, D = 2, 256, 4, 64
    q = np.random.randn(B, S, H, D).astype(np.float32) * 0.5
    k = np.random.randn(B, S, kv_heads, D).astype(np.float32) * 0.5
    v = np.random.randn(B, S, kv_heads, D).astype(np.float32) * 0.5
    out = flash_attention_fused(_t(q), _t(k), _t(v), causal=causal)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    from paddle_tpu.nn.functional.attention import scaled_dot_product_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fused

    B, S, H, D = 1, 128, 2, 64
    qn = np.random.randn(B, S, H, D).astype(np.float32) * 0.3
    kn = np.random.randn(B, S, H, D).astype(np.float32) * 0.3
    vn = np.random.randn(B, S, H, D).astype(np.float32) * 0.3

    # pallas path
    q1, k1, v1 = _t(qn), _t(kn), _t(vn)
    out = flash_attention_fused(q1, k1, v1, causal=causal)
    out.backward(_t(np.ones_like(qn), stop_gradient=True))

    # XLA oracle path (sdpa_p primitive, jax.vjp fallback backward)
    q2, k2, v2 = _t(qn), _t(kn), _t(vn)
    ref = scaled_dot_product_attention(q2, k2, v2, is_causal=causal)
    ref.backward(_t(np.ones_like(qn), stop_gradient=True))

    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-4, atol=2e-4)
    for a, b in ((q1, q2), (k1, k2), (v1, v2)):
        np.testing.assert_allclose(a.grad.numpy(), b.grad.numpy(),
                                   rtol=3e-3, atol=3e-3)


def test_flash_attention_gqa_grads():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fused

    B, S, H, Hkv, D = 1, 128, 4, 2, 64
    qn = np.random.randn(B, S, H, D).astype(np.float32) * 0.3
    kn = np.random.randn(B, S, Hkv, D).astype(np.float32) * 0.3
    vn = np.random.randn(B, S, Hkv, D).astype(np.float32) * 0.3

    q1, k1, v1 = _t(qn), _t(kn), _t(vn)
    out = flash_attention_fused(q1, k1, v1, causal=True)
    loss = (out * out).sum()
    loss.backward()

    # oracle: repeat kv, dense softmax via the registered sdpa primitive
    from paddle_tpu.nn.functional.attention import scaled_dot_product_attention

    q2, k2, v2 = _t(qn), _t(kn), _t(vn)
    ref = scaled_dot_product_attention(q2, k2, v2, is_causal=True)
    (ref * ref).sum().backward()

    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(q1.grad.numpy(), q2.grad.numpy(), rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(k1.grad.numpy(), k2.grad.numpy(), rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(v1.grad.numpy(), v2.grad.numpy(), rtol=3e-3, atol=3e-3)


def test_flash_attention_causal_cross_length():
    """Sq != Sk causal (KV-cache decode shape): the kernel's bottom-right
    aligned mask must match the XLA fallback's tril(offset=Sk-Sq)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fused

    B, Sq, Sk, H, D = 1, 128, 256, 2, 64
    q = np.random.randn(B, Sq, H, D).astype(np.float32) * 0.3
    k = np.random.randn(B, Sk, H, D).astype(np.float32) * 0.3
    v = np.random.randn(B, Sk, H, D).astype(np.float32) * 0.3
    out = flash_attention_fused(_t(q), _t(k), _t(v), causal=True)
    ref = _dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)


def test_sdpa_dropout_on_weights():
    """Dropout must hit the attention weights (reference flash_attention.py
    :991), not the output: p=1 zeroes the output entirely, p=0 is identity,
    and eval mode ignores p."""
    from paddle_tpu.nn.functional.attention import scaled_dot_product_attention

    q = _t(np.random.randn(1, 16, 2, 8).astype(np.float32), stop_gradient=True)
    full = scaled_dot_product_attention(q, q, q, dropout_p=0.0)
    dropped = scaled_dot_product_attention(q, q, q, dropout_p=1.0, training=True)
    np.testing.assert_allclose(dropped.numpy(), np.zeros_like(dropped.numpy()))
    evaled = scaled_dot_product_attention(q, q, q, dropout_p=0.7, training=False)
    np.testing.assert_allclose(evaled.numpy(), full.numpy(), rtol=1e-6)


def test_rms_norm_pallas_matches_xla():
    from paddle_tpu.core import flags

    R, Hd = 64, 256
    xn = np.random.randn(R, Hd).astype(np.float32)
    wn = np.random.randn(Hd).astype(np.float32)

    import paddle_tpu.nn.functional as F

    # pallas path (gate passes: hidden%128==0, rows%8==0, CPU interpret)
    flags.set_flags({"use_pallas_rms_norm": True,
                     "pallas_force_interpret": True})
    x1, w1 = _t(xn), _t(wn)
    y1 = F.rms_norm(x1, w1)
    (y1 * y1).sum().backward()

    flags.set_flags({"use_pallas_rms_norm": False})
    x2, w2 = _t(xn), _t(wn)
    y2 = F.rms_norm(x2, w2)
    (y2 * y2).sum().backward()
    flags.set_flags({"use_pallas_rms_norm": True,
                     "pallas_force_interpret": False})

    np.testing.assert_allclose(y1.numpy(), y2.numpy(), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(x1.grad.numpy(), x2.grad.numpy(), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(w1.grad.numpy(), w2.grad.numpy(), rtol=1e-4, atol=1e-4)


def test_rms_norm_pallas_3d_bf16():
    import jax.numpy as jnp

    from paddle_tpu.core import flags

    B, S, Hd = 2, 16, 128
    xn = np.random.randn(B, S, Hd).astype(np.float32)
    wn = np.ones(Hd, np.float32)
    import paddle_tpu.nn.functional as F

    flags.set_flags({"pallas_force_interpret": True})
    try:
        x = _t(xn.astype(np.float32))
        x = x.astype("bfloat16")
        w = _t(wn).astype("bfloat16")
        y = F.rms_norm(x, w)
        assert y.dtype == jnp.bfloat16.dtype or str(y.dtype) == "bfloat16"
        ref = xn / np.sqrt((xn ** 2).mean(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(y.astype("float32").numpy(), ref,
                                   rtol=3e-2, atol=3e-2)
    finally:
        flags.set_flags({"pallas_force_interpret": False})


def _varlen_oracle(q, k, v, cu_q, cu_k, causal, scale):
    """Per-segment dense attention over packed [T, H, D] arrays."""
    outs = []
    for i in range(len(cu_q) - 1):
        qs = q[cu_q[i]: cu_q[i + 1]][None]          # [1, s, H, D]
        ks = k[cu_k[i]: cu_k[i + 1]][None]
        vs = v[cu_k[i]: cu_k[i + 1]][None]
        qh, kh = qs.shape[2], ks.shape[2]
        if kh != qh:
            ks = np.repeat(ks, qh // kh, axis=2)
            vs = np.repeat(vs, qh // kh, axis=2)
        logits = np.einsum("bshd,bthd->bhst", qs, ks).astype(np.float64)
        logits *= scale
        if causal:
            s, t = logits.shape[-2:]
            mask = np.tril(np.ones((s, t), bool), t - s)
            logits = np.where(mask, logits, -np.inf)
        logits -= logits.max(-1, keepdims=True)
        p = np.exp(logits)
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("bhst,bthd->bshd", p, vs)[0])
    return np.concatenate(outs, 0).astype(np.float32)


class TestVarlenFlashAttention:
    LENS = [5, 1, 9, 3]

    def _pack(self, h=4, kvh=4, d=16, seed=0):
        rng = np.random.RandomState(seed)
        t = sum(self.LENS)
        cu = np.concatenate([[0], np.cumsum(self.LENS)]).astype("int32")
        q = rng.randn(t, h, d).astype("float32") * 0.5
        k = rng.randn(t, kvh, d).astype("float32") * 0.5
        v = rng.randn(t, kvh, d).astype("float32") * 0.5
        return q, k, v, cu

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("kvh", [4, 2])
    def test_forward_matches_per_segment_oracle(self, causal, kvh):
        import paddle_tpu.nn.functional.flash_attention as FA

        q, k, v, cu = self._pack(kvh=kvh)
        scale = 1.0 / np.sqrt(q.shape[-1])
        out, _ = FA.flash_attn_unpadded(
            _t(q, True), _t(k, True), _t(v, True), _t(cu), _t(cu),
            max(self.LENS), max(self.LENS), scale, causal=causal)
        want = _varlen_oracle(q, k, v, cu, cu, causal, scale)
        np.testing.assert_allclose(out.numpy(), want, rtol=2e-4, atol=2e-4)

    def test_one_compile_many_layouts(self):
        """Different cu_seqlens with the same packed shape reuse the jit
        cache — the sin the old per-segment loop committed."""
        import paddle_tpu.nn.functional.flash_attention as FA
        from paddle_tpu.ops.pallas import flash_attention_varlen as VF

        q, k, v, _ = self._pack()
        scale = 1.0 / np.sqrt(q.shape[-1])
        cu_a = np.array([0, 5, 6, 15, 18], dtype="int32")
        cu_b = np.array([0, 2, 10, 17, 18], dtype="int32")
        FA.flash_attn_unpadded(_t(q, True), _t(k, True), _t(v, True),
                               _t(cu_a), _t(cu_a), 9, 9, scale, causal=True)
        before = VF._vflash_fwd._cache_size()
        out, _ = FA.flash_attn_unpadded(
            _t(q, True), _t(k, True), _t(v, True),
            _t(cu_b), _t(cu_b), 9, 9, scale, causal=True)
        assert VF._vflash_fwd._cache_size() == before
        want = _varlen_oracle(q, k, v, cu_b, cu_b, True, scale)
        np.testing.assert_allclose(out.numpy(), want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_analytic_grads_vs_dense_autodiff(self, causal):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.nn.functional.flash_attention as FA

        q, k, v, cu = self._pack(d=8)
        scale = 1.0 / np.sqrt(q.shape[-1])

        qt, kt, vt = _t(q), _t(k), _t(v)
        out, _ = FA.flash_attn_unpadded(qt, kt, vt, _t(cu), _t(cu),
                                        max(self.LENS), max(self.LENS),
                                        scale, causal=causal)
        out.sum().backward()

        # oracle grads: jax autodiff over the per-segment dense composition
        def loss(qa, ka, va):
            total = 0.0
            for i in range(len(cu) - 1):
                qs = qa[cu[i]: cu[i + 1]]
                ks = ka[cu[i]: cu[i + 1]]
                vs = va[cu[i]: cu[i + 1]]
                logits = jnp.einsum("shd,thd->hst", qs, ks) * scale
                if causal:
                    s, t = logits.shape[-2:]
                    mask = jnp.tril(jnp.ones((s, t), bool), t - s)
                    logits = jnp.where(mask, logits, -jnp.inf)
                p = jax.nn.softmax(logits, axis=-1)
                total = total + jnp.einsum("hst,thd->shd", p, vs).sum()
            return total

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(qt.grad.numpy(), gq, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(kt.grad.numpy(), gk, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(vt.grad.numpy(), gv, rtol=2e-3, atol=2e-3)

    def test_gqa_grads(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.nn.functional.flash_attention as FA

        q, k, v, cu = self._pack(h=4, kvh=2, d=8, seed=3)
        scale = 1.0 / np.sqrt(q.shape[-1])
        qt, kt, vt = _t(q), _t(k), _t(v)
        out, _ = FA.flash_attn_unpadded(qt, kt, vt, _t(cu), _t(cu),
                                        max(self.LENS), max(self.LENS),
                                        scale, causal=True)
        out.sum().backward()

        def loss(qa, ka, va):
            ka = jnp.repeat(ka, 2, axis=1)
            va = jnp.repeat(va, 2, axis=1)
            total = 0.0
            for i in range(len(cu) - 1):
                qs = qa[cu[i]: cu[i + 1]]
                ks = ka[cu[i]: cu[i + 1]]
                vs = va[cu[i]: cu[i + 1]]
                logits = jnp.einsum("shd,thd->hst", qs, ks) * scale
                s, t = logits.shape[-2:]
                mask = jnp.tril(jnp.ones((s, t), bool), t - s)
                logits = jnp.where(mask, logits, -jnp.inf)
                p = jax.nn.softmax(logits, axis=-1)
                total = total + jnp.einsum("hst,thd->shd", p, vs).sum()
            return total

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(qt.grad.numpy(), gq, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(kt.grad.numpy(), gk, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(vt.grad.numpy(), gv, rtol=2e-3, atol=2e-3)

    def test_dropout_fallback_bottom_right_causal(self):
        """The dropout>0 dense fallback must use BOTTOM-RIGHT-aligned
        causal masking (the varlen contract) when len_k != len_q: query
        row r attends keys c <= r + (len_k - len_q). One-hot values make
        attention reach observable: over many rng draws every ALLOWED key
        must contribute at least once and every FORBIDDEN key never."""
        import paddle_tpu.nn.functional.flash_attention as FA

        rng = np.random.RandomState(7)
        len_q, len_k, h, d = 2, 6, 2, 8
        q = rng.randn(len_q, h, d).astype("float32")
        k = rng.randn(len_k, h, d).astype("float32")
        v = np.zeros((len_k, h, d), dtype="float32")
        for t in range(len_k):
            v[t, :, t] = 1.0  # v one-hot in key position
        cu_q = np.array([0, len_q], dtype="int32")
        cu_k = np.array([0, len_k], dtype="int32")
        scale = 1.0 / np.sqrt(d)

        acc = np.zeros((len_q, len_k))
        for _ in range(30):
            out, _ = FA.flash_attn_unpadded(
                _t(q), _t(k), _t(v), _t(cu_q), _t(cu_k),
                len_q, len_k, scale, dropout=0.3, causal=True,
                training=True)
            acc += np.abs(out.numpy()[:, 0, :len_k])

        off = len_k - len_q
        for r in range(len_q):
            for c in range(len_k):
                if c <= r + off:
                    assert acc[r, c] > 0, (
                        f"allowed key {c} never reached by row {r} - "
                        "top-left-aligned mask?")
                else:
                    assert acc[r, c] == 0, (
                        f"forbidden key {c} leaked into row {r}")


class TestFlashDropout:
    """In-kernel attention-weight dropout (reference flash_attn dropout,
    flash_attn_kernel.cu:35 rng plumbing; here a counter RNG regenerated
    identically in fwd and both bwd kernels)."""

    def test_invalid_dropout_args_raise(self):
        """Direct calls with dropout_p>=1 or a missing rng must fail
        with a clear ValueError, not a late division-by-zero or
        AttributeError (advisor round-4)."""
        import pytest

        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_fused

        q = Tensor._from_value(
            __import__("jax.numpy", fromlist=["x"]).zeros((1, 128, 2, 64)))
        with pytest.raises(ValueError, match="requires rng"):
            flash_attention_fused(q, q, q, dropout_p=0.5, rng=None)
        import jax

        rng = Tensor._from_value(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match=r"\[0, 1\)"):
            flash_attention_fused(q, q, q, dropout_p=1.0, rng=rng)

    def _arrays(self, B=1, S=128, H=2, D=64, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: rng.randn(B, S, H, D).astype(np.float32) * 0.3
        return mk(), mk(), mk()

    def test_deterministic_and_seed_sensitive(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd

        q, k, v = self._arrays()
        s1 = jnp.array([123], jnp.int32)
        s2 = jnp.array([987], jnp.int32)
        o1, l1 = flash_attention_bshd(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), s1, dropout_rate=0.2)
        o1b, _ = flash_attention_bshd(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), s1, dropout_rate=0.2)
        o2, _ = flash_attention_bshd(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), s2, dropout_rate=0.2)
        o0, l0 = flash_attention_bshd(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v))
        assert np.array_equal(np.asarray(o1), np.asarray(o1b))
        assert not np.allclose(np.asarray(o1), np.asarray(o2))
        assert not np.allclose(np.asarray(o1), np.asarray(o0))
        # the softmax denominator (lse) must NOT see the dropout mask
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                                   rtol=1e-6)

    def test_mean_field_and_keep_fraction(self):
        """E[dropped out] == undropped out (upscale_in_train), and the
        realized keep fraction tracks 1-rate."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            _dropout_keep, flash_attention_bshd)

        keep = _dropout_keep(jnp.int32(42), jnp.int32(1), jnp.int32(0),
                             jnp.int32(0), 128, 128, 0.3)
        frac = float(np.asarray(keep).mean())
        assert abs(frac - 0.7) < 0.02, frac

        q, k, v = self._arrays(B=2, S=256, H=4)
        o0, _ = flash_attention_bshd(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v))
        acc = np.zeros_like(q)
        n = 8
        for t in range(n):
            o, _ = flash_attention_bshd(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.array([1000 + t], jnp.int32), dropout_rate=0.3)
            acc += np.asarray(o)
        # elementwise: n=8 draws at rate .3 leave ~23% relative noise
        rel = np.abs(acc / n - np.asarray(o0)).mean() / (
            np.abs(np.asarray(o0)).mean())
        assert rel < 0.4, rel
        # aggregate: noise cancels across 512k elements, so any upscale
        # bias (a missing 1/(1-rate) shows as ~30%) is caught tightly
        bias = abs(float((acc / n).mean()) - float(np.asarray(o0).mean()))
        assert bias / abs(float(np.asarray(o0).mean())) < 0.05, bias

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_finite_difference(self, causal):
        """With the seed fixed the dropped attention is a smooth function
        of q/k/v, so analytic grads must match central differences
        (op_test.py:148 numeric-gradient pattern)."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            _flash_fwd_bhsd, _flash_bwd_bhsd)

        B, H, S, D = 1, 2, 128, 64
        rng = np.random.RandomState(3)
        q = rng.randn(B, H, S, D).astype(np.float32) * 0.5
        k = rng.randn(B, H, S, D).astype(np.float32) * 0.5
        v = rng.randn(B, H, S, D).astype(np.float32) * 0.5
        do = rng.randn(B, H, S, D).astype(np.float32)
        seed = jnp.array([99], jnp.int32)
        kw = dict(causal=causal, scale=0.125, dropout_rate=0.3)
        out, lse = _flash_fwd_bhsd(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), seed, **kw)
        dq, dk, dv = _flash_bwd_bhsd(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), out, lse,
                                     jnp.asarray(do), seed, **kw)

        def loss(q_, k_, v_):
            o, _ = _flash_fwd_bhsd(jnp.asarray(q_), jnp.asarray(k_),
                                   jnp.asarray(v_), seed, **kw)
            return float(np.asarray(o, np.float64).ravel() @ do.ravel())

        eps = 1e-2
        for name, base, grad in (("dq", q, dq), ("dk", k, dk),
                                 ("dv", v, dv)):
            idx = (0, 1, 100, 33)
            pert = np.zeros_like(base)
            pert[idx] = eps
            args = {"dq": ((base + pert, k, v), (base - pert, k, v)),
                    "dk": ((q, base + pert, v), (q, base - pert, v)),
                    "dv": ((q, k, base + pert), (q, k, base - pert))}[name]
            num = (loss(*args[0]) - loss(*args[1])) / (2 * eps)
            ana = float(np.asarray(grad)[idx])
            assert abs(num - ana) <= 2e-2 * max(abs(num), abs(ana), 0.05), (
                name, num, ana)

    def test_sdpa_routes_dropout_to_pallas_with_grads(self):
        """nn.functional SDPA keeps the flash path for dropout_p > 0 and
        the tape backward runs the custom vjp (seed grad slot is None)."""
        from paddle_tpu.core import flags
        from paddle_tpu.nn.functional.attention import (
            scaled_dot_product_attention)

        B, S, H, D = 1, 128, 2, 64
        rng = np.random.RandomState(5)
        q = _t(rng.randn(B, S, H, D).astype(np.float32) * 0.4)
        k = _t(rng.randn(B, S, H, D).astype(np.float32) * 0.4)
        v = _t(rng.randn(B, S, H, D).astype(np.float32) * 0.4)
        flags.set_flags({"pallas_force_interpret": True})
        try:
            out = scaled_dot_product_attention(q, k, v, dropout_p=0.25,
                                               training=True)
            out.sum().backward()
        finally:
            flags.set_flags({"pallas_force_interpret": False})
        assert q.grad is not None and k.grad is not None
        assert v.grad is not None
        assert np.isfinite(q.grad.numpy()).all()
        # eval mode must be exactly the no-dropout fast path
        e1 = scaled_dot_product_attention(q, k, v, dropout_p=0.25,
                                          training=False)
        e0 = scaled_dot_product_attention(q, k, v)
        np.testing.assert_allclose(e1.numpy(), e0.numpy(), rtol=1e-6)

    def test_varlen_dropout_in_kernel(self):
        """flash_attn_unpadded dropout runs in the varlen kernel:
        fixed_seed_offset pins the mask, grads flow, eval ignores p,
        cross-segment leakage stays impossible."""
        import paddle_tpu.nn.functional.flash_attention as FA

        rng = np.random.RandomState(11)
        T, H, D = 96, 2, 32
        cu = np.array([0, 40, 96], dtype="int32")
        q = _t(rng.randn(T, H, D).astype("float32") * 0.4)
        k = _t(rng.randn(T, H, D).astype("float32") * 0.4)
        v = _t(rng.randn(T, H, D).astype("float32") * 0.4)
        cu_t = _t(cu, stop_gradient=True)
        kw = dict(max_seqlen_q=64, max_seqlen_k=64,
                  scale=1.0 / np.sqrt(D), dropout=0.3, causal=False,
                  training=True)
        o1, _ = FA.flash_attn_unpadded(q, k, v, cu_t, cu_t,
                                       fixed_seed_offset=77, **kw)
        o2, _ = FA.flash_attn_unpadded(q, k, v, cu_t, cu_t,
                                       fixed_seed_offset=77, **kw)
        o3, _ = FA.flash_attn_unpadded(q, k, v, cu_t, cu_t,
                                       fixed_seed_offset=123, **kw)
        np.testing.assert_array_equal(o1.numpy(), o2.numpy())
        assert not np.allclose(o1.numpy(), o3.numpy())
        # eval mode: p ignored, matches the no-dropout kernel exactly
        oe, _ = FA.flash_attn_unpadded(q, k, v, cu_t, cu_t,
                                       **{**kw, "training": False})
        o0, _ = FA.flash_attn_unpadded(q, k, v, cu_t, cu_t,
                                       **{**kw, "dropout": 0.0})
        np.testing.assert_allclose(oe.numpy(), o0.numpy(), rtol=1e-6)
        # grads flow through the dropped kernel (manual vjp path)
        out, _ = FA.flash_attn_unpadded(q, k, v, cu_t, cu_t,
                                        fixed_seed_offset=77, **kw)
        out.sum().backward()
        for t in (q, k, v):
            assert t.grad is not None
            assert np.isfinite(t.grad.numpy()).all()
        # segment isolation survives dropout: perturbing segment 1's keys
        # must not change segment 0's outputs (same fixed seed)
        k2 = k.numpy().copy()
        k2[40:] += 10.0
        o_pert, _ = FA.flash_attn_unpadded(_t(k2 * 0 + q.numpy()), _t(k2),
                                           v, cu_t, cu_t,
                                           fixed_seed_offset=77, **kw)
        np.testing.assert_allclose(o_pert.numpy()[:40], o1.numpy()[:40],
                                   rtol=1e-5, atol=1e-5)


class TestFlashKeyBias:
    """[B, 1, 1, Sk] additive padding masks ride the flash kernel as a
    per-key logit bias instead of falling back to the XLA composition."""

    def _case(self, B=2, S=128, H=2, D=64, n_pad=37, seed=0):
        rng = np.random.RandomState(seed)
        q = rng.randn(B, S, H, D).astype(np.float32) * 0.4
        k = rng.randn(B, S, H, D).astype(np.float32) * 0.4
        v = rng.randn(B, S, H, D).astype(np.float32) * 0.4
        # last n_pad keys of each row masked out (padding pattern)
        mask = np.zeros((B, 1, 1, S), np.float32)
        mask[..., S - n_pad:] = -1e9
        return q, k, v, mask

    def test_matches_sdpa_mask_oracle(self):
        from paddle_tpu.core import flags
        from paddle_tpu.nn.functional.attention import (
            scaled_dot_product_attention)
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_fused)

        q, k, v, mask = self._case()
        # flash path with key_bias
        q1, k1, v1 = _t(q), _t(k), _t(v)
        bias = _t(mask.reshape(2, -1), stop_gradient=True)
        out = flash_attention_fused(q1, k1, v1, key_bias=bias)
        out.sum().backward()
        # oracle: sdpa_mask_p (XLA composition)
        q2, k2, v2 = _t(q), _t(k), _t(v)
        ref = scaled_dot_product_attention(
            q2, k2, v2, attn_mask=_t(mask, stop_gradient=True))
        ref.sum().backward()
        np.testing.assert_allclose(out.numpy(), ref.numpy(),
                                   rtol=2e-4, atol=2e-4)
        for a, b in ((q1, q2), (k1, k2), (v1, v2)):
            np.testing.assert_allclose(a.grad.numpy(), b.grad.numpy(),
                                       rtol=3e-3, atol=3e-3)
        # padded keys must receive zero dV/dK
        np.testing.assert_allclose(k1.grad.numpy()[:, -37:], 0.0, atol=1e-6)
        np.testing.assert_allclose(v1.grad.numpy()[:, -37:], 0.0, atol=1e-6)

    def test_sdpa_routes_padding_mask_to_flash(self):
        """With aligned shapes + the force-interpret flag, SDPA's masked
        path must produce the flash primitive when Sk is at/above the
        measured crossover (attention.py _MASK_FLASH_MIN_SK), the XLA
        fallback below it — and both must agree numerically."""
        import paddle_tpu.nn.functional.attention as A
        from paddle_tpu.core import dispatch, flags

        q, k, v, mask = self._case(B=1, n_pad=16)
        m = _t(mask[:1], stop_gradient=True)
        prev_flag = flags.get_flag("pallas_force_interpret")
        flags.set_flags({"pallas_force_interpret": True})
        orig_thresh = A._MASK_FLASH_MIN_SK
        calls = []
        orig_call = dispatch.call_primitive
        dispatch.call_primitive = lambda n, a, st: (
            calls.append(n), orig_call(n, a, st))[1]
        try:
            A._MASK_FLASH_MIN_SK = 128  # below this case's Sk: flash path
            out = A.scaled_dot_product_attention(_t(q[:1]), _t(k[:1]),
                                                 _t(v[:1]), attn_mask=m)
            routed_big = [c for c in calls if "flash" in c or "sdpa" in c]
            calls.clear()
            A._MASK_FLASH_MIN_SK = orig_thresh  # S=128 < 1024: XLA path
            ref = A.scaled_dot_product_attention(_t(q[:1]), _t(k[:1]),
                                                 _t(v[:1]), attn_mask=m)
            routed_small = [c for c in calls if "flash" in c or "sdpa" in c]
        finally:
            dispatch.call_primitive = orig_call
            A._MASK_FLASH_MIN_SK = orig_thresh
            flags.set_flags({"pallas_force_interpret": prev_flag})
        # the test must FAIL if routing regresses, not pass vacuously
        assert routed_big == ["flash_attention_p"], routed_big
        assert routed_small == ["sdpa_mask_p"], routed_small
        np.testing.assert_allclose(out.numpy(), ref.numpy(),
                                   rtol=2e-4, atol=2e-4)

    def test_trainable_mask_stays_on_xla_path(self):
        """A TRAINABLE additive bias must not route to flash (which
        returns no bias grad): grads must keep flowing at any Sk."""
        import paddle_tpu.nn.functional.attention as A

        q, k, v, mask = self._case(B=1, n_pad=16)
        m = _t(mask[:1])  # stop_gradient=False: trainable bias
        orig_thresh = A._MASK_FLASH_MIN_SK
        try:
            A._MASK_FLASH_MIN_SK = 128
            out = A.scaled_dot_product_attention(_t(q[:1]), _t(k[:1]),
                                                 _t(v[:1]), attn_mask=m)
            out.sum().backward()
        finally:
            A._MASK_FLASH_MIN_SK = orig_thresh
        assert m.grad is not None
        assert np.isfinite(m.grad.numpy()).all()

    def test_fully_masked_row_zero_both_paths(self):
        """A batch row whose keys are ALL -inf-masked yields exact zeros
        on BOTH routes (safe softmax), so behavior cannot flip at the
        Sk crossover."""
        import paddle_tpu.nn.functional.attention as A
        from paddle_tpu.core import flags

        q, k, v, _ = self._case(B=2)
        mask = np.zeros((2, 1, 1, 128), np.float32)
        mask[1] = -np.inf  # second row: everything masked
        m = _t(mask, stop_gradient=True)
        ref = A.scaled_dot_product_attention(_t(q), _t(k), _t(v),
                                             attn_mask=m)
        assert np.isfinite(ref.numpy()).all()
        np.testing.assert_allclose(ref.numpy()[1], 0.0, atol=1e-7)
        prev_flag = flags.get_flag("pallas_force_interpret")
        flags.set_flags({"pallas_force_interpret": True})
        orig_thresh = A._MASK_FLASH_MIN_SK
        try:
            A._MASK_FLASH_MIN_SK = 128
            out = A.scaled_dot_product_attention(_t(q), _t(k), _t(v),
                                                 attn_mask=m)
        finally:
            A._MASK_FLASH_MIN_SK = orig_thresh
            flags.set_flags({"pallas_force_interpret": prev_flag})
        np.testing.assert_allclose(out.numpy()[1], 0.0, atol=1e-7)
        np.testing.assert_allclose(out.numpy()[0], ref.numpy()[0],
                                   rtol=2e-4, atol=2e-4)

    def test_bias_with_dropout_composes(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bshd)

        q, k, v, mask = self._case()
        bias = jnp.asarray(mask.reshape(2, -1))
        s1 = jnp.array([5], jnp.int32)
        o1, l1 = flash_attention_bshd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), bias, s1,
            has_bias=True, dropout_rate=0.2)
        o1b, _ = flash_attention_bshd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), bias, s1,
            has_bias=True, dropout_rate=0.2)
        assert np.array_equal(np.asarray(o1), np.asarray(o1b))
        # masked keys stay masked under dropout; lse reflects bias only
        o0, l0 = flash_attention_bshd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), bias,
            has_bias=True)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                                   rtol=1e-5)

    def test_shared_batch1_mask_multi_batch(self):
        """A [1, Sk] bias shared across a B>1 batch uses the pinned
        (row-0) index map in all three kernels — must match the expanded
        [B, Sk] bias bit-for-bit, fwd and bwd."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            _flash_fwd_bhsd, _flash_bwd_bhsd)

        B, H, S, D = 3, 2, 128, 64
        rng = np.random.RandomState(8)
        q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.4)
        k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.4)
        v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.4)
        do = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
        bias1 = jnp.where(jnp.arange(S)[None, :] < 100, 0.0,
                          -1e9).astype(jnp.float32)          # [1, S]
        biasB = jnp.broadcast_to(bias1, (B, S))
        kw = dict(causal=False, scale=0.125)
        o1, l1 = _flash_fwd_bhsd(q, k, v, None, bias1, **kw)
        oB, lB = _flash_fwd_bhsd(q, k, v, None, biasB, **kw)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(oB))
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(lB))
        g1 = _flash_bwd_bhsd(q, k, v, o1, l1, do, None, bias1, **kw)
        gB = _flash_bwd_bhsd(q, k, v, oB, lB, do, None, biasB, **kw)
        for a, b in zip(g1, gB):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the single backward pass, the tile list and the [B, S, H*D] entry
# ---------------------------------------------------------------------------
def _composition_vjp(q, k, v, do, causal, bias=None, keep=None):
    """(out, (dq, dk, dv)) of float32 masked-softmax attention on
    [B, H, S, D] operands: GQA by repeat, the causal mask bottom-right
    aligned, ``bias`` [B|1, Sk] on the logits, ``keep`` ([B, H, Sq, Sk],
    already scaled) on the weights after the softmax."""
    import jax
    import jax.numpy as jnp

    g = q.shape[1] // k.shape[1]

    def f(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision="highest") * q.shape[-1] ** -0.5
        if bias is not None:
            s = s + bias[:, None, None, :]
        if causal:
            sq, sk = s.shape[-2:]
            s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), sk - sq), s,
                          -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if keep is not None:
            p = p * keep
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")

    out, vjp = jax.vjp(f, q, k, v)
    return out, vjp(do.astype(jnp.float32))


class TestFlashSinglePass:
    """One k-block-major kernel computes dq, dk and dv (named
    ``flash_bwd_dkv``); tiles of 128 make every shape here walk several
    tiles, some crossed by the mask's edge and some not, and skip those
    the mask empties."""

    @staticmethod
    def _operands(dtype, sq, sk, g, d=64, seed=0):
        import jax.numpy as jnp

        rng = np.random.RandomState(seed)
        mk = lambda *shape: jnp.asarray(
            rng.randn(*shape).astype(np.float32) * 0.4).astype(dtype)
        return (mk(1, 4, sq, d), mk(1, 4 // g, sk, d), mk(1, 4 // g, sk, d),
                mk(1, 4, sq, d))

    @pytest.mark.parametrize("g", [1, 4])
    @pytest.mark.parametrize("sq,sk", [(256, 256), (128, 384)])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_grads_match_composition(self, dtype, causal, sq, sk, g):
        from paddle_tpu.ops.pallas import flash_attention as fa

        q, k, v, do = self._operands(dtype, sq, sk, g)
        kw = dict(causal=causal, scale=64 ** -0.5, rate=0.0, has_bias=False,
                  interpret=True)
        out, lse = fa._fwd_local(q, k, v, blocks=(128, 128), **kw)
        got = fa._bwd_local(q, k, v, out, lse, do, blocks=(128, 128), **kw)
        ref, want = _composition_vjp(q, k, v, do, causal)
        tol = 3e-3 if dtype == "float32" else 4e-2
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=tol, atol=tol)
        for a, b in zip(got, want):
            assert a.shape == b.shape and a.dtype == q.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                       rtol=tol, atol=tol)

    @pytest.mark.parametrize("causal", [False, True])
    def test_dropout_takes_the_forwards_bits(self, causal):
        """The forward walks (256, 128) tiles and the backward (128, 256)
        transposed ones: both regenerate the bits of the global row and
        column, which the oracle applies as a dense mask."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import flash_attention as fa

        rate, s = 0.3, 256
        q, k, v, do = self._operands("float32", s, s, 1, seed=1)
        seed = jnp.array([77], jnp.int32)
        kw = dict(causal=causal, scale=0.125, rate=rate, has_bias=False,
                  interpret=True)
        out, lse = fa._fwd_local(q, k, v, seed, blocks=(256, 128), **kw)
        got = fa._bwd_local(q, k, v, out, lse, do, seed, blocks=(128, 256),
                            **kw)
        keep = jnp.stack([fa._dropout_keep(seed[0], bh, 0, 0, s, s, rate)
                          for bh in range(4)])[None] / (1.0 - rate)
        ref, want = _composition_vjp(q, k, v, do, causal, keep=keep)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-3, atol=3e-3)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), b, rtol=3e-3,
                                       atol=3e-3)

    @pytest.mark.parametrize("rows", [1, 2])
    def test_key_bias(self, rows):
        """The bias reaches the transposed tile as a [block_k, 1] column;
        a padded key (-inf) gets no weight and no gradient."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import flash_attention as fa

        rng = np.random.RandomState(4)
        q, k, v, do = (jnp.asarray(rng.randn(2, 2, 256, 64)
                                   .astype(np.float32) * 0.4)
                       for _ in range(4))
        bias = jnp.asarray(rng.randn(rows, 256).astype(np.float32))
        bias = bias.at[:, 200:].set(-jnp.inf)
        kw = dict(causal=False, scale=0.125, rate=0.0, has_bias=True,
                  interpret=True)
        out, lse = fa._fwd_local(q, k, v, bias, blocks=(128, 128), **kw)
        got = fa._bwd_local(q, k, v, out, lse, do, bias, blocks=(128, 128),
                            **kw)
        ref, want = _composition_vjp(q, k, v, do, False, bias=bias)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-3, atol=3e-3)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), b, rtol=3e-3,
                                       atol=3e-3)
        assert not np.asarray(got[1])[:, :, 200:].any()

    @pytest.mark.parametrize("g", [1, 2])
    def test_model_layout_entry_is_the_transposed_one_bit_for_bit(self, g):
        """Heads of 128: ``flash_attention_bshd`` / ``_flash_vjp`` block
        the [B, S, H*D] view; the head-major entries on swapped operands
        run the same tiles."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import flash_attention as fa

        q, k, v, do = self._operands("float32", 256, 256, g, d=128, seed=2)
        kw = dict(causal=True, scale=128 ** -0.5)
        out, lse = fa._flash_fwd_bhsd(q, k, v, **kw)
        grads = fa._flash_bwd_bhsd(q, k, v, out, lse, do, **kw)
        sw = lambda x: jnp.swapaxes(x, 1, 2)
        before = fa._M_TRACED.value(**{"pass": "bwd", "form": "single",
                                       "layout": "bshd"})
        out_m, lse_m = fa.flash_attention_bshd(sw(q), sw(k), sw(v), **kw)
        grads_m = fa._flash_vjp((sw(do),), (sw(q), sw(k), sw(v), out_m,
                                            lse_m), **kw)
        np.testing.assert_array_equal(np.asarray(sw(out_m)), np.asarray(out))
        np.testing.assert_array_equal(np.asarray(lse_m), np.asarray(lse))
        for a, b in zip(grads_m, grads):
            np.testing.assert_array_equal(np.asarray(sw(a)), np.asarray(b))
        # a cached trace counts nothing: at most one more, never fewer
        assert fa._M_TRACED.value(**{"pass": "bwd", "form": "single",
                                     "layout": "bshd"}) >= before

    def test_heads_of_64_go_head_major(self):
        from paddle_tpu.ops.pallas import flash_attention as fa

        assert fa._reads_bshd(128, 256) and not fa._reads_bshd(64)
        assert not fa._reads_bshd(192, 128)

    @pytest.mark.parametrize("causal", [False, True])
    def test_over_the_budget_takes_the_split_form_and_agrees(
            self, causal, monkeypatch):
        """The rule reads the shapes: a [Sq, D] float32 accumulator over
        the budget keeps the two kernels. (The budget is shrunk here; at
        its own size the sequence would be 16,384 at heads of 64.)"""
        from paddle_tpu.ops.pallas import flash_attention as fa

        q, k, v, do = self._operands("float32", 256, 256, 2, seed=3)
        kw = dict(causal=causal, scale=0.125, rate=0.0, has_bias=False,
                  interpret=True)
        out, lse = fa._fwd_local(q, k, v, **kw)
        assert fa._single_pass_fits(256, 64)
        single = fa._bwd_local(q, k, v, out, lse, do, blocks=(128, 128), **kw)
        monkeypatch.setattr(fa, "_DQ_ACC_BYTES", 256 * 64 * 4 - 1)
        assert not fa._single_pass_fits(256, 64)
        labels = {"pass": "bwd", "form": "split", "layout": "bhsd"}
        before = fa._M_TRACED.value(**labels)
        split = fa._bwd_local(q, k, v, out, lse, do, **kw)
        assert fa._M_TRACED.value(**labels) == before + 1
        for a, b in zip(single, split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        with pytest.raises(ValueError, match="head-major"):
            fa._bwd_local(*(x.swapaxes(1, 2) for x in (q, k, v, out)), lse,
                          do.swapaxes(1, 2), layout="bshd", **kw)

    @pytest.mark.parametrize("case,tiles", [
        # (nq, nk, block_q, block_k, offset, causal, window, k_major)
        ((4, 4, 512, 512, 0, True, None, False), 10),    # the train cell
        ((4, 8, 512, 256, 0, True, None, False), 20),
        ((4, 4, 512, 512, 0, True, None, True), 10),
        ((4, 4, 512, 512, 0, False, None, False), 16),
        ((4, 16, 512, 128, 0, True, 128, False), 19),     # a band of 128
        ((2, 1, 128, 128, -128, True, None, False), 2),  # queries before
    ])
    def test_tile_list(self, case, tiles):
        """Only the pairs the mask leaves anything of, each run's first
        and last flagged."""
        from paddle_tpu.ops.pallas import flash_attention as fa

        nq, nk, bq, bk, offset, causal, window, k_major = case
        qi, kj, fl = (np.asarray(x) for x in fa._tile_list(*case))
        assert len(qi) == len(kj) == len(fl) == tiles
        rows = np.arange(nq * bq)[:, None] + offset
        cols = np.arange(nk * bk)[None, :]
        seen = np.ones((nq * bq, nk * bk), bool)
        if causal:
            seen = cols <= rows
            if window is not None:
                seen &= cols > rows - window
        listed = set(zip(qi.tolist(), kj.tolist()))
        assert len(listed) == tiles
        wanted = {(i, j) for i in range(nq) for j in range(nk)
                  if seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()}
        # a block the mask empties keeps one pair, for its outputs
        assert wanted <= listed and len(listed - wanted) <= 1
        major = kj if k_major else qi
        assert sorted(major) == list(major)
        starts = np.flatnonzero(np.diff(major, prepend=-1))
        assert [bool(f & fa._FIRST) for f in fl] == [
            t in starts for t in range(tiles)]
        assert [bool(f & fa._LAST) for f in fl] == [
            t + 1 in starts or t + 1 == tiles for t in range(tiles)]

    def test_queries_before_the_first_key_read_zero(self):
        """Sq > Sk under the bottom-right-aligned mask: the first rows
        see no key; their output and gradients are zero, lse -inf."""
        from paddle_tpu.ops.pallas import flash_attention as fa

        q, k, v, do = self._operands("float32", 256, 128, 1, seed=5)
        kw = dict(causal=True, scale=0.125, rate=0.0, has_bias=False,
                  interpret=True)
        out, lse = fa._fwd_local(q, k, v, blocks=(128, 128), **kw)
        got = fa._bwd_local(q, k, v, out, lse, do, blocks=(128, 128), **kw)
        assert not np.asarray(out)[:, :, :128].any()
        assert np.isneginf(np.asarray(lse)[:, :, :128]).all()
        assert not np.asarray(got[0])[:, :, :128].any()
        ref, want = _composition_vjp(q[:, :, 128:], k, v, do[:, :, 128:],
                                     True)
        np.testing.assert_allclose(np.asarray(out)[:, :, 128:], ref,
                                   rtol=3e-3, atol=3e-3)
        for a, b in zip(got, want):
            a = np.asarray(a)
            a = a[:, :, 128:] if a.shape[2] == 256 else a
            np.testing.assert_allclose(a, b, rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# sharded programs: the kernels run per shard (ops/kernel_partition.py)
# ---------------------------------------------------------------------------
class TestShardedProgram:
    """A Mosaic kernel cannot be partitioned by XLA, so a model sharded
    by ``llama_shard_plan`` runs its kernels under shard_map. On the CPU
    mesh the same wrapping runs the interpreter: the dp2 x mp2 step must
    equal the unsharded one."""

    def _step(self, sharded):
        import paddle_tpu.distributed as dist
        import paddle_tpu.optimizer as opt
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       llama_shard_plan)

        paddle.seed(7)
        cfg = LlamaConfig.tiny(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=128)
        model = LlamaForCausalLM(cfg)
        ids_np = np.random.RandomState(0).randint(
            0, 256, (4, 128)).astype("int64")
        if sharded:
            mesh = dist.ProcessMesh(np.arange(4).reshape(2, 2),
                                    ["dp", "mp"])
            llama_shard_plan(model, mesh)
            ids = dist.shard_tensor(ids_np, mesh,
                                    [dist.Shard(0), dist.Replicate()])
        else:
            ids = paddle.to_tensor(ids_np)
        optimizer = opt.AdamW(learning_rate=1e-2,
                              parameters=model.parameters())

        @paddle.jit.to_static(full_graph=True)
        def train_step(ids, labels):
            loss, _ = model(ids, labels=labels)
            loss.backward()
            optimizer.step()
            optimizer.clear_grad()
            return loss

        losses = [float(train_step(ids, ids)) for _ in range(3)]
        return losses, train_step, model, optimizer

    def test_sharded_step_equals_unsharded(self):
        from paddle_tpu.core import flags

        flags.set_flags({"pallas_force_interpret": True})
        try:
            ref, _, _, _ = self._step(False)
            got, step, _, _ = self._step(True)
            text = step.lowered()[0].as_text()
        finally:
            flags.set_flags({"pallas_force_interpret": False})
        np.testing.assert_allclose(got, ref, rtol=2e-4)
        assert ref[-1] < ref[0]
        # the kernels really were inside shard_map on per-shard shapes
        # (head dim 64: [B/dp, H/mp, S, D] = [2, 1, 128, 64])
        assert "2x1x128x64" in text

    def test_adam_moments_take_parameter_sharding(self):
        """No accumulator of a sharded parameter may be whole on one
        device — before the first step as well as after it."""
        import paddle_tpu.distributed as dist
        import paddle_tpu.optimizer as opt
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       llama_shard_plan)

        model = LlamaForCausalLM(LlamaConfig.tiny(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2))
        model.bfloat16()
        mesh = dist.ProcessMesh(np.arange(4).reshape(2, 2), ["dp", "mp"])
        llama_shard_plan(model, mesh)
        optimizer = opt.AdamW(learning_rate=1e-2,
                              parameters=model.parameters(),
                              multi_precision=True)
        optimizer._ensure_accumulators()
        w = model.llama.layers[0].self_attn.q_proj.weight
        assert len(w._value.sharding.device_set) == 4
        stores = dict(optimizer._accumulators,
                      master=optimizer._master_weights)
        for name in ("moment1", "moment2", "master"):
            acc = stores[name][id(w)]
            assert acc.sharding == w._value.sharding, (name, acc.sharding)
            shard = acc.addressable_shards[0].data
            assert shard.size * 2 == acc.size, name     # split over mp
