#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of the 645M Llama decoder the repo benches (H=2048,
I=5632, L=10, 16 heads of 128, vocab 32,000, bf16; random weights from a
seed), on ONE TPU chip:

  device     what JAX runs on; fails unless it is a TPU the repo has
             peak figures for (paddle_tpu/device/chip.py)
  kernels    every Pallas kernel, executed on the chip at the shapes the
             train and serve paths use, against its jnp reference
  trainer    LlamaForCausalLM + AdamW(multi_precision) +
             to_static(full_graph=True), bs 4 x seq 2048, 6 steps on one
             batch: loss finite and falling, no eager fallback, the
             compiled step holds the flash and RMSNorm Mosaic kernels
  server     ServeEngine + warm_engine + run_load, once with defaults
             and once with prefix_cache + decode_burst=8 on a shared
             prefix: compiled paged kernel, every request finished, first
             tokens agree with model.generate() (bf16-tie margins aside)
  four_chip  only on a machine with >= 4 devices: the same train step
             over a dp2 x mp2 mesh in one process

Each phase is a child process (`--phase NAME`) of a parent that never
initialises a JAX backend, so one process holds the chip at a time and
each phase starts with empty device memory; they share one compile cache
(JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache). It sets
no JAX_PLATFORMS. Exit code 0 and a last stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

only if every phase passed; without an accelerator it exits non-zero and
prints no result. Times and bytes it prints are smoke output for the
reader, not metrics.

`--tiny` is a dry run of this script's own control flow at toy sizes on
whatever backend there is (Pallas interpreter off-TPU). It proves
nothing about the chip: it never prints the result line and always
exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PHASES = ("device", "kernels", "trainer", "server", "four_chip")
#: the whole run must end inside the driver's 1200 s, children included
DEADLINE_S = 1150.0


def log(msg=""):
    print(msg, flush=True)


class Checks:
    """Collects named pass/fail lines for one phase; a phase runs all of
    its checks before it fails, so one chip call shows every refusal."""

    def __init__(self, phase):
        self.phase, self.failed = phase, []

    def check(self, name, ok, detail=""):
        log(f"[{self.phase}] {'PASS' if ok else 'FAIL'}  {name}"
            + (f"  ({detail})" if detail else ""))
        if not ok:
            self.failed.append(name)

    def close(self, tol, name, got, ref):
        """max |got - ref| relative to max |ref| within ``tol``."""
        import jax.numpy as jnp
        import numpy as np

        got = np.asarray(jnp.asarray(got, jnp.float32))
        ref = np.asarray(jnp.asarray(ref, jnp.float32))
        finite = bool(np.isfinite(got).all())
        err = float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-30))
        self.check(name, finite and got.shape == ref.shape and err <= tol,
                   f"shape {got.shape}, rel err {err:.2e} <= {tol:g}")

    def finish(self):
        if self.failed:
            raise SystemExit(
                f"[{self.phase}] FAILED: {', '.join(self.failed)}")


def sizes(tiny):
    """The shapes of the run: the cells' real ones, or toy ones for
    --tiny (same code, same control flow)."""
    if not tiny:
        return dict(
            llama=dict(vocab_size=32000, hidden_size=2048,
                       intermediate_size=5632, num_hidden_layers=10,
                       num_attention_heads=16, num_key_value_heads=16,
                       max_position_embeddings=2048),
            batch=4, seq=2048, steps=6,
            flash_causal=(4, 16, 2048, 128), flash_bert=(36, 12, 512, 64),
            flash_bias=(8, 12, 1024, 64), flash_prefill=(8, 16, 128, 128),
            varlen=(8192, 16, 128), rms=((4, 2048, 2048), (8, 2048)),
            paged=dict(b=8, nh=16, dh=128, pages=96, page=128, pps=8),
            kv_write=dict(kvh=12, pages=736, page=128, dh=128, slots=128,
                          prompt=2048),
            # K-EXAONE's widths: a decode step's and a prefill piece's
            # tokens on 16 of 128 experts; 64/8 heads over rings of two
            # pages; a window of 128 in a 2,048 bucket
            moe=dict(tokens=(128, 2048), width=6144, expert=2048, held=16,
                     experts=128, top_k=8),
            ring=dict(b=128, nh=64, kvh=8, dh=128, page=128, ring=2,
                      window=128),
            flash_band=((1, 64, 2048, 128), 8, 128),
            serve_requests=8, serve_new=(8, 24))
    return dict(
        llama=dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                   num_hidden_layers=2, num_attention_heads=2,
                   num_key_value_heads=2, max_position_embeddings=256),
        batch=4, seq=128, steps=6,
        flash_causal=(2, 2, 256, 64), flash_bert=(2, 2, 128, 64),
        flash_bias=(2, 2, 256, 64), flash_prefill=(2, 2, 128, 64),
        varlen=(512, 2, 64), rms=((2, 64, 128), (8, 128)),
        paged=dict(b=4, nh=4, dh=64, pages=24, page=16, pps=4),
        kv_write=dict(kvh=2, pages=24, page=16, dh=64, slots=4, prompt=64),
        moe=dict(tokens=(8, 32), width=64, expert=32, held=3, experts=8,
                 top_k=2),
        ring=dict(b=4, nh=4, kvh=2, dh=64, page=16, ring=3, window=30),
        flash_band=((1, 4, 256, 64), 2, 40),
        serve_requests=4, serve_new=(3, 6))


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------
def phase_device(tiny):
    from importlib import metadata

    import jax
    import jaxlib

    import paddle_tpu  # noqa: F401
    from paddle_tpu import native
    from paddle_tpu.core.flags import pallas_mode
    from paddle_tpu.device import chip

    cache = chip.setup_compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"[device] python {sys.version.split()[0]}  jax {jax.__version__}  "
        f"jaxlib {jaxlib.__version__}  libtpu {libtpu}")
    info = chip.device_info()
    log(f"[device] default_backend={jax.default_backend()}  "
        f"platform={info['platform']}  device_kind={info['kind']!r}  "
        f"count={info['count']}")
    log(f"[device] compile cache: {cache}  (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f", {len(os.listdir(cache)) if os.path.isdir(cache) else 0} entries)")
    log(f"[device] native library available: {native.is_available()}  "
        f"pallas_mode: {pallas_mode()}")
    c = Checks("device")
    c.check("platform is tpu", info["platform"] == "tpu",
            f"got {info['platform']!r}")
    c.check("device_kind has peak figures", info["kind"] in chip.CHIP_PEAKS,
            f"{info['kind']!r}; table holds {sorted(chip.CHIP_PEAKS)}")
    if not tiny:
        c.finish()
    return {"device": info}


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def _dense_attention(q, k, v, *, causal, key_bias=None, keep=None, rate=0.0):
    """The reference: plain float32 attention over [B,H,S,D] (GQA by
    repeat, bottom-right causal, optional [B|1,Sk] key bias and dropout
    keep mask on the softmax weights)."""
    import jax
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    if k.shape[1] != q.shape[1]:
        k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
        v = jnp.repeat(v, q.shape[1] // v.shape[1], axis=1)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhsd,bhtd->bhst", q, k, precision=hi) \
        * q.shape[-1] ** -0.5
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    if causal:
        sq, sk = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), sk - sq), s,
                      -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = p * keep / (1.0 - rate)
    return jnp.einsum("bhst,bhtd->bhsd", p, v, precision=hi)


def _flash_case(c, name, shape, kv_heads, *, causal, rate=0.0, bias=None,
                tol=2e-2):
    """Forward + backward of the four array-level flash functions against
    the dense reference and its autodiff."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (
        _dropout_keep, _flash_bwd_bhsd, _flash_fwd_bhsd)

    B, H, S, D = shape
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    q = jax.random.normal(ks[0], shape, jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, kv_heads, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, kv_heads, S, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], shape, jnp.bfloat16)
    seed = jnp.asarray([20240926], jnp.int32) if rate else None
    key_bias = keep = None
    if bias is not None:
        # the padding-mask pattern: the last eighth of the keys masked
        live = jnp.arange(S) < S - S // 8
        key_bias = jnp.broadcast_to(
            jnp.where(live, 0.0, -jnp.inf).astype(jnp.float32), (bias, S))
    if rate:
        # the kernels' own counter RNG, evaluated outside them: forward
        # and both backward kernels must all have used THIS mask
        keep = jax.vmap(lambda bh: _dropout_keep(
            seed[0], bh, 0, 0, S, S, rate))(
                jnp.arange(B * H, dtype=jnp.int32)).reshape(B, H, S, S)
    kw = dict(causal=causal, scale=D ** -0.5, dropout_rate=rate)
    out, lse = _flash_fwd_bhsd(q, k, v, seed, key_bias, **kw)
    dq, dk, dv = _flash_bwd_bhsd(q, k, v, out, lse, do, seed, key_bias, **kw)
    ref, vjp = jax.vjp(
        lambda q, k, v: _dense_attention(q, k, v, causal=causal,
                                         key_bias=key_bias, keep=keep,
                                         rate=rate), q, k, v)
    rq, rk, rv = vjp(do.astype(jnp.float32))
    for label, got, want in (("out", out, ref), ("dq", dq, rq),
                             ("dk", dk, rk), ("dv", dv, rv)):
        c.close(tol, f"{name} {label}", got, want)


def _varlen_case(c, T, H, D, tol=2e-2):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.flash_attention_varlen import (
        _varlen_vjp, flash_attn_varlen_thd)

    # 5 segments with boundaries off every block edge
    cuts = np.array([0, 0.12, 0.49, 0.55, 0.88, 1.0]) * T
    cu = np.round(cuts).astype(np.int32)
    cu[1:-1] += np.array([3, -5, 7, 1], np.int32)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v, do = (jax.random.normal(kk, (T, H, D), jnp.bfloat16)
                   for kk in ks)
    cu_j = jnp.asarray(cu)
    kw = dict(causal=True, scale=D ** -0.5, n_seqs=5)
    out, lse = flash_attn_varlen_thd(q, k, v, cu_j, cu_j, **kw)
    dq, dk, dv = _varlen_vjp((do,), (q, k, v, cu_j, cu_j, out, lse),
                             **kw)[:3]
    refs = [[], [], [], []]
    for a, b in zip(cu[:-1], cu[1:]):
        # [L,H,D] -> [1,H,L,D] per segment
        seg = [jnp.swapaxes(x[a:b], 0, 1)[None] for x in (q, k, v, do)]
        ref, vjp = jax.vjp(
            lambda q, k, v: _dense_attention(q, k, v, causal=True), *seg[:3])
        for acc, r in zip(refs, (ref, *vjp(seg[3].astype(jnp.float32)))):
            acc.append(jnp.swapaxes(r[0], 0, 1))
    for label, got, want in zip(("out", "dq", "dk", "dv"),
                                (out, dq, dk, dv), refs):
        c.close(tol, f"varlen T={T} 5 segments causal {label}", got,
                jnp.concatenate(want, axis=0))


def _rms_case(c, shape, tol=2e-2):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.norm import _rms_norm_fwd
    from paddle_tpu.ops.pallas.rms_norm import _rms_bwd, _rms_fwd

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], shape, jnp.bfloat16)
    w = (1.0 + 0.1 * jax.random.normal(ks[1], shape[-1:])).astype(
        jnp.bfloat16)
    g = jax.random.normal(ks[2], shape, jnp.bfloat16)
    y = _rms_fwd(x, w, eps=1e-6)
    dx, dw = _rms_bwd(x, w, g, eps=1e-6)
    f32 = jnp.float32
    ref, vjp = jax.vjp(lambda x, w: _rms_norm_fwd(x, w, eps=1e-6),
                       x.astype(f32), w.astype(f32))
    rx, rw = vjp(g.astype(f32))
    for label, got, want in (("y", y, ref), ("dx", dx, rx), ("dw", dw, rw)):
        c.close(tol, f"rms_norm {shape} {label}", got, want)


def _paged_case(c, kv_heads, *, b, nh, dh, pages, page, pps, tol=2e-2):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.flags import pallas_mode
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel, paged_attention_decode_reference)

    ks = jax.random.split(jax.random.PRNGKey(kv_heads), 3)
    q = jax.random.normal(ks[0], (b, nh, dh), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (kv_heads, pages, page, dh), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (kv_heads, pages, page, dh), jnp.bfloat16)
    cap = pps * page
    # ragged: an empty (inactive) row, one token, both sides of a page
    # edge, a full table
    lengths = np.array([0, 1, page - 1, page, page + 1, cap // 2 + 3, cap,
                        cap - page // 2][:b], np.int32)
    tables = np.random.RandomState(0).permutation(pages)[:b * pps].reshape(
        b, pps).astype(np.int32)
    args = (q, kp, vp, jnp.asarray(lengths), jnp.asarray(tables))
    got = jax.jit(lambda *a: paged_attention_decode_kernel(
        *a, interpret=pallas_mode() != "compiled"))(*args)
    c.close(tol, f"paged decode {nh}/{kv_heads} heads, lengths "
                 f"{lengths.tolist()}", got,
            paged_attention_decode_reference(*(x.astype(jnp.float32)
                                               if x.dtype == jnp.bfloat16
                                               else x for x in args)))
    c.check(f"paged decode {nh}/{kv_heads} zero-length row is zeros",
            bool((np.asarray(got[0].astype(jnp.float32)) == 0).all()))


def _kv_write_case(c, *, kvh, pages, page, dh, slots, prompt):
    """The in-place K/V write against the scatter it replaced, bit for
    bit: a decode step's rows (one a stream, some slots inactive), a
    suffix prefill's (consecutive from a free start) and a cold prefill's
    blocks, at the benchmark cell's pool shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.flags import pallas_mode
    from paddle_tpu.ops.pallas.kv_write import kv_write, kv_write_reference

    backend = "kernel" if pallas_mode() == "compiled" else "interpret"
    rng = np.random.RandomState(0)
    pool = jax.random.normal(jax.random.PRNGKey(1), (kvh, pages, page, dh),
                             jnp.bfloat16)
    blocks = rng.permutation(pages)
    per = prompt // page + 1
    table = blocks[:per]

    def stream(start, n, bucket):
        pos = start + np.arange(bucket)
        slot = table[np.minimum(pos // page, per - 1)] * page + pos % page
        return np.where(np.arange(bucket) < n, slot, pages * page)

    decode = blocks[:slots] * page + rng.randint(0, page, slots)
    decode[::5] = pages * page                    # inactive slots
    cases = (("decode rows", decode, False),
             ("suffix prefill rows", stream(page // 2 + 3, prompt - 9,
                                            prompt), False),
             ("cold prefill blocks", stream(0, prompt - page // 3, prompt),
              True))
    for label, slot_ids, fresh in cases:
        rows = jax.random.normal(jax.random.PRNGKey(len(slot_ids)),
                                 (len(slot_ids), kvh, dh), jnp.bfloat16)
        ids = jnp.asarray(slot_ids, jnp.int32)
        got = jax.jit(lambda p, r, s: kv_write(
            p, r, s, rows_start_blocks=fresh, backend=backend))(
                pool, rows, ids)
        want = jax.jit(kv_write_reference)(pool, rows, ids)
        c.check(f"kv_write {label}: {len(slot_ids)} rows into "
                f"{tuple(pool.shape)} equal the scatter bit for bit",
                bool(jnp.array_equal(got, want)),
                f"{int(jnp.sum(jnp.any(got != pool, axis=(0, 3))))} rows "
                f"of the pool changed")


def _moe_case(c, *, tokens, width, expert, held, experts, top_k, tol=2e-2):
    """The held experts' grouped products against `lax.ragged_dot` on
    the same layout, and no assignment lost."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.flags import pallas_mode
    from paddle_tpu.ops.pallas.moe_experts import experts_ffn

    backend = "kernel" if pallas_mode() == "compiled" else "interpret"
    ks = jax.random.split(jax.random.PRNGKey(held), 4)
    gate_up = 0.02 * jax.random.normal(
        ks[0], (held, width, 2 * expert), jnp.float32).astype(jnp.bfloat16)
    down = 0.02 * jax.random.normal(
        ks[1], (held, expert, width), jnp.float32).astype(jnp.bfloat16)
    rng = np.random.RandomState(0)
    for t in tokens:
        x = jax.random.normal(ks[2], (t, width), jnp.bfloat16)
        w = jax.random.uniform(ks[3], (t, top_k), jnp.float32)
        chosen = jnp.asarray(np.stack([
            rng.choice(experts, top_k, replace=False) for _ in range(t)]),
            jnp.int32)
        got, sizes = jax.jit(lambda *a: experts_ffn(
            *a, first=1, backend=backend))(x, w, chosen, gate_up, down)
        want, _ = jax.jit(lambda *a: experts_ffn(
            *a, first=1, backend="reference"))(x, w, chosen, gate_up, down)
        c.close(tol, f"moe_experts {t} tokens x {top_k} on {held} of "
                     f"{experts} experts", got, want)
        landed = int(((np.asarray(chosen) >= 1)
                      & (np.asarray(chosen) < 1 + held)).sum())
        c.check(f"moe_experts {t} tokens: every held assignment counted",
                int(np.asarray(sizes).sum()) == landed, f"{landed}")


def _ring_case(c, *, b, nh, kvh, dh, page, ring, window, tol=2e-2):
    """Decode attention over a ring of pages with a lower bound."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.flags import pallas_mode
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel, paged_attention_decode_reference)

    ks = jax.random.split(jax.random.PRNGKey(ring), 3)
    q = jax.random.normal(ks[0], (b, nh, dh), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (kvh, b * ring, page, dh), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (kvh, b * ring, page, dh), jnp.bfloat16)
    lengths = np.random.RandomState(1).randint(0, 40 * page, b)
    lengths[:4] = [0, 1, page, window + 1]
    lengths = jnp.asarray(lengths, jnp.int32)
    rings = jnp.arange(b * ring, dtype=jnp.int32).reshape(b, ring)
    kw = dict(starts=jnp.maximum(lengths - window, 0), ring=True)
    got = jax.jit(lambda *a: paged_attention_decode_kernel(
        *a, interpret=pallas_mode() != "compiled", **kw))(
            q, kp, vp, lengths, rings)
    c.close(tol, f"paged decode {nh}/{kvh} heads over rings of {ring} "
                 f"pages, window {window}", got,
            paged_attention_decode_reference(
                *(x.astype(jnp.float32) for x in (q, kp, vp)), lengths,
                rings, **kw))


def _band_case(c, shape, kv_heads, window, tol=2e-2):
    """The banded causal flash forward against the masked softmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import _flash_fwd_bhsd

    b, h, s, d = shape
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], shape, jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, kv_heads, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, kv_heads, s, d), jnp.bfloat16)
    got, _ = _flash_fwd_bhsd(q, k, v, causal=True, scale=d ** -0.5,
                             window=window)
    f32 = lambda a: jnp.repeat(a.astype(jnp.float32), h // a.shape[1], 1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", f32(q), f32(k),
                        precision="highest") * d ** -0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i) & (i - j < window), scores,
                                     -jnp.inf), -1)
    c.close(tol, f"flash causal band {window} {shape} over {kv_heads} kv "
                 f"heads", got,
            jnp.einsum("bhqk,bhkd->bhqd", probs, f32(v), precision="highest"))


def phase_kernels(tiny):
    import paddle_tpu  # noqa: F401
    from paddle_tpu.core.flags import pallas_mode
    from paddle_tpu.device import chip

    chip.setup_compile_cache()
    sz = sizes(tiny)
    c = Checks("kernels")
    c.check("Pallas kernels are Mosaic-compiled",
            tiny or pallas_mode() == "compiled", f"mode {pallas_mode()}")
    shape = sz["flash_causal"]
    _flash_case(c, f"flash causal {shape}", shape, shape[1], causal=True)
    kv = max(shape[1] // 4, 1)
    _flash_case(c, f"flash causal GQA {shape[1]}/{kv} {shape}", shape, kv,
                causal=True)
    shape = sz["flash_bert"]
    _flash_case(c, f"flash non-causal {shape}", shape, shape[1],
                causal=False)
    _flash_case(c, f"flash non-causal dropout 0.1 {shape}", shape, shape[1],
                causal=False, rate=0.1)
    shape = sz["flash_bias"]
    for bias_batch in (shape[0], 1):
        _flash_case(c, f"flash key-bias[{bias_batch}] Sk={shape[2]} {shape}",
                    shape, shape[1], causal=False, bias=bias_batch)
    shape = sz["flash_prefill"]
    _flash_case(c, f"flash causal {shape}", shape, shape[1], causal=True)
    _varlen_case(c, *sz["varlen"])
    for shape in sz["rms"]:
        _rms_case(c, shape)
    p = sz["paged"]
    _paged_case(c, p["nh"], **p)
    _paged_case(c, p["nh"] // 4, **p)
    _kv_write_case(c, **sz["kv_write"])
    _moe_case(c, **sz["moe"])
    _ring_case(c, **sz["ring"])
    _band_case(c, *sz["flash_band"])
    c.finish()
    return {}


# ---------------------------------------------------------------------------
# phase: trainer (and its four-chip twin)
# ---------------------------------------------------------------------------
def _kernel_calls(hlo):
    """{kernel name: [HLO line]} of the Mosaic custom calls in a compiled
    program (the pallas_call ``name=`` is the instruction name)."""
    import re

    calls = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT )?%([a-z_]+?)(?:\.\d+)? = ", line)
            calls.setdefault(m.group(1) if m else "?", []).append(line)
    return calls


def _train(tiny, mesh=None):
    """Build the 645M trainer (sharded over ``mesh`` if given), run the
    steps on one fixed batch, and return what the phases assert on."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.observability as obs
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   llama_shard_plan)

    sz = sizes(tiny)
    obs.enable()                # jit.fallbacks is counted only when on
    paddle.seed(0)
    config = LlamaConfig(recompute=False, **sz["llama"])
    t0 = time.perf_counter()
    model = LlamaForCausalLM(config)
    n_params = model.num_parameters()
    model.bfloat16()
    ids_np = np.random.RandomState(0).randint(
        0, config.vocab_size, (sz["batch"], sz["seq"])).astype("int64")
    labels_np = np.roll(ids_np, -1, axis=1)
    if mesh is None:
        ids, labels = paddle.to_tensor(ids_np), paddle.to_tensor(labels_np)
    else:
        llama_shard_plan(model, mesh)
        rows = [dist.Shard(0), dist.Replicate()]
        ids = dist.shard_tensor(ids_np, mesh, rows)
        labels = dist.shard_tensor(labels_np, mesh, rows)
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          multi_precision=True)
    log(f"[train] built llama-{n_params / 1e6:.0f}M in "
        f"{time.perf_counter() - t0:.1f} s")

    @paddle.jit.to_static(full_graph=True)
    def train_step(ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    losses, times = [], []
    for _ in range(sz["steps"]):
        t0 = time.perf_counter()
        losses.append(float(train_step(ids, labels)))     # float() syncs
        times.append(time.perf_counter() - t0)
    log(f"[train] losses: {' '.join(f'{x:.4f}' for x in losses)}")
    log(f"[train] smoke output, not metrics: first call (trace + compile + "
        f"step) {times[0]:.1f} s; later steps "
        f"{' '.join(f'{t * 1e3:.0f}' for t in times[1:])} ms")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"[train] {d}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}  "
            f"bytes_limit {stats.get('bytes_limit', 'not reported')}")
    fallbacks = obs.registry.get("jit.fallbacks").total()
    return dict(config=config, model=model, optimizer=optimizer,
                train_step=train_step, losses=losses, fallbacks=fallbacks)


def _check_training(c, run, tiny):
    import math

    losses = run["losses"]
    c.check("losses finite", all(math.isfinite(x) for x in losses))
    c.check("loss falling over the steps", losses[-1] < losses[0],
            f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    c.check("to_static fallbacks == 0", run["fallbacks"] == 0,
            f"jit.fallbacks = {run['fallbacks']}")
    (lowered,) = run["train_step"].lowered()
    hlo = lowered.compile().as_text()
    if tiny:
        return hlo, {}
    calls = _kernel_calls(hlo)
    n_layers = run["config"].num_hidden_layers
    # flash_bwd_dkv names the one backward pass (dq, dk and dv)
    want = {"flash_fwd": n_layers, "flash_bwd_dkv": n_layers,
            "rms_norm_fwd": 2 * n_layers + 1,
            "rms_norm_bwd": 2 * n_layers + 1}
    got = {k: len(v) for k, v in calls.items()}
    # every attention and every norm of the step is a Mosaic call: none
    # went to the sdpa_p / rms_norm_p compositions
    c.check("compiled step holds the Pallas flash fwd, the one bwd kernel "
            "and RMSNorm fwd/bwd for every layer", got == want,
            f"Mosaic calls {got}")
    return hlo, calls


def phase_trainer(tiny):
    from paddle_tpu.device import chip

    chip.setup_compile_cache()
    run = _train(tiny)
    c = Checks("trainer")
    _check_training(c, run, tiny)
    c.finish()
    return {"loss0": run["losses"][0]}


def _gathered_before(hlo, calls, depth=4):
    """Names of Mosaic calls fed by an all-gather: one whose result
    reaches a kernel operand through producers of that operand's own
    size (bitcasts, copies, transposes, elementwise fusions) — q/k/v or
    the hidden state gathered to conform to the kernel's sharding.
    Gathers of other sizes further upstream (weights, loss chunks) are
    the surrounding program's business."""
    import math
    import re

    producers = {}      # instruction -> (opcode, operand names, elements)
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)", line)
        # the opcode is the first word( after the (possibly tuple) type
        op = m and re.search(r"(?:^|\s)([a-z][\w\-]*)\(", m.group(2))
        if op:
            shape = re.match(r"\w+\[([\d,]*)\]", m.group(2))
            size = shape and math.prod(
                int(d) for d in shape.group(1).split(",") if d)
            operands = m.group(2)[op.end():].split(")")[0]
            producers[m.group(1)] = (
                op.group(1), re.findall(r"%([\w.\-]+)", operands), size)
    unknown = ("", [], None)
    bad = set()
    for name, lines in calls.items():
        for line in lines:
            instr = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
            frontier = [(n, producers.get(n, unknown)[2])
                        for n in producers[instr][1]]
            for _ in range(depth):
                nxt = []
                for n, size in frontier:
                    op, srcs, own = producers.get(n, unknown)
                    if own is None or own != size:
                        continue
                    if op.startswith("all-gather"):
                        bad.add(name)
                    nxt += [(src, size) for src in srcs]
                frontier = nxt
    return sorted(bad)


def phase_four_chip(tiny, ref_loss):
    import jax
    import numpy as np

    import paddle_tpu.distributed as dist
    from paddle_tpu.core import flags
    from paddle_tpu.device import chip

    chip.setup_compile_cache()
    n = len(jax.devices())
    if n < 4:
        log(f"[four_chip] did not run: this machine has {n} device(s) and "
            f"the dp2 x mp2 phase needs 4")
        return {"ran": False}
    if tiny and flags.pallas_mode() == "off":
        flags.set_flags({"pallas_force_interpret": True})
    mesh = dist.ProcessMesh(np.arange(4).reshape(2, 2), ["dp", "mp"])
    run = _train(tiny, mesh)
    c = Checks("four_chip")
    hlo, calls = _check_training(c, run, tiny)
    sz = sizes(tiny)
    if not tiny:
        B, S = sz["batch"], sz["seq"]
        cfg = run["config"]
        # heads of 128: the kernels block the model's [B, S, H*D] view
        qkv = f"bf16[{B // 2},{S},{cfg.hidden_size // 2}]"
        rows = f"bf16[{B // 2 * S},{cfg.hidden_size}]"
        c.check(f"flash kernels run on per-shard {qkv} (B/dp, H*D/mp)",
                all(qkv in l for k in ("flash_fwd", "flash_bwd_dkv")
                    for l in calls.get(k, [""])))
        c.check(f"RMSNorm kernels run on per-shard {rows} (rows/dp)",
                all(rows in l for k in ("rms_norm_fwd", "rms_norm_bwd")
                    for l in calls.get(k, [""])))
        bad = _gathered_before(hlo, calls)
        c.check("no all-gather of q/k/v or the hidden state in front of a "
                "Mosaic call", not bad, f"gathered before: {bad}")
    # state of the sharded parameters: same sharding for the parameter,
    # its fp32 master and both Adam moments; nothing whole on one device
    optimizer = run["optimizer"]
    per_device, mismatched, whole = {}, [], []
    for name, p in run["model"].named_parameters():
        state = {"param": p._value,
                 "master": optimizer._master_weights[id(p)],
                 "moment1": optimizer._accumulators["moment1"][id(p)],
                 "moment2": optimizer._accumulators["moment2"][id(p)]}
        split = not p._value.sharding.is_fully_replicated
        for kind, arr in state.items():
            if not arr.sharding.is_equivalent_to(p._value.sharding, arr.ndim):
                mismatched.append(f"{name}.{kind}")
            if split and arr.addressable_shards[0].data.size == arr.size:
                whole.append(f"{name}.{kind}")
            for shard in arr.addressable_shards:
                per_device[shard.device] = (per_device.get(shard.device, 0)
                                            + shard.data.nbytes)
    c.check("parameter, master and both Adam moments share one sharding",
            not mismatched, f"{len(mismatched)} differ: {mismatched[:4]}")
    c.check("no state of an mp-sharded parameter is whole on a device",
            not whole, f"{len(whole)} whole: {whole[:4]}")
    lo, hi = min(per_device.values()), max(per_device.values())
    c.check("per-device state bytes within 5% of each other",
            len(per_device) == 4 and hi <= 1.05 * lo,
            "  ".join(f"{d.id}: {b / 1e9:.3f} GB"
                      for d, b in sorted(per_device.items(),
                                         key=lambda kv: kv[0].id)))
    if ref_loss is None:
        c.check("step-1 loss equals the one-chip loss", False,
                "the trainer phase gave no loss to compare with")
    else:
        c.check("step-1 loss equals the one-chip loss (bf16 tolerance)",
                abs(run["losses"][0] - ref_loss) <= 0.05,
                f"{run['losses'][0]:.4f} vs {ref_loss:.4f}")
    c.finish()
    return {"ran": True}


# ---------------------------------------------------------------------------
# phase: server
# ---------------------------------------------------------------------------
def _first_tokens_agree(c, label, model, requests):
    """The engine's first token (prefill) and second token (the first one
    through the paged decode kernel) against the model's own dense paths.
    On an untrained bf16 model near-tie argmaxes may flip between two
    attention formulations: a disagreement is accepted only where the
    model's own top-2 logit margin is at bf16 scale (a mask or position
    bug moves logits by O(1) and flips LARGE-margin tokens)."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle

    def margins(ids):
        logits = np.asarray(model(paddle.to_tensor(ids))._value[:, -1, :]
                            .astype(jnp.float32))
        top2 = np.sort(logits, axis=-1)[:, -2:]
        return logits.argmax(-1), top2[:, 1] - top2[:, 0]

    prompts = np.stack([r.prompt for r in requests]).astype("int64")
    first = np.array([r.output_ids[0] for r in requests])
    gen = model.generate(paddle.to_tensor(prompts),
                         max_new_tokens=1).numpy()[:, -1]
    _, margin = margins(prompts)
    bad = [i for i in range(len(requests))
           if first[i] != gen[i] and margin[i] >= 0.05]
    c.check(f"{label}: first tokens agree with generate() greedy",
            not bad, f"{int((first == gen).sum())}/{len(first)} equal; "
            f"large-margin disagreements: {bad}")
    second = np.array([r.output_ids[1] for r in requests])
    ref2, margin2 = margins(np.concatenate([prompts, first[:, None]], 1))
    bad = [i for i in range(len(requests))
           if second[i] != ref2[i] and margin2[i] >= 0.05]
    c.check(f"{label}: first paged-decode tokens agree with the full "
            f"forward", not bad,
            f"{int((second == ref2).sum())}/{len(second)} equal; "
            f"large-margin disagreements: {bad}")


def phase_server(tiny):
    import paddle_tpu as paddle
    from paddle_tpu.device import chip
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serve import ServeEngine, run_load
    from paddle_tpu.serve.load import (_metric_total, default_serving_setup,
                                       warm_engine)

    chip.setup_compile_cache()
    sz = sizes(tiny)
    paddle.seed(0)
    config, sp = default_serving_setup(not tiny)
    model = LlamaForCausalLM(config)
    model.bfloat16()
    model.eval()
    bs = sp["block_size"]
    c = Checks("server")
    runs = (("default", {}, dict(prompt_len=(bs, bs))),
            ("prefix+burst", dict(prefix_cache=True, decode_burst=8),
             dict(prompt_len=(bs // 4, bs // 4), shared_prefix_tokens=bs,
                  shared_prefix_frac=1.0)))
    for label, engine_kw, load_kw in runs:
        t0 = time.perf_counter()
        engine = ServeEngine(
            model, max_slots=sp["slots"], block_size=bs,
            num_blocks=sp["num_blocks"], max_seq_len=sp["max_seq_len"],
            name=f"smoke-{label}", **engine_kw)
        c.check(f"{label}: attention resolved to the compiled paged kernel",
                engine.attention_backend == ("reference" if tiny
                                             else "kernel"),
                f"attention_backend={engine.attention_backend!r}")
        # every prefill bucket a prompt of this run can land in
        warm_engine(engine, max_prompt_len=2 * bs)
        t_warm = time.perf_counter() - t0
        warm_traces = (engine.decode_traces, engine.prefill_traces)
        res = run_load(engine, rate=sp["rate"],
                       n_requests=sz["serve_requests"],
                       max_new=sz["serve_new"], seed=0, **load_kw)
        log(f"[server] {label}: warm-up (all compiles) {t_warm:.1f} s; "
            f"load: {json.dumps(res.to_dict())}")
        c.check(f"{label}: nothing was traced inside the load",
                (engine.decode_traces, engine.prefill_traces) == warm_traces,
                f"(decode, prefill) traces {warm_traces} after warm-up, "
                f"{(engine.decode_traces, engine.prefill_traces)} after")
        done = [r for r in res.requests if r.state == "FINISHED"]
        c.check(f"{label}: every request finished",
                len(done) == sz["serve_requests"] and not res.rejected
                and engine.pool.used_blocks == 0,
                f"{len(done)}/{sz['serve_requests']} finished, "
                f"{res.rejected} rejected, {res.total_tokens} tokens, "
                f"{engine.pool.used_blocks} blocks leaked")
        if engine.decode_burst > 1:
            # warm-up compiled one scan per pow2 burst length (1, 2, 4,
            # 8); the load must pick among those and add none
            c.check(f"{label}: one decode trace per pow2 burst length",
                    engine.decode_traces == 4 and res.burst_tokens > 0
                    and engine.burst_lens_used <= {1, 2, 4, 8},
                    f"decode_traces={engine.decode_traces} "
                    f"burst_lens_used={sorted(engine.burst_lens_used)}")
            c.check(f"{label}: later requests mounted the shared prefix",
                    res.prefix_hits > 0 and res.prefix_blocks_shared > 0,
                    f"hits={res.prefix_hits} "
                    f"blocks_shared={res.prefix_blocks_shared}")
            # a prompt that IS the resident prefix block: its last token
            # is recomputed into a copy-on-write duplicate (_cow_fn)
            cow0 = _metric_total("serve.cow_copies")
            req = engine.submit(res.requests[0].prompt[:bs],
                                max_new_tokens=2)
            engine.run()
            c.check(f"{label}: copy-on-write step ran",
                    req.state == "FINISHED"
                    and _metric_total("serve.cow_copies") == cow0 + 1)
        else:
            c.check(f"{label}: decode_traces == 1",
                    engine.decode_traces == 1,
                    f"decode_traces={engine.decode_traces}")
        _first_tokens_agree(c, label, model, res.requests)
    c.finish()
    return {}


# ---------------------------------------------------------------------------
# parent: runs the phases as sequential children, touches no JAX itself
# ---------------------------------------------------------------------------
def run_child(phase, args, deadline, results_dir, ref_loss):
    result = os.path.join(results_dir, f"{phase}.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--result", result]
    if args.tiny:
        cmd.append("--tiny")
    if ref_loss is not None:
        cmd += ["--ref-loss", repr(ref_loss)]
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, cwd=ROOT)
    try:
        rc = proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
    except subprocess.TimeoutExpired:
        rc = "timeout"
    finally:
        if proc.poll() is None:     # stop what we started, always
            proc.kill()
            proc.wait()
    log(f"[chip_smoke] phase {phase}: rc={rc} in "
        f"{time.monotonic() - t0:.0f} s")
    if rc != 0 or not os.path.exists(result):
        return None
    with open(result) as f:
        return json.load(f)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phase", choices=PHASES)
    ap.add_argument("--result", help="(child) where to write the result")
    ap.add_argument("--ref-loss", type=float, default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="dry run of the script at toy sizes; never passes")
    ap.add_argument("--only", nargs="+", choices=PHASES, default=PHASES,
                    help="run a subset of phases; the run then never "
                         "passes")
    args = ap.parse_args()

    if args.phase:                                  # child
        fn = globals()[f"phase_{args.phase}"]
        out = (fn(args.tiny, args.ref_loss) if args.phase == "four_chip"
               else fn(args.tiny))
        with open(args.result, "w") as f:
            json.dump(out, f)
        return 0

    deadline = time.monotonic() + DEADLINE_S
    env_flags = os.environ.get("XLA_FLAGS", "")
    if args.tiny and "host_platform_device_count" not in env_flags:
        os.environ["XLA_FLAGS"] = (
            env_flags + " --xla_force_host_platform_device_count=4").strip()
    results, failed = {}, []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        for phase in PHASES:
            if phase not in args.only:
                continue
            ref_loss = (results.get("trainer") or {}).get("loss0")
            out = run_child(phase, args, deadline, tmp, ref_loss)
            if out is None:
                failed.append(phase)
                if phase == "device":
                    break       # no accelerator: nothing else can run
            results[phase] = out
    if failed:
        log(f"[chip_smoke] FAILED phases: {', '.join(failed)}")
        return 1
    if args.tiny or tuple(args.only) != PHASES:
        log("[chip_smoke] partial or --tiny run: control flow only, no "
            "result")
        return 1
    print(json.dumps({"ok": True, "device": results["device"]["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
