"""Probe: this repo's flash attention kernels at the benchmark cells'
shapes, beside jax's bundled TPU kernels and the MXU's peak.

For each case (``CASES``: the train cell's causal attention, forward
and backward; the serving prefills of K-EXAONE, banded and plain,
DeepSeek-V2's 192/128 heads and Granite's heads of 64 at the 2,048
bucket, forward only) it chains calls in one ``lax.scan`` and prints
microseconds a call for

- this repo's kernels through their head-major entries
  (``_flash_fwd_bhsd`` / ``_flash_bwd_bhsd``), with the largest absolute
  error against the float32 composition (``highest`` precision);
- for the train case, the pair a layer runs (``flash_attention_bshd`` /
  ``_flash_vjp`` on ``[B, S, H, D]``: the layout's copies included), the
  two-kernel backward where this tree keeps one (``_bwd_split``) and a
  sweep of tile sizes where the local functions take ``blocks``;
- ``jax.experimental.pallas.ops.tpu.flash_attention`` and
  ``splash_attention`` where they take the shape;
- the least time at the MXU's bf16 peak (197 TFLOP/s, one v5e chip) for
  the count ``benchmark/work/flash_train.py`` uses: the causal half of
  QK^T and PV forward, 2.5 times that backward.

It imports nothing from ``benchmark/`` and is run by no cell. A tree's
figures are this file run under that tree (copy it into the parent's
``tools/``); the sections a tree lacks are left out.

    chiprun -- python3 tools/flash_kernel_probe.py
    python3 tools/flash_kernel_probe.py --tiny    # here: control flow only

MEASURED (v5e, one chip, my chip runs, PR 34; us a call, 40 calls
chained; "parent" is commit b710d04 under this file; in brackets the
share of the MXU's peak for the count above):

  train cgpt590m 2k (4 x 12 heads x 2,048 x 128, bfloat16, causal;
  262 us forward and 654 backward at the peak)
                                        parent          this tree
    forward, head-major entry           1,348 (19.4)      554 (47.2)
    backward, head-major entry          2,089 (31.3)    1,062 (61.6)
      the two kernels (_bwd_split)                      2,092 (31.3)
    a layer's forward, [B, S, H*D]      1,399             612
    a layer's backward, [B, S, H*D]     2,348           1,109
    largest abs error, forward / grads  0.0048 / 0.0078   0.0048 / 0.0078
    (the final tree, every causal tile masked: 552, 1,064, 611, 1,106)
    jax flash_attention forward 3,327, with its backward 14,620
    jax splash_attention (512 tiles, fused backward) forward 663,
      with its backward 2,073-2,103

  by part of ISSUE 34 (predicted -> read):
    1 one backward pass: 2,090 -> 1,200-1,400 predicted with parts 2
      and 3; 2,092 -> 1,062 read (the split form above is the parent's
      two kernels as they were; parts 1-3 cannot be told apart in the
      backward beyond what part 2's line says)
    2 tiles: the emptied tiles cost no step and no DMA (a prefetched
      list of the pairs to visit). MASKING ONLY THE TILES THE EDGE
      CROSSES DID NOTHING: with every tile masked the forward read 560
      against 556 and the backward 1,068 against 1,067, so the second
      copy of each kernel's body went and every causal tile is masked.
      512 x 512 tiles stayed (sweep below): 256-wide k blocks read 829
      where the count hoped for 9/10 of the time
    3 statistics and operands: the rest of the forward's 1,348 -> 554
      (lane-replicated maximum and sum, bfloat16 operands, q scaled
      once a block); `broadcast_in_dim` gone from the traced step
      (0.089 s of 5.88 -> none)
    4 layout: a layer's pair 3,747 -> 1,721 where the entries' pair is
      3,437 -> 1,616: the [B, S, H*D] blocks cost the kernels 58 + 47
      us and save the layer 205 of the parent's 310 us of copies

  tiles (block_q, block_k), this tree      forward        backward
    (512, 512)  the rule                     571           1,062
    (512, 256)                               829           1,232
    (256, 512)                               685           1,226
    (1024, 512)                              628           1,168
    (1024, 256)                              733           1,268
    (1024, 1024) / (2048, 512)               634 / 791
    (256, 256) / (512, 1024)                               1,632 / 1,165

  serving prefills, forward only            parent        this tree
    kexaone 2k band (64/8 heads, window 128) 1,221           556
    kexaone 2k plain                         1,585           726 (48.1)
    dsv2 2k 192/128 (128 heads)              4,289         1,992 (43.8)
    granite 2k heads of 64 (32/8)              799           374 (23.3)
    512 x 512 was fastest in each but the band, where (256, 512) read
    525 against 555; largest abs error 0.0044-0.0049 on both trees
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from paddle_tpu.core.flags import pallas_mode  # noqa: E402
from paddle_tpu.device import chip  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402

ON_TPU = pallas_mode() == "compiled"
MXU_PEAK = 197e12  # bf16 FLOP/s of one v5e chip (Google Cloud, "TPU v5e")

# name: batch, q heads, kv heads, sequence, head size of q and k, of v,
# window (None: plain causal), backward too
CASES = {
    "train cgpt590m 2k": (4, 12, 12, 2048, 128, 128, None, True),
    "kexaone prefill 2k band": (1, 64, 8, 2048, 128, 128, 128, False),
    "kexaone prefill 2k plain": (1, 64, 8, 2048, 128, 128, None, False),
    "dsv2 prefill 2k 192/128": (1, 128, 128, 2048, 192, 128, None, False),
    "granite prefill 2k d64": (1, 32, 8, 2048, 64, 64, None, False),
}
TINY = {
    "train tiny": (1, 2, 2, 256, 128, 128, None, True),
    "band tiny": (1, 4, 2, 256, 128, 128, 128, False),
    "two sizes tiny": (1, 2, 2, 128, 192, 128, None, False),
    "d64 tiny": (1, 4, 2, 128, 64, 64, None, False),
}
FWD_BLOCKS = [(512, 512), (512, 256), (1024, 512), (1024, 256), (256, 512),
              (1024, 1024), (2048, 512)]
BWD_BLOCKS = [(512, 512), (512, 256), (256, 512), (1024, 512), (1024, 256),
              (256, 256), (512, 1024)]


def operands(case, seed=0):
    b, h, hkv, s, d, dv, _, _ = case
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda key, *shape: (jax.random.normal(key, shape, jnp.float32)
                              * 0.5).astype(jnp.bfloat16)
    return (mk(ks[0], b, h, s, d), mk(ks[1], b, hkv, s, d),
            mk(ks[2], b, hkv, s, dv), mk(ks[3], b, h, s, dv))


def composition(q, k, v, window):
    """[B, H, S, D] float32 masked softmax attention, GQA by repeat."""
    g = q.shape[1] // k.shape[1]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    ahead = jnp.arange(s.shape[-1])[None, :] - jnp.arange(s.shape[-2])[:, None]
    seen = ahead <= 0
    if window is not None:
        seen &= ahead > -window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def time_chain(fn, carry, rest, steps):
    """Seconds a call: ``steps`` calls of ``fn(carry, *rest)`` chained in
    one scan; the first elements of every result go into the next call's
    first operand, so no call starts before the last one ends, no result
    is dead code and no copy of an operand runs between calls."""
    @jax.jit
    def chained(c0):
        def body(c, _):
            outs = jax.tree_util.tree_leaves(fn(c, *rest))
            tip = sum(o[(0,) * o.ndim].astype(jnp.float32) for o in outs)
            return c.at[(0,) * c.ndim].set(tip.astype(c.dtype)), ()
        return jax.lax.scan(body, c0, None, length=steps)[0]

    jax.block_until_ready(chained(carry))
    t0 = time.perf_counter()
    jax.block_until_ready(chained(carry))
    return (time.perf_counter() - t0) / steps


def report(label, fn, carry, rest, steps, floor_us=None, err=None):
    try:
        if err is not None:
            err = err()
        us = time_chain(fn, carry, rest, steps) * 1e6
    except Exception as e:  # noqa: BLE001 — a kernel that refuses the shape
        print(f"  {label}: not taken ({type(e).__name__}: "
              f"{str(e).splitlines()[0][:120]})")
        return None
    tail = "" if floor_us is None else f"  ({100 * floor_us / us:.1f}% of peak)"
    tail += "" if err is None else f"  max abs err {err:.4f}"
    print(f"  {label}: {us:.0f} us{tail}")
    return us


def bundled_flash(causal_scale):
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    def call(q, k, v):
        # jax's kernels mix Python ints into int32 arithmetic, which
        # `jax_enable_x64` (on with `import paddle_tpu`) turns into int64
        with jax.enable_x64(False):
            return jfa.flash_attention(q, k, v, causal=True,
                                       sm_scale=causal_scale)
    return call


def splash(h, s, window):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    one = (sm.CausalMask((s, s)) if window is None
           else sm.LocalMask((s, s), (window - 1, 0), 0))
    with jax.enable_x64(False):
        kernel = sk.make_splash_mha_single_device(
            sm.MultiHeadMask([one] * h), block_sizes=sk.BlockSizes(
                block_q=512, block_kv=512, block_kv_compute=512,
                block_q_dkv=512, block_kv_dkv=512, block_kv_dkv_compute=512,
                use_fused_bwd_kernel=True))

    def call(q, k, v):
        with jax.enable_x64(False):
            return jax.vmap(kernel)(q * q.shape[-1] ** -0.5, k, v)
    return call


def probe_case(name, case, steps, sweep):
    b, h, hkv, s, d, dv, window, train = case
    q, k, v, do = operands(case)
    scale = d ** -0.5
    fwd_flops = 2 * b * h * s * s * (d + dv) / 2
    if window is not None:
        fwd_flops = 2 * b * h * s * window * (d + dv)
    fwd_floor = fwd_flops / MXU_PEAK * 1e6
    print(f"{name}: B {b}, heads {h}/{hkv}, S {s}, head {d}/{dv}, "
          f"window {window}; forward {fwd_floor:.0f} us at the MXU's peak"
          + (f", backward {2.5 * fwd_floor:.0f}" if train else ""))
    ref = jax.jit(lambda q, k, v: composition(q, k, v, window))
    band = {} if window is None else {"window": window}
    fwd = lambda q, k, v: fa._flash_fwd_bhsd(q, k, v, causal=True,
                                             scale=scale, **band)
    report("this repo, forward", fwd, q, (k, v), steps, fwd_floor,
           err=lambda: _err(jax.jit(fwd)(q, k, v)[0], ref(q, k, v)))
    local_kw = dict(causal=True, scale=scale, rate=0.0, has_bias=False,
                    interpret=not ON_TPU)
    if sweep and "blocks" in fa._fwd_local.__code__.co_varnames:
        for blocks in FWD_BLOCKS:
            if s % blocks[0] or s % blocks[1]:
                continue
            report(f"  forward, tiles {blocks}",
                   lambda q, k, v: fa._fwd_local(
                       q, k, v, blocks=blocks, **local_kw, **band),
                   q, (k, v), steps, fwd_floor)
    if d == dv and h == hkv and window is None:
        report("jax flash_attention, forward", bundled_flash(scale), q,
               (k, v), steps, fwd_floor)
    if d == dv and h == hkv:
        try:
            sp = splash(h, s, window)
        except Exception as e:  # noqa: BLE001
            print(f"  jax splash_attention: not taken ({type(e).__name__}: "
                  f"{str(e).splitlines()[0][:120]})")
            sp = None
        if sp is not None:
            report("jax splash_attention, forward", sp, q, (k, v), steps,
                   fwd_floor)
    if not train:
        return
    out, lse = jax.jit(fwd)(q, k, v)
    bwd = lambda q, k, v, out, lse, do: fa._flash_bwd_bhsd(
        q, k, v, out, lse, do, causal=True, scale=scale)

    def grad_err():
        want = jax.jit(lambda q, k, v, do: jax.vjp(
            lambda *a: composition(*a, window), q, k, v)[1](
                do.astype(jnp.float32)))(q, k, v, do)
        got = jax.jit(bwd)(q, k, v, out, lse, do)
        return max(_err(g, w) for g, w in zip(got, want))

    bwd_floor = 2.5 * fwd_floor
    report("this repo, backward", bwd, q, (k, v, out, lse, do), steps,
           bwd_floor, err=grad_err)
    if hasattr(fa, "_bwd_split"):
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
        report("  backward, two kernels (_bwd_split)",
               lambda q, k, v, do, lse, delta: fa._bwd_split(
                   q, k, v, do, lse, delta, None, None, causal=True,
                   scale=scale, rate=0.0, interpret=not ON_TPU),
               q, (k, v, do, lse, delta), steps, bwd_floor)
    if sweep and "blocks" in fa._bwd_local.__code__.co_varnames:
        for blocks in BWD_BLOCKS:
            if s % blocks[0] or s % blocks[1]:
                continue
            report(f"  backward, tiles {blocks}",
                   lambda q, k, v, out, lse, do: fa._bwd_local(
                       q, k, v, out, lse, do, blocks=blocks, **local_kw),
                   q, (k, v, out, lse, do), steps, bwd_floor)
    # what a layer runs: [B, S, H*D] as the projections hand it over and
    # take it back, viewed [B, S, H, D]; the layout's copies (where a
    # tree makes them) included
    q3, k3, v3, do3 = (jnp.swapaxes(x, 1, 2).reshape(b, s, -1)
                       for x in (q, k, v, do))
    heads = lambda x, n: x.reshape(b, s, n, -1)

    def layer_fwd(q3, k3, v3):
        out, lse = fa.flash_attention_bshd(
            heads(q3, h), heads(k3, hkv), heads(v3, hkv), causal=True,
            scale=scale)
        return out.reshape(b, s, -1), lse

    def layer_bwd(q3, k3, v3, out3, lse, do3):
        grads = fa._flash_vjp(
            (heads(do3, h),), (heads(q3, h), heads(k3, hkv), heads(v3, hkv),
                               heads(out3, h), lse), causal=True, scale=scale)
        return [g.reshape(b, s, -1) for g in grads[:3]]

    report("this repo, a layer's forward ([B, S, H*D])", layer_fwd, q3,
           (k3, v3), steps, fwd_floor)
    out3, lse3 = jax.jit(layer_fwd)(q3, k3, v3)
    report("this repo, a layer's backward ([B, S, H*D])", layer_bwd, q3,
           (k3, v3, out3, lse3, do3), steps, bwd_floor)
    if d == dv and h == hkv:
        for label, make in (("jax flash_attention", lambda: bundled_flash(
                scale)), ("jax splash_attention", lambda: splash(
                    h, s, window))):
            try:
                f = make()
            except Exception as e:  # noqa: BLE001
                print(f"  {label}: not taken ({type(e).__name__})")
                continue
            def both(q, k, v, do, f=f):
                with jax.enable_x64(False):   # the backward's trace too
                    return jax.vjp(f, q, k, v)[1](do)
            report(f"{label}, forward and backward", both, q, (k, v, do),
                   steps, fwd_floor + bwd_floor)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes, two calls a chain: control flow only")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--no-sweep", action="store_true")
    ap.add_argument("--only", default="", help="cases whose name holds this")
    args = ap.parse_args()
    chip.setup_compile_cache()
    print(f"# device: {chip.device_info()}  pallas_mode: {pallas_mode()}")
    if not (ON_TPU or args.tiny):
        sys.exit("no TPU attached: times come from the chip only (--tiny "
                 "rehearses the control flow under the interpreter)")
    for name, case in (TINY if args.tiny else CASES).items():
        if args.only not in name:
            continue
        probe_case(name, case, 2 if args.tiny else args.steps,
                   sweep=not args.no_sweep and not args.tiny)
