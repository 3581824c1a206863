"""The inside of a Mamba-2 (SSD) mixer, on arrays: what lies between its
input projection and its gated norm, in the two forms a server runs it.

The layer's equations (Dao & Gu 2024, as ``transformers``'
``modeling_bamba.py`` / ``modeling_granitemoehybrid.py`` compute them): the
projection gives a token ``[z | xBC | dt]``; ``xBC`` passes a causal
depthwise convolution of ``K`` taps and a SiLU and splits into ``x``
``[heads, dh]``, ``B`` and ``C`` ``[n]`` (one group); ``dt = softplus(dt +
dt_bias)`` and ``A = -exp(A_log)`` per head; per head the state ``H`` ``[dh,
n]`` moves ``H_t = exp(dt_t A) H_(t-1) + dt_t x_t (outer) B_t`` and reads
``y_t = H_t C_t + D x_t``.

- :func:`mamba2_step`: one token a slot from the cached state (the
  recurrence itself, ``ops/pallas/ssm_decode``) and the cached tail of the
  convolution's input.
- :func:`mamba2_prefill`: a whole prompt from a zero state, as a CHUNKED
  scan (:func:`ssd_chunked`): inside a chunk of ``chunk`` tokens the
  recurrence is a masked matrix product, between chunks a scan over the
  chunks' states: never a loop over tokens, never a ``[heads, T, T]``
  tensor over the prompt. Returns the state and the tail the last REAL
  token leaves: a bucket's pad rows are given ``dt = 0``, which is the
  identity on ``H`` (decay 1, nothing added).

The arithmetic of both is float32 (matrix products at ``highest``
precision: on a TPU a float32 product is otherwise one bfloat16 pass);
what is stored between steps is the caller's, in its cache's type.
A layer's leaves (``lp``): ``conv_w`` ``[K, C]`` (tap ``k`` weighs the
input ``K - 1 - k`` tokens back), ``conv_b`` ``[C]``, ``dt_bias``,
``A_log``, ``D`` ``[heads]``. ``st`` holds the statics ``heads``, ``dh``,
``n``, ``chunk``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas.moe_experts import scoped
from .pallas.ssm_decode import pack_state, ssm_decode

__all__ = ["mamba2_step", "mamba2_prefill", "ssd_chunked", "conv_taps",
           "step_sizes"]

HI = lax.Precision.HIGHEST


def conv_taps(taps, lp, dtype):
    """``silu(sum_k conv_w[k] * taps[k] + conv_b)`` in float32, rounded to
    the activations' ``dtype``: ``taps`` ``[K, ..., C]`` holds, oldest
    first, the convolution's inputs up to the token computed. The one
    formula of both forms, so that a state rebuilt by a prefill continues
    as the decoded one would."""
    f32 = jnp.float32
    w = lp["conv_w"].astype(f32)
    shape = (w.shape[0],) + (1,) * (taps.ndim - 2) + (w.shape[1],)
    acc = jnp.sum(taps.astype(f32) * w.reshape(shape), axis=0)
    return jax.nn.silu(acc + lp["conv_b"].astype(f32)).astype(dtype)


def step_sizes(dt, lp):
    """(``softplus(dt + dt_bias)`` float32, ``A = -exp(A_log)``)."""
    f32 = jnp.float32
    return (jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32)),
            -jnp.exp(lp["A_log"].astype(f32)))


def _split(xbc, st):
    """(x [rows, heads, dh], B [rows, n], C [rows, n]) of the convolved
    ``xBC``."""
    nh, dh, n = st["heads"], st["dh"], st["n"]
    inner = nh * dh
    return (xbc[:, :inner].reshape(-1, nh, dh), xbc[:, inner:inner + n],
            xbc[:, inner + n:])


def mamba2_step(lp, st, xbc, dt, tail, state, live, *, backend="auto",
                scope=None):
    """One token a slot. ``xbc`` ``[S, C]`` and ``dt`` ``[S, heads]`` are
    the projection's; ``tail`` ``[K - 1, S, C]`` the convolution's last
    inputs (oldest first) and ``state`` the cached state of the ``S``
    slots (``ssm_decode.state_shape``), both updated for the ``live`` rows and left as they are for the
    others. Returns (y ``[S, heads * dh]`` float32, tail, state)."""
    named = scoped(scope)
    with named("conv"):
        taps = jnp.concatenate([tail, xbc[None].astype(tail.dtype)], 0)
        x, b, c = _split(conv_taps(taps, lp, xbc.dtype), st)
        tail = jnp.where(live[None, :, None], taps[1:], tail)
    with named("scan"):
        dt, a = step_sizes(dt, lp)
        y, state = ssm_decode(state, x, dt, a, b, c, lp["D"], live,
                              backend=backend)
    return y.reshape(y.shape[0], -1), tail, state


def ssd_chunked(x, dt, a, b, c, chunk):
    """The recurrence over ``T`` tokens from a zero state, by chunks.
    ``x`` ``[T, heads, dh]``, ``dt`` ``[T, heads]`` float32 (0 on a pad
    row), ``a`` ``[heads]``, ``b`` / ``c`` ``[T, n]``. Returns (``H_t C_t``
    ``[T, heads, dh]`` float32, the last state ``[heads, dh, n]``
    float32). ``T`` is a multiple of the chunk's length, ``min(chunk,
    T)``."""
    f32 = jnp.float32
    t, nh, dh = x.shape
    n = b.shape[-1]
    ln = min(chunk, t)
    if t % ln:
        raise ValueError(f"{t} tokens are not whole chunks of {ln}")
    nc = t // ln
    b = b.astype(f32).reshape(nc, ln, n)
    c = c.astype(f32).reshape(nc, ln, n)
    xdt = (x.astype(f32) * dt[..., None]).reshape(nc, ln, nh, dh)
    # log-decays summed inside each chunk, the row's own included
    acs = jnp.cumsum((dt * a[None, :]).reshape(nc, ln, nh).transpose(
        0, 2, 1), axis=-1)                                 # [nc, nh, ln]
    # inside a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(acs_l - acs_s) xdt_s
    at = jnp.arange(ln)
    seen = at[:, None] >= at[None, :]
    seg = acs[:, :, :, None] - acs[:, :, None, :]          # [nc, nh, l, s]
    cb = jnp.einsum("cln,csn->cls", c, b, precision=HI)
    w = jnp.where(seen, jnp.exp(jnp.where(seen, seg, 0.0)), 0.0) \
        * cb[:, None]
    y = jnp.einsum("chls,cshp->clhp", w, xdt, precision=HI)
    # what a chunk adds to the state at its end
    to_end = jnp.exp(acs[:, :, -1:] - acs).transpose(0, 2, 1)  # [nc, ln, nh]
    added = jnp.einsum("clhp,cln->chpn", xdt * to_end[..., None], b,
                       precision=HI)
    # between chunks: the state before each chunk, and after the last
    whole = jnp.exp(acs[:, :, -1])                         # [nc, nh]

    def over(h, chunk_):
        add, decay = chunk_
        return h * decay[:, None, None] + add, h

    last, before = lax.scan(over, jnp.zeros((nh, dh, n), f32),
                            (added, whole))
    y = y + jnp.einsum("cln,chpn->clhp", c, before, precision=HI) \
        * jnp.exp(acs).transpose(0, 2, 1)[..., None]
    return y.reshape(t, nh, dh), last


def mamba2_prefill(lp, st, xbc, dt, n, *, scope=None):
    """A prompt of ``n`` tokens padded to ``T`` rows, from a zero state
    and an empty tail. ``xbc`` ``[T, C]``, ``dt`` ``[T, heads]``. Returns
    (y ``[T, heads * dh]`` float32 (the pad rows' are never read), the
    tail ``[K - 1, C]`` that rows ``n - K + 1 .. n - 1`` leave, the state
    after row ``n - 1`` in float32, as one row of
    ``ssm_decode.state_shape``'s layout)."""
    named = scoped(scope)
    t = xbc.shape[0]
    k = lp["conv_w"].shape[0]
    with named("conv"):
        padded = jnp.concatenate(
            [jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc], 0)
        taps = jnp.stack([padded[j:j + t] for j in range(k)])
        x, b, c = _split(conv_taps(taps, lp, xbc.dtype), st)
        # rows n - (K - 1) .. n - 1, zeros where the prompt is shorter
        tail = lax.dynamic_slice_in_dim(padded, n, k - 1, axis=0)
    with named("scan"):
        dt, a = step_sizes(dt, lp)
        dt = jnp.where((jnp.arange(t) < n)[:, None], dt, 0.0)
        y, state = ssd_chunked(x, dt, a, b, c, st["chunk"])
        y = y + lp["D"].astype(jnp.float32)[None, :, None] \
            * x.astype(jnp.float32)
    return y.reshape(t, -1), tail, pack_state(state)
