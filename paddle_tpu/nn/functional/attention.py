"""Attention functional ops.

Reference: python/paddle/nn/functional/flash_attention.py:198 (flash_attention),
:602 (flash_attn_unpadded), :991 (scaled_dot_product_attention) over the
flashattn lib (phi/kernels/gpu/flash_attn_kernel.cu:35).

TPU design: a Pallas flash-attention kernel (ops/pallas/flash_attention.py)
is the fast path on real TPU; a reference XLA composition (fused by the
compiler, fp32 softmax accumulation) is the fallback and the numerics
oracle. Layout is paddle's [batch, seqlen, num_heads, head_dim].
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.flags import get_flag, pallas_mode
from ...core.tensor import Tensor, apply
from ...ops._helpers import defprim, ensure_tensor

__all__ = ["scaled_dot_product_attention", "flash_attention", "sdp_kernel"]

def _attn_dropout(probs, key, dropout_p):
    # reference semantics: dropout on the attention WEIGHTS (softmax output),
    # not the output activations (flash_attention.py:991 attn_dropout)
    if dropout_p > 0.0:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype))
    return probs


def _sdpa_xla(q, k, v, key, *, causal, scale, dropout_p):
    # q,k,v: [B, S, H, D] (paddle layout); kv heads may be fewer (GQA)
    qh, kh = q.shape[2], k.shape[2]
    if kh != qh:
        rep = qh // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), t - s)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    probs = _attn_dropout(probs, key, dropout_p)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _sdpa_mask_xla(q, k, v, mask, key, *, scale, dropout_p):
    qh, kh = q.shape[2], k.shape[2]
    if kh != qh:
        rep = qh // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    logits = logits + mask.astype(logits.dtype)
    # safe softmax: a row whose keys are ALL masked to -inf outputs exact
    # zeros instead of NaN — the same convention as the Pallas flash
    # kernel's l==0 finalize, so the two routes agree at every Sk
    lf = logits.astype(jnp.float32)
    row_max = jnp.max(lf, axis=-1, keepdims=True)
    dead = row_max == -jnp.inf
    e = jnp.exp(lf - jnp.where(dead, 0.0, row_max))
    denom = jnp.sum(e, axis=-1, keepdims=True)
    probs = jnp.where(dead, 0.0, e / jnp.where(dead, 1.0, denom))
    probs = probs.astype(q.dtype)
    probs = _attn_dropout(probs, key, dropout_p)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


defprim("sdpa_p", _sdpa_xla)
defprim("sdpa_mask_p", _sdpa_mask_xla)


# Masked-SDPA routing crossover, MEASURED on v5e (2026-07-31, fwd+bwd
# carry-chained, 7/8 keys live): S=512 xla 7.65ms vs flash 7.94; S=1024
# 11.80 vs 11.37; S=2048 12.16 vs 11.27; S=4096 14.61 vs 13.00. Below
# this the XLA composition's fused S^2 path is faster; at/above it the
# flash kernel wins AND avoids the O(S^2) probs buffer XLA materializes
# for backward (mandatory at long context).
_MASK_FLASH_MIN_SK = 1024


def _use_pallas(q, k):
    if not get_flag("use_pallas_flash_attention"):
        return False
    if pallas_mode() == "off":
        return False
    # lane-aligned seqlens, MXU-friendly head dim, divisible GQA groups
    return (q.shape[-1] % 64 == 0 and q.shape[1] % 128 == 0
            and k.shape[1] % 128 == 0 and q.shape[2] % k.shape[2] == 0)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, *, partition=None):
    """paddle.nn.functional.scaled_dot_product_attention parity
    (flash_attention.py:991). Input layout [B, S, H, D]. Dropout applies to
    the attention weights, matching the reference; the Pallas kernel
    regenerates the dropout mask in-kernel from a counter RNG, so a nonzero
    rate stays on the flash path (the masked path is still XLA).
    ``partition`` is the ``KernelPartition`` a sharded model's shard plan
    recorded: the flash kernel then runs per shard (XLA partitions the
    compositions itself)."""
    from ...core import generator

    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    p = float(dropout_p) if training else 0.0
    rng = Tensor._from_value(generator.next_key("local_seed"))
    if attn_mask is not None:
        m = ensure_tensor(attn_mask)
        if (_use_pallas(q, k) and p < 1.0 and m.ndim == 4
                and m.shape[1] == 1 and m.shape[2] == 1
                and m.shape[3] == k.shape[1]
                and m.shape[0] in (1, q.shape[0])
                and m.stop_gradient  # flash takes no bias grad; a
                # TRAINABLE additive bias must stay on the XLA path
                and k.shape[1] >= _MASK_FLASH_MIN_SK):
            # [B, 1, 1, Sk] additive padding mask: stays on the flash
            # path as a per-key logit bias instead of the XLA fallback
            from ...ops.pallas.flash_attention import flash_attention_fused

            # a batch-1 mask stays batch-1: the kernel's index map pins
            # it to row 0 rather than materializing B copies
            bias = m.reshape([m.shape[0], m.shape[3]]).astype("float32")
            bias.stop_gradient = True
            # causal=False: the sdpa_mask_p fallback gives the mask
            # precedence over is_causal — both paths must agree
            return flash_attention_fused(
                q, k, v, causal=False, scale=scale,
                dropout_p=p, rng=rng, key_bias=bias, partition=partition)
        out = apply("sdpa_mask_p", q, k, v, m, rng,
                    scale=scale, dropout_p=p)
    elif _use_pallas(q, k) and p < 1.0:
        # p == 1.0 would need 1/(1-p) rescale in-kernel; the XLA path
        # already produces the exact all-zero output for it
        from ...ops.pallas.flash_attention import flash_attention_fused

        out = flash_attention_fused(q, k, v, causal=bool(is_causal),
                                    scale=scale, dropout_p=p, rng=rng,
                                    partition=partition)
    else:
        out = apply("sdpa_p", q, k, v, rng, causal=bool(is_causal),
                    scale=scale, dropout_p=p)
    return out


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity
    (flash_attention.py:198). Returns (out, softmax_lse-placeholder)."""
    out = scaled_dot_product_attention(
        query, key, value, None, dropout, causal, training
    )
    return out, None


class sdp_kernel:
    """Context manager parity with paddle's kernel-dispatch selector."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        self.enable_flash = enable_flash
        self._prev = None

    def __enter__(self):
        from ...core import flags

        self._prev = flags.get_flag("use_pallas_flash_attention")
        flags.set_flags({"use_pallas_flash_attention": self.enable_flash})
        return self

    def __exit__(self, *exc):
        from ...core import flags

        flags.set_flags({"use_pallas_flash_attention": self._prev})
        return False
