"""Context parallelism: ring attention + Ulysses (all-to-all) attention.

The reference has NO ring attention / Ulysses / blockwise CP — long context
is handled only by flash-attn + Megatron-SP and the extra "sep" topology
axis (SURVEY §5.7; reference `fleet/base/topology.py:188`,
`fleet/meta_parallel/segment_parallel.py:26`,
`auto_parallel/operators/dist_flash_attn.py:38` is RNG control only).
This module supplies the TPU-native design the metric set demands:

- **Ring attention** (`ring_attention`): Q stays put, K/V blocks rotate
  around the sep mesh axis via `lax.ppermute` over ICI, merged with the
  flash-attention online-softmax recurrence — exact attention over the full
  sequence with per-device memory O(S/n). Compute for step i overlaps the
  permute for step i+1 (XLA schedules the ppermute asynchronously).
- **Ulysses** (`ulysses_attention`): two `lax.all_to_all`s swap the shard
  axis seq↔heads so each device runs *full-sequence* attention for H/n
  heads — cheaper than a ring when num_heads ≥ n and ICI all-to-all
  bandwidth is plentiful.

Both run inside `shard_map` over the `ProcessMesh`'s sep axis, compose with
jit/GSPMD (dp/mp axes untouched), and are reverse-differentiable (ppermute/
all_to_all have transposes; the python ring loop is unrolled — the axis
size is static).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def shard_map(fn, mesh, in_specs, out_specs):
    # replication checking is disabled: ppermute/all_to_all bodies are not
    # representable under it
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

from ...core.tensor import Tensor, apply
from ...ops._helpers import defprim, ensure_tensor
from ..auto_parallel.placement import ProcessMesh

_NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale):
    """One flash block: returns (numerator [B,s,H,D], rowmax m, rowsum l).

    q [B,sq,H,D] x k [B,sk,H,D] — contraction in fp32 for stability.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                          # [B,H,sq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m == -inf-ish → make their contribution exactly 0
    p = jnp.where((m > _NEG_INF / 2)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)                          # [B,H,sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, jnp.where(m > _NEG_INF / 2, m, _NEG_INF), l


def _merge(o, m, l, o2, m2, l2):
    """Online-softmax merge of two partial blocks (flash recurrence)."""
    m_new = jnp.maximum(m, m2)
    a = jnp.exp(m - m_new)
    b = jnp.exp(m2 - m_new)
    o_new = o * a[..., None].swapaxes(1, 2) + o2 * b[..., None].swapaxes(1, 2)
    l_new = l * a + l2 * b
    return o_new, m_new, l_new


def _ring_attn_local(q, k, v, *, axis, n, chunk, causal, scale):
    """Per-device body under shard_map: q fixed, k/v rotate n-1 times."""
    idx = lax.axis_index(axis)
    b, sq, h, d = q.shape
    qf = q.astype(jnp.float32)
    q_pos = idx * chunk + jnp.arange(sq)             # global query positions
    o = jnp.zeros((b, sq, h, d), jnp.float32)
    m = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    # NOTE(perf): with causal=True, blocks where src > idx are fully
    # masked; a zigzag chunk layout (device i holds chunks i and 2n-1-i)
    # would balance causal work and ~halve compute at large n. Kept
    # contiguous for layout simplicity; revisit when CP perf matters.
    perm = [(j, (j + 1) % n) for j in range(n)]
    for i in range(n):
        src = (idx - i) % n                          # whose k/v we hold now
        if causal:
            k_pos = src * chunk + jnp.arange(k.shape[1])
            mask = q_pos[:, None] >= k_pos[None, :]  # [sq, sk]
            mask = mask[None, None]                  # [1,1,sq,sk]
        else:
            mask = None
        o2, m2, l2 = _block_attn(qf, k.astype(jnp.float32),
                                 v.astype(jnp.float32), mask, scale)
        o, m, l = _merge(o, m, l, o2, m2, l2)
        if i != n - 1:
            k = lax.ppermute(k, axis, perm)
            v = lax.ppermute(v, axis, perm)
    out = o / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-kernel ring attention (production path): per-rotation Pallas flash
# blocks merged in lse form. Per-device memory stays O(chunk·D) — the einsum
# ring materializes an O(chunk²) score block per rotation, which is exactly
# the wall long-context CP exists to avoid. Backward is the ring-attention
# algorithm (Liu et al. formulation): per-block flash backward against the
# GLOBAL lse (which exactly captures the merge-weight gradients), with dk/dv
# partials rotating alongside k/v and one final hop delivering them home.
# Gradients validated against jax.grad of the einsum ring to ~5e-8
# (tests/test_context_parallel.py::test_flash_ring_matches_einsum_ring).
# ---------------------------------------------------------------------------
def _ring_flash_loop(q, k, v, *, axis, n, causal, scale):
    from ...ops.pallas.flash_attention import _flash_fwd_bhsd

    idx = lax.axis_index(axis)
    qt = jnp.swapaxes(q, 1, 2)                       # [B, H, sq, D]
    o = jnp.zeros(qt.shape, jnp.float32)
    lse = jnp.full(qt.shape[:3], -jnp.inf, jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]
    for i in range(n):
        kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
        if causal and i == 0:
            # rotation 0 holds OUR OWN keys: the causal diagonal block
            o2, lse2 = _flash_fwd_bhsd(qt, kt, vt, causal=True, scale=scale)
        else:
            o2, lse2 = _flash_fwd_bhsd(qt, kt, vt, causal=False, scale=scale)
            if causal:
                # rotations where we hold FUTURE keys (idx < i after the
                # wrap) contribute nothing; -inf lse zeroes their weight
                lse2 = jnp.where(idx < i, -jnp.inf, lse2)
        lse_new = jnp.logaddexp(lse, lse2)
        finite = jnp.isfinite(lse_new)
        w1 = jnp.where(finite, jnp.exp(lse - lse_new), 0.0)[..., None]
        w2 = jnp.where(finite, jnp.exp(lse2 - lse_new), 0.0)[..., None]
        o = o * w1 + o2.astype(jnp.float32) * w2
        lse = lse_new
        if i != n - 1:
            k = lax.ppermute(k, axis, perm)
            v = lax.ppermute(v, axis, perm)
    return o, lse


def _ring_flash_local_factory(axis, n, causal, scale):
    """Build the jax-differentiable per-device ring body (custom_vjp is
    per-(axis, n, causal, scale) since those are nondiff statics)."""
    from ...ops.pallas.flash_attention import _flash_bwd_bhsd

    @jax.custom_vjp
    def ring(q, k, v):
        o, _ = _ring_flash_loop(q, k, v, axis=axis, n=n, causal=causal,
                                scale=scale)
        return jnp.swapaxes(o, 1, 2).astype(q.dtype)

    def ring_fwd(q, k, v):
        o, lse = _ring_flash_loop(q, k, v, axis=axis, n=n, causal=causal,
                                  scale=scale)
        return (jnp.swapaxes(o, 1, 2).astype(q.dtype),
                (q, k, v, o.astype(q.dtype), lse))

    def ring_bwd(saved, do):
        q, k, v, out_bhsd, lse = saved
        idx = lax.axis_index(axis)
        qt = jnp.swapaxes(q, 1, 2)
        dot = jnp.swapaxes(do, 1, 2)
        perm = [(j, (j + 1) % n) for j in range(n)]
        dq = jnp.zeros(qt.shape, jnp.float32)
        dk = jnp.zeros(jnp.swapaxes(k, 1, 2).shape, jnp.float32)
        dv = jnp.zeros_like(dk)
        for i in range(n):
            kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
            dqi, dki, dvi = _flash_bwd_bhsd(
                qt, kt, vt, out_bhsd, lse, dot,
                causal=bool(causal and i == 0), scale=scale)
            if causal and i > 0:
                alive = (idx >= i).astype(jnp.float32)
                dqi, dki, dvi = dqi * alive, dki * alive, dvi * alive
            dq = dq + dqi.astype(jnp.float32)
            dk = dk + dki.astype(jnp.float32)
            dv = dv + dvi.astype(jnp.float32)
            if i != n - 1:
                k = lax.ppermute(k, axis, perm)
                v = lax.ppermute(v, axis, perm)
                dk = lax.ppermute(dk, axis, perm)
                dv = lax.ppermute(dv, axis, perm)
        # the k/v held after the last rotation came from device idx+1;
        # one more hop delivers every accumulated (dk, dv) home
        dk = lax.ppermute(dk, axis, perm)
        dv = lax.ppermute(dv, axis, perm)
        return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
                jnp.swapaxes(dk, 1, 2).astype(k.dtype),
                jnp.swapaxes(dv, 1, 2).astype(v.dtype))

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


def _ring_use_flash(chunk: int, head_dim: int, nq: int, nkv: int) -> bool:
    from ...core.flags import get_flag, pallas_mode

    if not get_flag("use_pallas_flash_attention") or pallas_mode() == "off":
        return False
    # non-divisible GQA head counts would silently floor-divide in the
    # kernel's kv-head map; let them fall back to the einsum path, which
    # rejects them with a shape error instead
    return chunk % 128 == 0 and head_dim % 64 == 0 and nq % nkv == 0


def _ring_attn_fwd(q, k, v, *, mesh: ProcessMesh, axis: str, causal: bool,
                   scale):
    n = mesh.get_dim_size(axis)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    chunk = q.shape[1] // n
    spec = P(None, axis, None, None)                 # [B, S, H, D]: shard S
    if _ring_use_flash(chunk, q.shape[-1], q.shape[2], k.shape[2]):
        fn = _ring_flash_local_factory(axis, n, bool(causal), float(scale))
    else:
        fn = functools.partial(_ring_attn_local, axis=axis, n=n, chunk=chunk,
                               causal=causal, scale=scale)
    return shard_map(fn, mesh=mesh.jax_mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def _ulysses_local(q, k, v, *, axis, n, causal, scale):
    """all_to_all seq-shard → head-shard, full-seq attention, back."""
    def to_heads(x):   # [B, S/n, H, D] -> [B, S, H/n, D]
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):     # [B, S, H/n, D] -> [B, S/n, H, D]
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    sq = qh.shape[1]
    mask = None
    if causal:
        pos = jnp.arange(sq)
        mask = (pos[:, None] >= pos[None, :])[None, None]
    o, m, l = _block_attn(qh.astype(jnp.float32), kh.astype(jnp.float32),
                          vh.astype(jnp.float32), mask, scale)
    out = (o / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)).astype(q.dtype)
    return to_seq(out)


def _ulysses_fwd(q, k, v, *, mesh, axis, causal, scale):
    n = mesh.get_dim_size(axis)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses_attention: num_heads {q.shape[2]} must be divisible "
            f"by the '{axis}' axis degree {n}")
    spec = P(None, axis, None, None)
    fn = functools.partial(_ulysses_local, axis=axis, n=n, causal=causal,
                           scale=scale)
    return shard_map(fn, mesh=mesh.jax_mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


defprim("ring_attention_p", _ring_attn_fwd)
defprim("ulysses_attention_p", _ulysses_fwd)


def _resolve_mesh_axis(mesh, axis):
    if mesh is None:
        from .topology import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        if hcg is None:
            raise ValueError("context parallelism needs a mesh: pass one or "
                             "init fleet with a sep/cp degree > 1")
        mesh = hcg.mesh
        if axis is None:
            axis = "sep"
    return mesh, axis or "sep"


def ring_attention(q, k, v, mesh: ProcessMesh = None, axis: str = None,
                   causal: bool = False, scale=None) -> Tensor:
    """Exact attention over a sequence sharded on ``axis`` (ring schedule).

    q/k/v: [B, S, H, D] with S sharded over the mesh's sep/cp axis. GQA is
    handled upstream (repeat kv heads before the call, as the flash kernel
    does).
    """
    mesh, axis = _resolve_mesh_axis(mesh, axis)
    q, k, v = ensure_tensor(q), ensure_tensor(k), ensure_tensor(v)
    n = mesh.get_dim_size(axis)
    if q.shape[1] % n != 0:
        raise ValueError(f"ring_attention: seq len {q.shape[1]} must be "
                         f"divisible by the '{axis}' axis degree {n}")
    return apply("ring_attention_p", q, k, v, mesh=mesh, axis=axis,
                 causal=bool(causal), scale=scale)


def ulysses_attention(q, k, v, mesh: ProcessMesh = None, axis: str = None,
                      causal: bool = False, scale=None) -> Tensor:
    """DeepSpeed-Ulysses style sequence parallelism: all_to_all to shard
    heads, local full-sequence attention, all_to_all back."""
    mesh, axis = _resolve_mesh_axis(mesh, axis)
    q, k, v = ensure_tensor(q), ensure_tensor(k), ensure_tensor(v)
    n = mesh.get_dim_size(axis)
    if q.shape[1] % n != 0:
        raise ValueError(f"ulysses_attention: seq len {q.shape[1]} must be "
                         f"divisible by the '{axis}' axis degree {n}")
    return apply("ulysses_attention_p", q, k, v, mesh=mesh, axis=axis,
                 causal=bool(causal), scale=scale)
