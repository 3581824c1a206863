"""Dropless expert products of a sparse FFN under fixed shapes.

One chip holds ``count`` of a layer's experts (all of them, or its share
of an expert-parallel deployment). A step routes ``T`` tokens to ``top_k``
experts each; which of those assignments land on the held experts, and
how many on each, is data. Nothing here has a capacity and no assignment
is dropped: the buffers are sized for the worst case (every assignment
held), and the work done follows the real counts.

The layout (:func:`plan`): assignments to held experts are grouped by
expert, each group starting on a multiple of ``tm`` rows, so that every
``tm``-row tile of the buffer belongs to one expert. The grouped product
(:func:`grouped_matmul`, ``pallas_call`` name ``moe_experts``) then walks
the tiles: tile ``m`` is multiplied by the matrix of ``tile_group[m]``,
tiles past the last used one are skipped (their block indices are pinned
to the last used tile's, so that no DMA is issued for them), and an
expert's matrix is read once for each of its tiles, once in all where
its group fits a tile, as a decode step's does. Rows of a tile past its
group's size are computed on whatever the buffer holds there and never
read back.

``grouped_matmul`` is bf16 x bf16 on the MXU with a float32 accumulator
in VMEM, grid ``(n tiles, m tiles, k tiles)`` with ``k`` innermost. All
scalars in the kernel are ``np.int32``: ``jax_enable_x64`` is on.

The group sizes leave :func:`plan` with the layout, so that a serving
step can hand them to the host with its tokens (``serve.moe_*``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import resolve_backend

__all__ = ["plan", "Plan", "grouped_matmul", "grouped_matmul_reference",
           "experts_ffn", "pick_tm"]

_i32 = np.int32


class Plan(NamedTuple):
    """Where each assignment's row lies in the grouped buffer."""

    src_token: jax.Array     # [M] the token whose row feeds buffer row m
    dest: jax.Array          # [T, top_k] buffer row of each assignment
    held: jax.Array          # [T, top_k] bool: the expert is held here
    tile_group: jax.Array    # [M // tm] the held expert of each tile
    n_used: jax.Array        # [1] tiles that hold a group's rows
    group_sizes: jax.Array   # [count] assignments to each held expert


def pick_tm(tokens: int) -> int:
    """Rows of a tile: the MXU's 128, fewer where a call has fewer tokens
    than that (never under one bf16 sublane tile). A decode step's groups
    are far smaller, but a tile costs the MXU its matrix's load whatever
    its rows, and smaller tiles mean more of them to skip."""
    return int(min(128, max(16, 1 << (max(tokens, 1) - 1).bit_length())))


def buffer_rows(tokens: int, top_k: int, count: int, tm: int) -> int:
    """Rows of the grouped buffer: every assignment held, and each group
    rounded up to whole tiles."""
    worst = tokens * min(top_k, count) + count * (tm - 1)
    return -(-worst // tm) * tm


def plan(experts, first: int, count: int, tm: int) -> Plan:
    """The grouped layout of ``experts`` ``[T, top_k]`` (ids over all the
    layer's experts) for the ``count`` experts from ``first`` on."""
    t, k = experts.shape
    m = buffer_rows(t, k, count, tm)
    local = experts.astype(jnp.int32) - _i32(first)
    held = (local >= 0) & (local < count)
    flat = jnp.where(held, local, _i32(count)).reshape(-1)
    sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
    tiles = (sizes + _i32(tm - 1)) // _i32(tm)
    ends = jnp.cumsum(tiles)                        # in tiles
    starts = (ends - tiles) * _i32(tm)              # in rows
    # rank of an assignment inside its group, in (token, k) order
    order = jnp.argsort(flat, stable=True)
    first_of = jnp.cumsum(sizes) - sizes
    sorted_group = flat[order]
    safe_group = jnp.minimum(sorted_group, _i32(count - 1))
    rank = jnp.arange(t * k, dtype=jnp.int32) - first_of[safe_group]
    dest_sorted = jnp.where(sorted_group < count,
                            starts[safe_group] + rank, _i32(m))
    dest = jnp.zeros(t * k, jnp.int32).at[order].set(dest_sorted)
    src = jnp.zeros(m, jnp.int32).at[dest].set(
        jnp.arange(t * k, dtype=jnp.int32) // _i32(k), mode="drop")
    n_used = ends[-1:].astype(jnp.int32)
    tile = jnp.arange(m // tm, dtype=jnp.int32)
    group = jnp.searchsorted(ends, tile, side="right").astype(jnp.int32)
    # tiles past the last used one name its group: the kernel pins their
    # blocks there, so they cost no DMA
    last = jnp.searchsorted(ends, jnp.maximum(n_used[0] - 1, 0),
                            side="right").astype(jnp.int32)
    group = jnp.minimum(jnp.where(tile < n_used[0], group, last),
                        _i32(count - 1))
    return Plan(src, dest.reshape(t, k), held, group, n_used, sizes)


def grouped_matmul_reference(lhs, rhs, tile_group, n_used, *, tm):
    """``out[m] = lhs[m] @ rhs[tile_group[m // tm]]`` for the used tiles,
    zeros after them: the oracle, and the CPU's path."""
    m = lhs.shape[0]
    sizes = jnp.zeros(rhs.shape[0], jnp.int32).at[tile_group].add(
        jnp.where(jnp.arange(m // tm) < n_used[0], _i32(tm), _i32(0)))
    return lax.ragged_dot(lhs, rhs, sizes,
                          preferred_element_type=jnp.float32
                          ).astype(lhs.dtype)


def _kernel(group_ref, used_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
            k_tiles):
    del group_ref
    m, k = pl.program_id(1), pl.program_id(2)

    @pl.when(m < used_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(k == _i32(k_tiles - 1))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tile(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is ``target`` halved some times."""
    t = min(n, target)
    while n % t:
        t //= 2
    return t


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "interpret"))
def grouped_matmul_kernel(lhs, rhs, tile_group, n_used, *, tm, tk=2048,
                          tn=1024, interpret=False):
    """The Pallas kernel proper (TPU; ``interpret=True`` on the CPU)."""
    m, kdim = lhs.shape
    n = rhs.shape[2]
    if m % tm or rhs.shape[1] != kdim or tile_group.shape != (m // tm,):
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape}, tile_group "
            f"{tile_group.shape}, tm {tm}")
    tk, tn = _tile(kdim, tk), _tile(n, tn)
    k_tiles = kdim // tk
    last_k = _i32(k_tiles - 1)

    def pin(mi, ki, used_ref):
        """(m tile, k tile) to fetch: a skipped tile's are the last used
        tile's last, which is what the step before it held."""
        live = mi < used_ref[0]
        return (jnp.where(live, mi, jnp.maximum(used_ref[0] - 1, 0)),
                jnp.where(live, ki, last_k))

    def lhs_map(ni, mi, ki, group_ref, used_ref):
        mi, ki = pin(mi, ki, used_ref)
        return (mi, ki)

    def rhs_map(ni, mi, ki, group_ref, used_ref):
        mi, ki = pin(mi, ki, used_ref)
        return (group_ref[mi], ki, ni)

    def out_map(ni, mi, ki, group_ref, used_ref):
        return (pin(mi, ki, used_ref)[0], ni)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, m // tm, k_tiles),
        in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                  pl.BlockSpec((1, tk, tn), rhs_map)],
        out_specs=pl.BlockSpec((tm, tn), out_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, k_tiles=k_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="moe_experts",
        interpret=interpret,
    )(tile_group.astype(jnp.int32), n_used.astype(jnp.int32), lhs,
      rhs.astype(lhs.dtype))


def grouped_matmul(lhs, rhs, tile_group, n_used, *, tm, backend="auto"):
    """``lhs`` ``[M, K]`` times the matrix of each tile's expert, ``rhs``
    ``[count, K, N]`` -> ``[M, N]``; rows of the tiles from ``n_used`` on
    are not to be read. ``backend`` as ``paged_attention_decode``'s."""
    backend = resolve_backend(backend)
    if backend == "reference":
        return grouped_matmul_reference(lhs, rhs, tile_group, n_used, tm=tm)
    return grouped_matmul_kernel(lhs, rhs, tile_group, n_used, tm=tm,
                                 interpret=(backend == "interpret"))


def scoped(scope):
    """``jax.named_scope`` under the prefix ``scope`` (or under none)."""
    return (lambda s: jax.named_scope(f"{scope}/{s}")) if scope \
        else jax.named_scope


def experts_ffn(x, weights, experts, gate_up, down, *, first, tm=None,
                backend="auto", scope=None):
    """``sum_j weights[t, j] * E_{experts[t, j]}(x[t])`` over the held
    experts ``[first, first + count)`` alone, each ``E`` a SwiGLU
    ``down(silu(gate(x)) * up(x))`` whose gate and up matrices lie side
    by side in ``gate_up`` ``[count, H, 2 I]`` and whose down matrix is
    ``down`` ``[count, I, H]``. ``x`` ``[T, H]``, ``weights`` float32 and
    ``experts`` int ``[T, top_k]``. Returns (``[T, H]`` float32, the
    held experts' group sizes ``[count]``). ``scope`` prefixes the
    ``jax.named_scope`` of each stage (dispatch, experts, combine)."""
    t, k = experts.shape
    count, _, two_i = gate_up.shape
    tm = tm or pick_tm(t)
    named = scoped(scope)
    with named("dispatch"):
        p = plan(experts, first, count, tm)
        xs = jnp.take(x, p.src_token, axis=0)
    with named("experts"):
        kw = dict(tm=tm, backend=backend)
        gu = grouped_matmul(xs, gate_up, p.tile_group, p.n_used, **kw)
        i = two_i // 2
        act = (jax.nn.silu(gu[:, :i].astype(jnp.float32)).astype(x.dtype)
               * gu[:, i:])
        ys = grouped_matmul(act, down, p.tile_group, p.n_used, **kw)
    with named("combine"):
        rows = jnp.take(ys, jnp.where(p.held, p.dest, 0).reshape(-1),
                        axis=0).reshape(t, k, -1).astype(jnp.float32)
        # a select, not a product: rows no group wrote are not zeros
        rows = jnp.where(p.held[:, :, None], rows, 0.0)
        out = jnp.sum(rows * weights[:, :, None].astype(jnp.float32), 1)
    return out, p.group_sizes
