"""Dropless expert products of a sparse FFN under fixed shapes.

One chip holds ``count`` of a layer's experts (all of them, or its share
of an expert-parallel deployment). A step routes ``T`` tokens to ``top_k``
experts each; which of those assignments land on the held experts, and
how many on each, is data. Nothing here has a capacity and no assignment
is dropped: the work done follows the real counts.

:func:`experts_ffn` takes one of two paths, chosen from the input's shape
(:func:`takes_direct`); both leave the held experts' group sizes beside
the output, so that a serving step can hand them to the host with its
tokens (``serve.moe_*``).

**Direct** (``T`` at most a tile's rows, ``pick_tm(T)``: a decode step
of up to 128 streams, a prompt bucket of up to 128 tokens). A token picks
an expert at most once, so no group is larger than ``T``, and the step's
own ``[T, H]`` rows can be the one tile each used expert multiplies.
:func:`route_direct` lists the held experts that got an assignment (a
cumsum over ``count``, no sort) and spreads the routing weights over a
dense ``[count, T]`` (a comparison, no scatter). The gate-up product
(:func:`direct_matmul`) reads ``x`` itself for every listed expert; the
down product combines in its epilogue: each expert's bfloat16 output,
weighted in float32 where its weight is not zero, is summed into one
float32 ``[T, H]``. Slots past the list are pinned to its last expert,
so an unused expert is never read.

**Grouped** (more tokens than a tile: a prompt's buckets and
``MOE_CHUNK``). :func:`plan` sorts the assignments to held experts by
expert into a buffer sized for the worst case (every assignment held),
each group starting on a multiple of ``tm`` rows, so that every
``tm``-row tile belongs to one expert. :func:`grouped_matmul` walks the
tiles: tile ``m`` is multiplied by the matrix of ``tile_group[m]``,
tiles past the last used one are skipped (their block indices are pinned
to the last used tile's, so that no DMA is issued for them), and an
expert's matrix is read once for each of its tiles. Rows of a tile past
its group's size are computed on whatever the buffer holds there and
never read back; the combine gathers each assignment's row.

Either path runs two ``pallas_call`` named ``moe_experts`` a layer (gate
and up, then down), bf16 x bf16 on the MXU with a float32 accumulator in
VMEM, grid ``(n tiles, m tiles or experts, k tiles)`` with ``k``
innermost; the activation between them is the same. All scalars in the
kernels are ``np.int32``: ``jax_enable_x64`` is on. The ``reference``
backend is the oracle of both paths: ``lax.ragged_dot`` over the grouped
layout, whatever the shape.
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
           "route_direct", "Route", "direct_matmul", "experts_ffn",
           "pick_tm", "takes_direct"]

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


class Route(NamedTuple):
    """Which held experts a step's tokens hit, for the direct path."""

    used: jax.Array          # [count] the held experts hit, in order;
    #                          slots from n_used on hold some valid index
    n_used: jax.Array        # [1] how many held experts were hit
    weights: jax.Array       # [count, T, 1] float32: a token's routing
    #                          weight on each held expert, 0 where it did
    #                          not pick it
    group_sizes: jax.Array   # [count] assignments to each held expert


def route_direct(experts, weights, first: int, count: int) -> Route:
    """The direct path's view of ``experts`` ``[T, top_k]`` (ids over all
    the layer's experts; a negative id routes nowhere) and ``weights``
    for the ``count`` experts from ``first`` on: comparisons, a cumsum
    and reductions over ``count x T x top_k``, no sort and no scatter."""
    local = experts.astype(jnp.int32) - _i32(first)
    slot = jnp.arange(count, dtype=jnp.int32)
    hit = local[None] == slot[:, None, None]             # [count, T, k]
    sizes = jnp.sum(hit, axis=(1, 2), dtype=jnp.int32)
    dense = jnp.sum(jnp.where(hit, weights.astype(jnp.float32)[None], 0.0),
                    axis=2)
    ends = jnp.cumsum((sizes > 0).astype(jnp.int32))    # hit experts <= e
    # the g-th hit expert is the count of experts e with at most g hit
    # experts in 0..e
    used = jnp.sum(ends[None, :] <= slot[:, None], axis=1, dtype=jnp.int32)
    return Route(jnp.minimum(used, _i32(count - 1)), ends[-1:],
                 dense[:, :, None], sizes)


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


def _pin(i, ki, used_ref, *, last_k):
    """(tile or slot, k tile) to fetch at grid step ``(i, ki)``: past the
    ``used_ref[0]`` used ones, the last used one's last k tile, which is
    what the step before held, so that a skipped step costs no DMA."""
    live = i < used_ref[0]
    return (jnp.where(live, i, jnp.maximum(used_ref[0] - 1, 0)),
            jnp.where(live, ki, last_k))


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
    pin = functools.partial(_pin, last_k=_i32(k_tiles - 1))

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


def _combine_kernel(used_ref, n_ref, lhs_ref, rhs_ref, w_ref, out_ref,
                    acc_ref, *, k_tiles):
    del used_ref
    g, k = pl.program_id(1), pl.program_id(2)

    @pl.when((g == 0) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(g < n_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(lhs_ref[0], rhs_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(k == _i32(k_tiles - 1))
        def _():
            y = acc_ref[...].astype(lhs_ref.dtype).astype(jnp.float32)
            w = w_ref[0]
            # a select, not a product: a token that did not pick the
            # expert takes nothing of its row
            out_ref[...] += jnp.where(w != 0, w * y, 0.0)


@functools.partial(jax.jit, static_argnames=("tk", "tn", "interpret"))
def direct_matmul(lhs, rhs, used, n_used, weights=None, *, tk=2048,
                  tn=1024, interpret=False):
    """The direct path's products (TPU; ``interpret=True`` on the CPU),
    over the held experts ``used[:n_used]`` of ``rhs`` ``[count, K, N]``.

    Without ``weights``: ``lhs`` ``[T, K]`` (the step's own rows, read by
    every expert) -> ``[count, T, N]`` in ``lhs``'s dtype, slot ``g``
    holding ``lhs @ rhs[used[g]]``; slots from ``n_used`` on are not to
    be read. With ``weights`` ``[count, T, 1]`` float32 (by held expert):
    ``lhs`` ``[count, T, K]`` (slot ``g``'s rows) -> ``[T, N]`` float32,
    the sum over the used slots of ``lhs[g] @ rhs[used[g]]`` rounded to
    ``lhs``'s dtype and weighted by ``weights[used[g]]`` where that is
    not zero."""
    count, kdim, n = rhs.shape
    t = lhs.shape[-2]
    combine = weights is not None
    if lhs.shape[-1] != kdim or used.shape != (count,) or (
            combine and (lhs.shape[0] != count
                         or weights.shape != (count, t, 1))):
        raise ValueError(
            f"direct_matmul: lhs {lhs.shape}, rhs {rhs.shape}, used "
            f"{used.shape}, weights "
            f"{None if weights is None else weights.shape}")
    tk, tn = _tile(kdim, tk), _tile(n, tn)
    k_tiles = kdim // tk
    pin = functools.partial(_pin, last_k=_i32(k_tiles - 1))

    def rhs_map(ni, gi, ki, used_ref, n_ref):
        gi, ki = pin(gi, ki, n_ref)
        return (used_ref[gi], ki, ni)

    rhs_spec = pl.BlockSpec((1, tk, tn), rhs_map)
    if combine:
        def lhs_map(ni, gi, ki, used_ref, n_ref):
            gi, ki = pin(gi, ki, n_ref)
            return (gi, _i32(0), ki)

        def w_map(ni, gi, ki, used_ref, n_ref):
            return (used_ref[pin(gi, ki, n_ref)[0]], _i32(0), _i32(0))

        in_specs = [pl.BlockSpec((1, t, tk), lhs_map), rhs_spec,
                    pl.BlockSpec((1, t, 1), w_map)]
        out_spec = pl.BlockSpec((t, tn),
                                lambda ni, gi, ki, u, c: (_i32(0), ni))
        out_shape = jax.ShapeDtypeStruct((t, n), jnp.float32)
        body = _combine_kernel
        operands = (lhs, rhs.astype(lhs.dtype), weights.astype(jnp.float32))
    else:
        def lhs_map(ni, gi, ki, used_ref, n_ref):
            return (_i32(0), pin(gi, ki, n_ref)[1])

        def out_map(ni, gi, ki, used_ref, n_ref):
            return (pin(gi, ki, n_ref)[0], _i32(0), ni)

        in_specs = [pl.BlockSpec((t, tk), lhs_map), rhs_spec]
        out_spec = pl.BlockSpec((None, t, tn), out_map)
        out_shape = jax.ShapeDtypeStruct((count, t, n), lhs.dtype)
        body = _kernel
        operands = (lhs, rhs.astype(lhs.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, count, k_tiles),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((t, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(body, k_tiles=k_tiles),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="moe_experts",
        interpret=interpret,
    )(used.astype(jnp.int32), n_used.astype(jnp.int32), *operands)


def scoped(scope):
    """``jax.named_scope`` under the prefix ``scope`` (or under none)."""
    return (lambda s: jax.named_scope(f"{scope}/{s}")) if scope \
        else jax.named_scope


def takes_direct(tokens: int, backend: str = "auto", tm=None) -> bool:
    """Whether :func:`experts_ffn` over ``tokens`` rows takes the direct
    path: the rows fit one tile of ``tm`` (``pick_tm``'s by default) and
    the kernels run (the ``reference`` backend computes every shape over
    the grouped layout)."""
    return (tokens <= (tm or pick_tm(tokens))
            and resolve_backend(backend) != "reference")


def experts_ffn(x, weights, experts, gate_up, down, *, first, tm=None,
                backend="auto", scope=None):
    """``sum_j weights[t, j] * E_{experts[t, j]}(x[t])`` over the held
    experts ``[first, first + count)`` alone, each ``E`` a SwiGLU
    ``down(silu(gate(x)) * up(x))`` whose gate and up matrices lie side
    by side in ``gate_up`` ``[count, H, 2 I]`` and whose down matrix is
    ``down`` ``[count, I, H]``. ``x`` ``[T, H]``, ``weights`` float32 and
    ``experts`` int ``[T, top_k]`` (a negative id routes nowhere).
    Returns (``[T, H]`` float32, the held experts' group sizes
    ``[count]``).

    Where :func:`takes_direct` (``T`` at most a tile of ``tm`` rows, the
    kernels running) the experts multiply ``x`` itself and the down
    product combines in its epilogue; else the assignments go through
    :func:`plan`'s grouped buffer and a gather combines them. Both round
    each expert's output to ``x``'s dtype and weight it in float32; only
    the order of a token's float32 sum differs. ``scope`` prefixes the
    ``jax.named_scope`` of each stage (dispatch, experts and, grouped,
    combine)."""
    t, k = experts.shape
    count, _, two_i = gate_up.shape
    tm = tm or pick_tm(t)
    named = scoped(scope)
    i = two_i // 2

    def act(gu):
        return (jax.nn.silu(gu[..., :i].astype(jnp.float32)).astype(x.dtype)
                * gu[..., i:])

    if takes_direct(t, backend, tm):
        interpret = resolve_backend(backend) == "interpret"
        with named("dispatch"):
            r = route_direct(experts, weights, first, count)
        with named("experts"):
            gu = direct_matmul(x, gate_up, r.used, r.n_used,
                               interpret=interpret)
            out = direct_matmul(act(gu), down, r.used, r.n_used, r.weights,
                                interpret=interpret)
        return out, r.group_sizes
    with named("dispatch"):
        p = plan(experts, first, count, tm)
        xs = jnp.take(x, p.src_token, axis=0)
    with named("experts"):
        kw = dict(tm=tm, backend=backend)
        gu = grouped_matmul(xs, gate_up, p.tile_group, p.n_used, **kw)
        ys = grouped_matmul(act(gu), down, p.tile_group, p.n_used, **kw)
    with named("combine"):
        rows = jnp.take(ys, jnp.where(p.held, p.dest, 0).reshape(-1),
                        axis=0).reshape(t, k, -1).astype(jnp.float32)
        # a select, not a product: rows no group wrote are not zeros
        rows = jnp.where(p.held[:, :, None], rows, 0.0)
        out = jnp.sum(rows * weights[:, :, None].astype(jnp.float32), 1)
    return out, p.group_sizes
