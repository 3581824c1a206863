"""One recurrent step of a Mamba-2 (SSD) layer over per-slot state.

A state-space layer keeps, for each serving slot and head, a state
``H`` ``[dh, n]``. A decode step moves every LIVE slot's state on by one
token and reads it out::

    H <- exp(dt A) H + dt x (outer) B        y = H C + D x

with ``x`` ``[dh]``, ``dt`` and ``A`` scalars of the head, ``B`` and ``C``
``[n]`` shared by the heads of a row (one group). The arithmetic is
float32; ``H`` is read and stored in the cache's own type (bfloat16 where
the model is served in it: what the public implementations of such
checkpoints allocate their ``ssm_states`` in), and ``y`` is taken from the
float32 value before it is rounded for storage, as ``mamba_ssm``'s
``selective_state_update`` takes it.

**The state's layout** is this module's (:func:`state_shape`,
:func:`pack_state`, :func:`unpack_state`): ``[slots, heads / hp, n, hp *
dh]`` with ``hp = 128 // dh`` heads side by side along the lanes, so that
``H[s, h, p, k]`` lies at ``[s, h // hp, k, (h % hp) * dh + p]``. With
``n`` along the sublanes and (head, ``p``) along the lanes, a row's ``x``
and ``y`` are plain lane vectors as the projections give and take them
(no transposed operand, no lane broadcast inside the kernel), the decay
is a lane vector, ``B`` and ``C`` vary along the sublanes and are shared
by every head of the row, and the read-out's sum over ``n`` is a sum of
vregs. The first version kept ``[slots, heads, dh, n]``: ``x`` was then
needed as a column a head and ``y`` as a lane reduction a vreg, and the
kernel ran at 35% of its roofline, bound by those (PERF.md §6, PR 31).

The step is bound by the HBM: it reads and writes ``slots x heads x dh x
n`` numbers (1 MB a row and layer at 64 x 64 x 128 in bfloat16) for a few
FLOPs each. So the kernel (``pallas_call`` name ``ssm_decode``):

- aliases the state to its result: the rows are updated where they lie
  and no program holds a second copy of the state;
- walks the LIVE rows only. The caller's ``live`` mask is compacted into
  a list of row ids that rides in SMEM (scalar prefetch) and drives the
  block index maps: grid step ``j`` fetches row ``rows[j]``, and the
  steps past the last live row are pinned to that row's index, so no
  DMA is issued for them and their bodies are skipped. A call's time
  follows its live rows, not ``max_slots`` (what ``paged_decode`` learnt,
  PERF.md PR 28). A row that is not live keeps its state bit for bit;
- is handed ``B`` and ``C`` spread along the lanes (``[slots, n, lanes]``
  in the activations' type, 3% of the state's bytes each): a column
  spread along the lanes inside the kernel is an XLU pass a vreg, in HBM
  it is a broadcast XLA fuses into the projection's epilogue.

:func:`ssm_decode_reference` is the same step in plain ``jnp`` over the
same layout: the oracle of the tests and the path of a CPU engine. All
scalars in the kernel are ``np.int32``: ``jax_enable_x64`` is on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import resolve_backend

__all__ = ["ssm_decode", "ssm_decode_reference", "ssm_decode_kernel",
           "state_shape", "pack_state", "unpack_state"]

_i32 = np.int32
Z = _i32(0)


LANES = 128


def heads_per_row(dh: int, heads: int) -> int:
    """Heads that lie side by side along the lanes of a state row: as many
    as fill the 128 lanes, fewer where the layer has fewer (a test's
    size)."""
    hp = LANES // dh if LANES % dh == 0 else 1
    while heads % hp:
        hp -= 1
    return hp


def state_shape(slots: int, heads: int, dh: int, n: int) -> tuple:
    """The shape of the state of ``slots`` rows of ``heads`` heads."""
    hp = heads_per_row(dh, heads)
    return (slots, heads // hp, n, hp * dh)


def pack_state(h):
    """``[..., heads, dh, n]`` -> the layout above ``[..., heads / hp, n,
    hp * dh]``."""
    *lead, nh, dh, n = h.shape
    hp = heads_per_row(dh, nh)
    k = len(lead)
    h = h.reshape(*lead, nh // hp, hp, dh, n)
    h = h.transpose(*range(k + 1), k + 3, k + 1, k + 2)
    return h.reshape(*lead, nh // hp, n, hp * dh)


def unpack_state(state, dh: int):
    """The layout above -> ``[..., heads, dh, n]``."""
    *lead, g, n, lanes = state.shape
    hp = lanes // dh
    k = len(lead)
    h = state.reshape(*lead, g, n, hp, dh)
    h = h.transpose(*range(k + 1), k + 2, k + 3, k + 1)
    return h.reshape(*lead, g * hp, dh, n)


def _check_shapes(state, x, dt, a, b, c, live):
    if x.ndim != 3:
        raise ValueError(f"x must be [slots, heads, dh], got {x.shape}")
    s, nh, dh = x.shape
    n = b.shape[-1]
    if state.shape != state_shape(s, nh, dh, n):
        raise ValueError(
            f"state must be {state_shape(s, nh, dh, n)} (state_shape), "
            f"got {state.shape}")
    if dt.shape != (s, nh) or a.shape != (nh,):
        raise ValueError(
            f"dt must be [{s}, {nh}] and A [{nh}], got {dt.shape} / "
            f"{a.shape}")
    if b.shape != (s, n) or c.shape != (s, n):
        raise ValueError(
            f"B and C must be [{s}, {n}], got {b.shape} / {c.shape}")
    if live.shape != (s,):
        raise ValueError(f"live must be [{s}], got {live.shape}")


def _operands(x, dt, a, b, c):
    """The step's small operands in float32: (decay ``exp(dt A)`` [S, nh],
    ``dt x`` [S, nh, dh], B, C)."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    return (jnp.exp(dt * a.astype(f32)[None, :]),
            x.astype(f32) * dt[..., None], b.astype(f32), c.astype(f32))


def ssm_decode_reference(state, x, dt, a, b, c, live):
    """(``H C`` [S, nh, dh] float32, the new state): the step in plain
    ``jnp``; rows that are not live keep their state and read 0."""
    _check_shapes(state, x, dt, a, b, c, live)
    decay, xdt, b, c = _operands(x, dt, a, b, c)
    new = (unpack_state(state, x.shape[-1]).astype(jnp.float32)
           * decay[..., None, None] + xdt[..., None] * b[:, None, None, :])
    y = jnp.sum(new * c[:, None, None, :], axis=-1)
    return (jnp.where(live[:, None, None], y, 0.0),
            jnp.where(live[:, None, None, None],
                      pack_state(new).astype(state.dtype), state))


def _step_kernel(rows_ref, n_ref, h_ref, xdt_ref, decay_ref, b_ref, c_ref,
                 o_ref, y_ref):
    """One live row a grid step: ``h_ref`` / ``o_ref`` its ``[1, G, n,
    L]`` state (one buffer in HBM), ``xdt_ref`` / ``decay_ref`` / ``y_ref``
    ``[1, G, L]`` lane vectors a group of heads, ``b_ref`` / ``c_ref``
    ``[1, n, L]`` spread along the lanes."""
    j = pl.program_id(0)
    n_live = n_ref[0]

    @pl.when(j < n_live)
    def _row():
        b = b_ref[0].astype(jnp.float32)                 # [n, L]
        c = c_ref[0].astype(jnp.float32)
        for g in range(h_ref.shape[1]):
            new = (h_ref[0, g].astype(jnp.float32)
                   * decay_ref[0, g:g + 1, :]
                   + b * xdt_ref[0, g:g + 1, :])         # [n, L]
            o_ref[0, g] = new.astype(o_ref.dtype)
            y_ref[0, g:g + 1, :] = jnp.sum(new * c, axis=0, keepdims=True)

    # no live row at all: the one block the pinned index names goes back
    # as it came
    @pl.when((j == 0) & (n_live == 0))
    def _none():
        o_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_kernel(state, x, dt, a, b, c, live, *, interpret=False):
    """The Pallas kernel proper (TPU; ``interpret=True`` on the CPU):
    (``H C`` [S, nh, dh] float32, the state, updated in place). Jitted,
    so that a program's calls, one a layer, are lowered to Mosaic once."""
    _check_shapes(state, x, dt, a, b, c, live)
    s, nh, dh = x.shape
    _, g, n, lanes = state.shape
    decay, xdt, _, _ = _operands(x, dt, a, b, c)
    # the live rows' ids first, the rest pinned to the last of them
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32)
    rows = order[jnp.minimum(jnp.arange(s, dtype=jnp.int32),
                             jnp.maximum(n_live - 1, 0))]
    row = lambda *tail: (lambda j, rows, _n: (rows[j],) + tail)
    spread = lambda v: jnp.broadcast_to(v[:, :, None], (s, n, lanes))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, g, n, lanes), row(Z, Z, Z)),
            pl.BlockSpec((1, g, lanes), row(Z, Z)),
            pl.BlockSpec((1, g, lanes), row(Z, Z)),
            pl.BlockSpec((1, n, lanes), row(Z, Z)),
            pl.BlockSpec((1, n, lanes), row(Z, Z)),
        ],
        out_specs=[
            pl.BlockSpec((1, g, n, lanes), row(Z, Z, Z)),
            pl.BlockSpec((1, g, lanes), row(Z, Z)),
        ],
    )
    new_state, y = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, g, lanes), jnp.float32)],
        # operands count the prefetched scalars: 0 rows, 1 n_live, 2 state
        input_output_aliases={2: 0},
        # rows in order on one core: the steps past the last live row
        # lean on the block before them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="ssm_decode",
        interpret=interpret,
    )(rows, n_live[None], state, xdt.reshape(s, g, lanes),
      jnp.repeat(decay, dh, axis=1).reshape(s, g, lanes), spread(b),
      spread(c))
    # a row that no step visited was never written: whatever lies there
    y = jnp.where(live[:, None, None], y.reshape(s, nh, dh), 0.0)
    return y, new_state


def ssm_decode(state, x, dt, a, b, c, d, live, *, backend="auto"):
    """One recurrent step for every live slot.

    Args:
      state: :func:`state_shape` ``(slots, heads, dh, n)`` in the cache's
        type; donate it, and the result is the same buffer.
      x: ``[slots, heads, dh]`` the step's input (after the convolution).
      dt: ``[slots, heads]`` float32 step sizes (after softplus).
      a: ``[heads]`` float32, negative (``-exp(A_log)``).
      b, c: ``[slots, n]`` the row's input and output projections.
      d: ``[heads]`` the skip weights.
      live: ``[slots]`` bool; a row that is not live keeps its state and
        reads 0.
      backend: as ``paged_attention_decode``'s.

    Returns (``y = H C + D x`` ``[slots, heads, dh]`` float32, the new
    state).
    """
    backend = resolve_backend(backend)
    if backend == "reference":
        y, new = ssm_decode_reference(state, x, dt, a, b, c, live)
    else:
        y, new = ssm_decode_kernel(state, x, dt, a, b, c, live,
                                   interpret=(backend == "interpret"))
    skip = d.astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
    return y + jnp.where(live[:, None, None], skip, 0.0), new
