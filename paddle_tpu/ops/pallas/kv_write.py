"""In-place write of a step's new K/V rows into the paged pool.

The serving engine keeps each layer's K and V in a pool
``[KVH, num_blocks, block_size, DH]`` (the layout ``paged_decode`` reads,
see paged_attention.py) and every compiled step writes a few new rows
into it: one row a stream in a decode tick, a prompt's rows in a prefill.
Written as a scatter along the middle axis of the ``[KVH, slots, DH]``
view, XLA:TPU re-lays the whole donated pool into another layout and
copies the result back, two pool-sized copies a pool a step, whatever the
number of rows. This module writes the rows where the pool lies.

One algorithm, aligned pieces of the pool updated in place, at two
granularities that follow what the call can see in its input:

- **rows** (:func:`kv_write_kernel`): a Pallas kernel whose pool operand
  stays in HBM (``pl.ANY``) and is aliased to its result. In HBM the pool
  is tiled ``(sublanes, 128)`` over its last two axes, 16 rows a tile for
  bf16 with rows packed in pairs into 32-bit words, so one row at a free
  offset is half a word and cannot be the unit of a DMA. The unit is the
  aligned chunk of one tile's rows (:func:`_chunk_rows`): read into VMEM, the row replaced
  under an iota mask, written back. A call starts the reads of a whole
  wave of rows together, modifies, starts the writes, waits. Rows of one
  wave that fall into one chunk (a prefill's consecutive positions) are
  modified in the one VMEM copy of the first of them, which alone is
  read and written: such rows have to be adjacent in the call, as every
  caller's are (decode's rows fall into blocks of their own).
- **blocks** (:func:`kv_write_blocks`): rows that start a stream (row
  ``i`` lands at offset ``i % block_size`` of its block) are whole blocks
  but for the last; each is one ``lax.dynamic_update_slice`` of an aligned
  ``[KVH, 1, block_size, DH]`` block, which XLA does in place. Rows that
  are not to be written keep the pool's old content under a mask, so a
  block partly filled comes out as the scatter leaves it.

Fencing is the scatter's ``mode="drop"``: a row whose slot id is not in
``[0, num_blocks * block_size)`` (an inactive slot, a pad row of a
bucket) is skipped, and no piece of the pool is touched that holds no row
to be written. :func:`kv_write_reference` is that scatter, the oracle the
other paths are tested against bit for bit and the path of a CPU engine.

The serving engine runs on one device, so no call here sits in a sharded
program and the ``shard_map`` rule for Mosaic kernels
(ops/kernel_partition.py) does not arise. All scalars in the kernel are
``np.int32``: ``jax_enable_x64`` is on, and a Python int would reach
Mosaic as an int64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import Z
from .paged_attention import _for, resolve_backend

__all__ = ["kv_write", "kv_write_path", "kv_write_reference",
           "kv_write_kernel", "kv_write_blocks"]

# VMEM a wave's chunks may take; with the wave's new rows double-buffered
# beside them it stays inside the 16 MB of scoped VMEM a kernel gets
_WAVE_BYTES = 8 * 2 ** 20
_MAX_WAVE = 128


_i32 = np.int32


def _check_shapes(pool, rows, slots):
    if pool.ndim != 4:
        raise ValueError(
            f"pool must be [KVH, blocks, block_size, DH], got {pool.shape}")
    kvh, _, _, dh = pool.shape
    if rows.ndim != 3 or rows.shape[1:] != (kvh, dh):
        raise ValueError(
            f"rows must be [R, {kvh}, {dh}], got {rows.shape}")
    if slots.shape != rows.shape[:1]:
        raise ValueError(
            f"slots must be [R]={rows.shape[0]}, got {slots.shape}")


def kv_write_reference(pool, rows, slots):
    """The scatter on the pool's ``[KVH, slots, DH]`` view: the oracle,
    and the path where no kernel compiles (on the CPU it has no layout
    to change)."""
    _check_shapes(pool, rows, slots)
    kvh, nb, bs, dh = pool.shape
    flat = pool.reshape(kvh, nb * bs, dh)
    flat = flat.at[:, slots, :].set(
        rows.astype(pool.dtype).transpose(1, 0, 2), mode="drop")
    return flat.reshape(kvh, nb, bs, dh)


def _chunk_rows(block_size, dtype) -> int:
    """Rows of the aligned piece a DMA may move: one HBM tile of
    ``dtype`` (8 32-bit sublanes, narrower types packed into them), or
    the whole block where tiles do not divide it."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return block_size if block_size % tile else tile


def _rows_kernel(slots_ref, rows_ref, _pool_in, pool_ref, buf, rsem, wsem,
                 *, n_rows, wave, bs, n_slots):
    """One wave: ``rows_ref`` is its ``[wave, KVH, DH]`` block in VMEM,
    ``pool_ref`` the whole pool in HBM (aliased to ``_pool_in``), ``buf``
    one chunk ``[KVH, chunk, DH]`` a row."""
    _, kvh, chunk, dh = buf.shape
    base = pl.program_id(0) * _i32(wave)

    def chunk_of(i):
        """(written, leads, block, first row of the chunk, row in it)
        of the wave's row ``i``; a row leads when it is the first of the
        wave's adjacent rows in its chunk."""
        r = base + i
        slot = slots_ref[jnp.minimum(r, _i32(n_rows - 1))]
        ok = (r < _i32(n_rows)) & (slot >= _i32(0)) & (slot < _i32(n_slots))
        prev = slots_ref[jnp.clip(r - _i32(1), _i32(0), _i32(n_rows - 1))]
        leads = ok & ((i == _i32(0)) | (prev < _i32(0))
                      | (lax.div(prev, _i32(chunk))
                         != lax.div(slot, _i32(chunk))))
        off = lax.rem(slot, _i32(bs))
        start = pl.multiple_of(lax.div(off, _i32(chunk)) * _i32(chunk),
                               chunk)
        return ok, leads, lax.div(slot, _i32(bs)), start, off - start

    def copy(i, into_pool):
        _, _, blk, start, _ = chunk_of(i)
        piece = pool_ref.at[:, blk, pl.ds(start, chunk), :]
        if into_pool:
            return pltpu.make_async_copy(buf.at[i], piece, wsem.at[i])
        return pltpu.make_async_copy(piece, buf.at[i], rsem.at[i])

    def each_lead(fn):
        def body(i, c):
            pl.when(chunk_of(i)[1])(lambda: fn(i))
            return c
        _for(wave, body, _i32(0))

    each_lead(lambda i: copy(i, False).start())
    each_lead(lambda i: copy(i, False).wait())

    at_row = lax.broadcasted_iota(jnp.int32, (chunk, dh), 0)

    def modify(i, lead):
        ok, leads, _, _, row = chunk_of(i)
        lead = jnp.where(leads, i, lead)

        @pl.when(ok)
        def _():
            new = rows_ref[i]                              # [KVH, DH]
            for h in range(kvh):
                buf[lead, h] = jnp.where(
                    at_row == row,
                    jnp.broadcast_to(new[h:h + 1], at_row.shape),
                    buf[lead, h])
        return lead

    _for(wave, modify, _i32(0))

    each_lead(lambda i: copy(i, True).start())
    each_lead(lambda i: copy(i, True).wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_write_kernel(pool, rows, slots, *, interpret=False):
    """The Pallas kernel proper (TPU; ``interpret=True`` on the CPU).
    Jitted, so that the calls of one program with the same shapes, two a
    layer, are traced and lowered to Mosaic once and not once each."""
    _check_shapes(pool, rows, slots)
    kvh, nb, bs, dh = pool.shape
    n_rows = rows.shape[0]
    chunk = _chunk_rows(bs, pool.dtype)
    chunk_bytes = kvh * chunk * dh * jnp.dtype(pool.dtype).itemsize
    wave = max(1, min(n_rows, _MAX_WAVE, _WAVE_BYTES // chunk_bytes))
    kernel = functools.partial(
        _rows_kernel, n_rows=n_rows, wave=wave, bs=bs, n_slots=nb * bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(n_rows, wave),),
        in_specs=[
            pl.BlockSpec((wave, kvh, dh), lambda w, *_: (w, Z, Z)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((wave, kvh, chunk, dh), pool.dtype),
            pltpu.SemaphoreType.DMA((wave,)),
            pltpu.SemaphoreType.DMA((wave,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands count the prefetched slots: 0 slots, 1 rows, 2 pool
        input_output_aliases={2: 0},
        name="kv_write",
        interpret=interpret,
    )(slots.astype(jnp.int32), rows.astype(pool.dtype), pool)


@jax.jit
def kv_write_blocks(pool, rows, slots):
    """Rows that start their stream, a block at a time: block ``j`` of
    the call is rows ``[j * block_size, (j + 1) * block_size)`` and goes
    to the block that the first of them names. Only the blocks up to the
    last row to be written are touched; ``slots`` is in range on a
    prefix of the rows, as a prompt padded to its bucket has it."""
    _check_shapes(pool, rows, slots)
    kvh, nb, bs, dh = pool.shape
    n_blocks = -(-rows.shape[0] // bs)
    pad = n_blocks * bs - rows.shape[0]
    slots = jnp.pad(slots.astype(jnp.int32), (0, pad),
                    constant_values=nb * bs)
    written = (slots >= 0) & (slots < nb * bs)
    new = jnp.pad(rows.astype(pool.dtype), ((0, pad), (0, 0), (0, 0)))
    new = new.transpose(1, 0, 2).reshape(kvh, n_blocks, bs, dh)
    written = written.reshape(n_blocks, bs)
    first = slots.reshape(n_blocks, bs)[:, 0] // bs
    zero = jnp.int32(0)

    def body(j, pool):
        at = (zero, first[j], zero, zero)
        old = lax.dynamic_slice(pool, at, (kvh, 1, bs, dh))
        blk = lax.dynamic_slice(new, (zero, j, zero, zero),
                                (kvh, 1, bs, dh))
        keep = lax.dynamic_slice(written, (j, zero), (1, bs))
        return lax.dynamic_update_slice(
            pool, jnp.where(keep[None, :, :, None], blk, old), at)

    used = (jnp.sum(written, dtype=jnp.int32) + (bs - 1)) // bs
    return lax.fori_loop(zero, used, body, pool)


def kv_write_path(n_rows, block_size, *, rows_start_blocks=False,
                  backend="auto") -> str:
    """Which path :func:`kv_write` takes for ``n_rows`` rows: ``"blocks"``
    where the caller's rows start their stream's blocks and fill at
    least one, ``"rows"`` otherwise, ``"reference"`` where ``backend``
    resolves to it."""
    if resolve_backend(backend) == "reference":
        return "reference"
    if rows_start_blocks and n_rows >= block_size:
        return "blocks"
    return "rows"


def kv_write(pool, rows, slots, *, rows_start_blocks=False,
             backend="auto"):
    """Write ``rows`` into the paged pool at flat slot ids, in place.

    Args:
      pool: ``[KVH, num_blocks, block_size, DH]``; donate it, and the
        result is the same buffer.
      rows: ``[R, KVH, DH]`` new K or V rows.
      slots: ``[R]`` int32, ``block * block_size + offset`` of each row;
        a row whose id is not in ``[0, num_blocks * block_size)`` is not
        written. Rows that fall into one tile of the pool (16 rows of
        a block in bf16) are adjacent.
      rows_start_blocks: the caller's static knowledge that row ``i``
        lands at offset ``i % block_size`` of its block and that the
        rows to be written are a prefix (a fresh prefill).
      backend: as ``paged_attention_decode``'s.

    Returns the pool, bit for bit what :func:`kv_write_reference` gives.
    """
    backend = resolve_backend(backend)
    path = kv_write_path(rows.shape[0], pool.shape[2],
                         rows_start_blocks=rows_start_blocks,
                         backend=backend)
    if path == "reference":
        return kv_write_reference(pool, rows, slots)
    if path == "blocks":
        return kv_write_blocks(pool, rows, slots)
    return kv_write_kernel(pool, rows, slots,
                           interpret=(backend == "interpret"))
