"""In-place K/V write into the paged pool
(paddle_tpu/ops/pallas/kv_write.py).

The scatter the serving engine used to run (``kv_write_reference``) is
the oracle; the Pallas kernel under the interpreter (rows) and the
block updates (blocks) have to give its pool bit for bit, the rest of
the pool included. The interpreter checks the kernel's arithmetic, not
that Mosaic accepts it: tests/test_tpu_aot_compile.py compiles it for the
chip and chip_smoke.py runs it there.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — turns on jax_enable_x64, as every user does
from paddle_tpu.ops.pallas.kv_write import (
    kv_write, kv_write_blocks, kv_write_kernel, kv_write_path,
    kv_write_reference)

NB, BS, DH = 10, 32, 128
DTYPES = [jnp.bfloat16, jnp.float32]


def _pool(kvh, dtype, seed=0, bs=BS, dh=DH):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(kvh, NB, bs, dh)), dtype)


def _rows(n, kvh, dtype, seed=1, dh=DH):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, kvh, dh)), dtype)


def _stream_slots(table, start, n, bucket, bs=BS):
    """Flat slot ids of a prefill: ``bucket`` rows at positions
    ``start + i`` through ``table``, the rows from ``n`` on fenced off."""
    pos = start + np.arange(bucket)
    blk = np.asarray(table)[np.clip(pos // bs, 0, len(table) - 1)]
    slot = blk * bs + pos % bs
    return jnp.asarray(np.where(np.arange(bucket) < n, slot, NB * bs),
                       jnp.int32)


def _same(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)))


def _interpret(pool, rows, slots):
    return jax.jit(lambda *a: kv_write_kernel(*a, interpret=True))(
        pool, rows, slots)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kvh", [12, 4])          # kvh = nh, and GQA 16/4
def test_rows_in_blocks_of_their_own(kvh, dtype):
    """Decode's case: every stream writes one row into a block of its
    own, inactive slots carry an id out of range and write nothing."""
    pool, rows = _pool(kvh, dtype), _rows(6, kvh, dtype)
    slots = jnp.asarray([3 * BS + 5, NB * BS, 0 * BS + BS - 1,
                         7 * BS + 16, 2 * BS, NB * BS + 7], jnp.int32)
    want = kv_write_reference(pool, rows, slots)
    _same(_interpret(pool, rows, slots), want)
    written = np.zeros((NB, BS), bool)
    written[[3, 0, 7, 2], [5, BS - 1, 16, 0]] = True
    keep = ~written[None, :, :, None]
    assert bool(((want == pool) | ~keep).all()), "a dropped row was written"
    assert not bool((want == pool).all())


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("start,n,bucket", [
    (13, 37, 40),     # free start, pad rows, three blocks' worth of tiles
    (32, 8, 8),       # starts a block (a prefix hit on whole blocks)
    (5, 3, 8),        # all in one tile
    (31, 2, 8),       # straddles a block edge
])
def test_consecutive_rows_share_tiles(start, n, bucket, dtype):
    """The suffix prefill's case: consecutive positions from a free
    start, so up to a tile's rows fall into one chunk and take one read
    and one write under one mask; pad rows of the bucket are dropped."""
    kvh = 4
    pool, rows = _pool(kvh, dtype), _rows(bucket, kvh, dtype)
    slots = _stream_slots([4, 1, 8], start, n, bucket)
    _same(_interpret(pool, rows, slots),
          kv_write_reference(pool, rows, slots))


def test_rows_past_one_wave():
    """More rows than one wave holds in VMEM: a tile that straddles two
    waves is read, modified and written by each in turn."""
    kvh, bs = 2, 64
    pool = _pool(kvh, jnp.bfloat16, bs=bs)
    rows = _rows(300, kvh, jnp.bfloat16)
    slots = _stream_slots([9, 2, 5, 0, 7, 3], 24, 290, 300, bs=bs)
    _same(_interpret(pool, rows, slots),
          kv_write_reference(pool, rows, slots))


@pytest.mark.parametrize("bs,dh", [(4, 16), (24, 64)])
def test_block_no_tile_divides(bs, dh):
    """A block that is no whole number of tiles is its own chunk."""
    pool = _pool(2, jnp.float32, bs=bs, dh=dh)
    rows = _rows(7, 2, jnp.float32, dh=dh)
    slots = _stream_slots([6, 3, 1], bs - 2, 6, 7, bs=bs)
    _same(_interpret(pool, rows, slots),
          kv_write_reference(pool, rows, slots))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n,bucket", [
    (70, 128),        # ends inside the third of four blocks
    (64, 64),         # ends with a block
    (1, 32),          # one row of one block
    (33, 40),         # a bucket that is no whole number of blocks
])
def test_blocks_of_a_fresh_prefill(n, bucket, dtype):
    """Rows from position 0 of a stream, whole blocks at a time: rows
    past ``n`` keep the pool's content, blocks past the last row's and
    every block outside the table are never touched."""
    kvh = 4
    pool, rows = _pool(kvh, dtype), _rows(bucket, kvh, dtype)
    table = [4, 1, 8, 6]
    slots = _stream_slots(table, 0, n, bucket)
    want = kv_write_reference(pool, rows, slots)
    got = jax.jit(kv_write_blocks)(pool, rows, slots)
    _same(got, want)
    untouched = sorted(set(range(NB)) - set(table[:-(-n // BS)]))
    _same(got[:, untouched], pool[:, untouched])


@pytest.mark.parametrize("backend", ["interpret", "reference"])
def test_path_follows_the_shapes(backend):
    """``kv_write`` takes blocks only for rows that start their stream
    and fill a block, rows otherwise; every path gives the same pool."""
    kw = dict(backend=backend)
    names = ((lambda p: "reference") if backend == "reference"
             else (lambda p: p))
    assert kv_write_path(128, BS, **kw) == names("rows")
    assert kv_write_path(128, BS, rows_start_blocks=True,
                         **kw) == names("blocks")
    assert kv_write_path(BS // 2, BS, rows_start_blocks=True,
                         **kw) == names("rows")
    pool = _pool(4, jnp.bfloat16)
    for bucket, n in ((16, 9), (64, 50)):     # under a block; two blocks
        rows = _rows(bucket, 4, jnp.bfloat16)
        slots = _stream_slots([5, 2], 0, n, bucket)
        _same(jax.jit(lambda *a: kv_write(
            *a, rows_start_blocks=True, **kw))(pool, rows, slots),
            kv_write_reference(pool, rows, slots))
    with pytest.raises(ValueError, match="unknown backend"):
        kv_write_path(8, BS, backend="mosaic")


@pytest.mark.parametrize("fresh", [False, True])
def test_pool_in_a_scan_carry(fresh):
    """The burst's case: the pool rides a ``lax.scan`` carry and each
    tick writes the next row of every stream (or, ``fresh``, the next
    block)."""
    kvh = 4
    pool = _pool(kvh, jnp.bfloat16)
    n = BS if fresh else 3
    ticks = 3
    rows = jnp.stack([_rows(n, kvh, jnp.bfloat16, seed=t)
                      for t in range(ticks)])
    if fresh:
        slots = jnp.stack([_stream_slots([t + 2], 0, n - t, n)
                           for t in range(ticks)])
    else:
        slots = jnp.stack([jnp.asarray(
            [1 * BS + 14 + t, NB * BS, 6 * BS + t], jnp.int32)
            for t in range(ticks)])

    def run(backend):
        def tick(pool, xs):
            return kv_write(pool, *xs, rows_start_blocks=fresh,
                            backend=backend), None
        return jax.jit(lambda p: jax.lax.scan(tick, p, (rows, slots))[0])(
            pool)

    _same(run("interpret"), run("reference"))


def test_shapes_checked():
    pool = _pool(4, jnp.float32)
    with pytest.raises(ValueError, match="rows must be"):
        kv_write(pool, _rows(3, 2, jnp.float32), jnp.zeros(3, jnp.int32))
    with pytest.raises(ValueError, match="slots must be"):
        kv_write(pool, _rows(3, 4, jnp.float32), jnp.zeros(4, jnp.int32))
    with pytest.raises(ValueError, match="pool must be"):
        kv_write(pool[0], _rows(3, 4, jnp.float32), jnp.zeros(3, jnp.int32))
