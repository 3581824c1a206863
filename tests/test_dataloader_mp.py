"""Multiprocess DataLoader workers.

Reference: python/paddle/io/dataloader/dataloader_iter.py
(_DataLoaderIterMultiProcess) — worker processes, ordered batches, clean
shutdown, thread fallback for unpicklable datasets.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset
from paddle_tpu.io.dataloader import _MultiprocessIter, _PrefetchIter


class SlowDataset(Dataset):
    """Picklable dataset with a genuinely slow (sleep) __getitem__."""

    def __init__(self, n=32, delay=0.02):
        self.n = n
        self.delay = delay

    def __getitem__(self, idx):
        time.sleep(self.delay)
        return np.full((4,), idx, dtype="float32"), np.int64(idx)

    def __len__(self):
        return self.n


class FastDataset(Dataset):
    def __init__(self, n=64):
        self.n = n

    def __getitem__(self, idx):
        return np.full((3,), idx, dtype="float32")

    def __len__(self):
        return self.n


class FailingDataset(Dataset):
    def __getitem__(self, idx):
        if idx == 5:
            raise ValueError("boom at 5")
        return np.zeros((2,), dtype="float32")

    def __len__(self):
        return 16


def test_uses_worker_processes():
    dl = DataLoader(FastDataset(16), batch_size=4, num_workers=2)
    it = iter(dl)
    assert isinstance(it, _MultiprocessIter)
    assert len(it.procs) == 2
    assert all(p.pid is not None for p in it.procs)
    list(it)  # drain + shutdown


def test_batch_order_identical_to_single_process():
    ds = FastDataset(50)
    single = [b.numpy() for b in DataLoader(ds, batch_size=4, shuffle=False,
                                            num_workers=0)]
    multi = [b.numpy() for b in DataLoader(ds, batch_size=4, shuffle=False,
                                           num_workers=3)]
    assert len(single) == len(multi)
    for a, b in zip(single, multi):
        np.testing.assert_array_equal(a, b)


class StampedDataset(SlowDataset):
    """SlowDataset whose items carry when their __getitem__ began and
    ended (CLOCK_MONOTONIC: one clock for every process of the host)."""

    def __getitem__(self, idx):
        t0 = time.monotonic()
        value, _ = super().__getitem__(idx)
        return value, np.array([t0, time.monotonic()])


def _most_in_flight(stamps):
    """The most intervals [begin, end] that hold one instant."""
    edges = sorted([(b, 1) for b, _ in stamps] + [(e, -1) for _, e in stamps])
    level = most = 0
    for _, step in edges:
        level += step
        most = max(most, level)
    return most


def test_overlap_with_slow_getitem():
    """4 workers on a sleep-bound dataset overlap its items — processes
    actually parallelize the Python-level work — and 1 worker takes them
    one at a time. A count of items in flight at one instant, from the
    items' own stamps: a ratio of two epochs' wall times does not hold
    still on a host that tier-1 shares among six xdist workers."""
    ds = StampedDataset(n=24, delay=0.03)

    def run(workers):
        dl = DataLoader(ds, batch_size=4, num_workers=workers,
                        persistent_workers=True)
        batches = [(b[0].numpy(), b[1].numpy()) for b in dl]
        dl._persistent_pool._shutdown()
        return ([v for v, _ in batches],
                _most_in_flight(np.concatenate([s for _, s in batches])))

    out4, flight4 = run(4)
    out1, flight1 = run(1)
    for a, b in zip(out1, out4):
        np.testing.assert_array_equal(a, b)
    assert flight1 == 1 and flight4 >= 2, (flight1, flight4)


def test_persistent_workers_reused_across_epochs():
    dl = DataLoader(FastDataset(12), batch_size=4, num_workers=2,
                    persistent_workers=True)
    it1 = iter(dl)
    b1 = [b.numpy() for b in it1]
    pids1 = [p.pid for p in it1.procs]
    it2 = iter(dl)
    assert it2 is it1  # same pool, re-armed
    b2 = [b.numpy() for b in it2]
    pids2 = [p.pid for p in it2.procs]
    assert pids1 == pids2, "workers were respawned between epochs"
    for a, b in zip(b1, b2):
        np.testing.assert_array_equal(a, b)
    it1._shutdown()


def test_persistent_workers_abandoned_epoch_restart():
    """Breaking out of an epoch mid-iteration must not leak stale batches
    into the next epoch: _attach drains in-flight jobs from the old index
    stream first (reference iterator reset semantics)."""
    dl = DataLoader(FastDataset(32), batch_size=4, num_workers=2,
                    persistent_workers=True)
    it1 = iter(dl)
    first = next(it1).numpy()  # abandon the epoch with 7 batches pending
    it2 = iter(dl)
    assert it2 is it1  # same pool, re-armed
    batches = [b.numpy() for b in it2]
    assert len(batches) == 8, f"epoch yielded {len(batches)} batches, not 8"
    np.testing.assert_array_equal(batches[0], first)  # fresh stream start
    it1._shutdown()


def test_unpicklable_dataset_falls_back_to_threads():
    class Local(Dataset):  # local class: not picklable for forkserver/spawn
        def __getitem__(self, idx):
            return np.full((2,), idx, dtype="float32")

        def __len__(self):
            return 8

    dl = DataLoader(Local(), batch_size=2, num_workers=2)
    it = iter(dl)
    assert isinstance(it, _PrefetchIter)
    batches = [b.numpy() for b in it]
    assert len(batches) == 4
    np.testing.assert_array_equal(batches[0][:, 0], [0, 1])


def test_custom_collate_falls_back_to_threads():
    dl = DataLoader(FastDataset(8), batch_size=2, num_workers=2,
                    collate_fn=lambda xs: np.stack(xs).sum())
    it = iter(dl)
    assert isinstance(it, _PrefetchIter)
    assert len(list(it)) == 4


def test_worker_error_propagates():
    dl = DataLoader(FailingDataset(), batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="boom at 5"):
        list(dl)


def test_clean_shutdown_no_leak():
    dl = DataLoader(FastDataset(12), batch_size=4, num_workers=2)
    it = iter(dl)
    procs = list(it.procs)
    list(it)
    deadline = time.time() + 10
    while time.time() < deadline and any(p.is_alive() for p in procs):
        time.sleep(0.05)
    assert not any(p.is_alive() for p in procs), "workers leaked"


def test_tuple_samples_tensorized():
    dl = DataLoader(SlowDataset(8, delay=0.0), batch_size=4, num_workers=2)
    x, y = next(iter(dl))
    assert isinstance(x, paddle.Tensor) and isinstance(y, paddle.Tensor)
    assert list(x.shape) == [4, 4] and list(y.shape) == [4]


class PlatformProbeDataset(Dataset):
    """Reports the JAX platform pin each worker process was started with."""

    def __getitem__(self, idx):
        import os

        return np.int64(os.environ.get("JAX_PLATFORMS") == "cpu")

    def __len__(self):
        return 8


def test_workers_cannot_open_the_accelerator(monkeypatch):
    """The chip belongs to the training process. Workers are spawned
    with JAX_PLATFORMS=cpu whatever the parent runs on, and the parent's
    own environment is left as it was."""
    import os

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    dl = DataLoader(PlatformProbeDataset(), batch_size=4, num_workers=2)
    pinned = np.concatenate([b.numpy() for b in dl])
    assert pinned.tolist() == [1] * 8
    assert "JAX_PLATFORMS" not in os.environ
