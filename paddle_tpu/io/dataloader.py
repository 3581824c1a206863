"""Dataset / DataLoader.

Reference: python/paddle/io/ (Dataset, IterableDataset, TensorDataset,
Sampler/RandomSampler/BatchSampler, DataLoader with worker processes —
reader/dataloader_iter.py). TPU design: host-side numpy batching with a
background prefetch thread; device transfer happens lazily on first op (or
eagerly via places). Multi-process workers use a thread pool instead — the
GIL is released inside numpy/jax host ops, and TPU input pipelines are
host-bound on decode, not on Python loops at this scale.
"""
from __future__ import annotations

import itertools
import os
import queue
import threading
from typing import Any, Callable, Iterable, List, Optional, Sequence

import numpy as np

from .. import observability as _obs
from ..core import generator
from ..core.tensor import Tensor

# -- prefetch-ring telemetry (ROADMAP open item) ----------------------------
# queue_depth is sampled at every consumer pop (how many batches were
# ready = how far ahead the producers run); wait_seconds is the time the
# training loop spent blocked on input — the "is the step loop
# input-bound?" gauge. Labeled by ring: python (thread prefetcher),
# native (csrc ring), mp (worker processes).
_obs_state = _obs.state
_M_QUEUE_DEPTH = _obs.gauge(
    "io.queue_depth",
    "prefetched batches ready at the last consumer pop, by ring "
    "(python | native | mp)")
_M_WAIT_SECONDS = _obs.histogram(
    "io.wait_seconds",
    "wall seconds the consumer blocked waiting for the next batch, by "
    "ring (python | native | mp)")
_M_BATCHES = _obs.counter(
    "io.batches_delivered",
    "batches handed to the training loop, by ring (python | native | mp)")


def _record_pop(ring: str, depth: int, waited: float):
    _M_QUEUE_DEPTH.set(depth, ring=ring)
    _M_WAIT_SECONDS.observe(waited, ring=ring)
    _M_BATCHES.inc(ring=ring)


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset does not support indexing")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors: Sequence[Tensor]):
        lens = {len(t) for t in tensors}
        if len(lens) != 1:
            raise ValueError("all tensors must share dim 0")
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            sample = d[idx]
            out.extend(sample if isinstance(sample, (list, tuple)) else [sample])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        di = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if di == 0 else int(self.cum[di - 1])
        return self.datasets[di][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    total = len(dataset)
    if sum(lengths) != total:
        # fraction form
        if all(0 < l < 1 for l in lengths):
            lengths = [int(l * total) for l in lengths]
            lengths[-1] = total - sum(lengths[:-1])
        else:
            raise ValueError("lengths must sum to dataset size")
    perm = np.random.permutation(total)
    out, off = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[off : off + l].tolist()))
        off += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class SubsetRandomSampler(Sampler):
    """Random permutation over a fixed index subset
    (reference: io/dataloader/sampler.py SubsetRandomSampler)."""

    def __init__(self, indices):
        if len(indices) == 0:
            raise ValueError("indices must not be empty")
        self.indices = list(indices)

    def __iter__(self):
        return iter(np.random.permutation(self.indices).tolist())

    def __len__(self):
        return len(self.indices)


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(
            len(self.weights), self.num_samples, self.replacement, p
        )
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1,
                 drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Reference: python/paddle/io/dataloader/batch_sampler.py
    DistributedBatchSampler — shards indices across data-parallel ranks."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            from ..distributed import env as dist_env

            num_replicas = num_replicas or dist_env.get_world_size()
            rank = rank if rank is not None else dist_env.get_rank()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: self.total_size - n]
        indices = indices[self.local_rank :: self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


# single collate ladder, shared with worker processes (the jax-free
# module handles Tensors through its `.numpy()` duck-typed fallback)
from ._mp_worker import numpy_collate as _numpy_collate  # noqa: E402


def _tensorize(obj):
    """Consumer-side half: wrap numpy payloads into Tensors."""
    if isinstance(obj, np.ndarray):
        return Tensor._from_value(obj)
    if isinstance(obj, dict):
        return {k: _tensorize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)) and obj and \
            not isinstance(obj[0], (str, bytes)):
        return type(obj)(_tensorize(v) for v in obj)
    return obj


def default_collate_fn(batch):
    """Stack samples → numpy batches → Tensors (reference:
    io/dataloader/collate.py default_collate_fn)."""
    return _tensorize(_numpy_collate(batch))


def _native_queue(capacity: int):
    """Native C++ prefetch ring (csrc/ptpu_queue.cc), or None.

    TPU-native analog of the reference's buffered reader / blocking queue
    between data-feed workers and the trainer (framework/data_feed.cc):
    workers push pickled numpy batches, the step loop pops and tensorizes.
    """
    try:
        from paddle_tpu import native

        if native.is_available():
            return native.BlockingQueue(capacity)
    except Exception:
        pass
    return None


class _PrefetchIter:
    def __init__(self, loader, index_iter):
        self.loader = loader
        self.index_iter = index_iter
        cap = max(2, loader.prefetch_factor)
        # Native ring only carries picklable payloads, i.e. the default
        # (numpy) collate path; custom collate_fns stay on the Python queue.
        self.nq = _native_queue(cap) if loader.collate_fn is None and \
            getattr(loader, "use_buffer_reader", True) else None
        self.q: "queue.Queue" = queue.Queue(maxsize=cap) \
            if self.nq is None else None
        self.done = object()
        self.workers: List[threading.Thread] = []
        n = max(1, loader.num_workers)
        self.lock = threading.Lock()
        self._launch(n)

    def _launch(self, n):
        import pickle

        def work():
            # thread fallback still honors per-worker init (single worker
            # thread -> id 0)
            if getattr(self.loader, "worker_init_fn", None) is not None:
                self.loader.worker_init_fn(0)
            while True:
                with self.lock:
                    try:
                        idxs = next(self.index_iter)
                    except StopIteration:
                        break
                batch = [self.loader.dataset[i] for i in idxs]
                if self.nq is not None:
                    payload = pickle.dumps(
                        _numpy_collate(batch), pickle.HIGHEST_PROTOCOL
                    )
                    try:
                        self.nq.push(b"B" + payload)
                    except RuntimeError:  # consumer closed early
                        return
                else:
                    collate = self.loader.collate_fn or default_collate_fn
                    self.q.put(collate(batch))
            if self.nq is not None:
                try:
                    self.nq.push(b"D")
                except RuntimeError:
                    pass
            else:
                self.q.put(self.done)

        for _ in range(1):  # single prefetch thread preserves batch order
            t = threading.Thread(target=work, daemon=True)
            t.start()
            self.workers.append(t)

    def __iter__(self):
        return self

    def __next__(self):
        import time as _time

        rec = _obs_state.on  # latch: obs toggled mid-pop must not record
        t0 = _time.perf_counter() if rec else 0.0
        if self.nq is not None:
            import pickle

            item = self.nq.pop()
            if item is None or item[:1] == b"D":
                raise StopIteration
            if rec:
                _record_pop("native", len(self.nq),
                            _time.perf_counter() - t0)
            return _tensorize(pickle.loads(item[1:]))
        item = self.q.get()
        if item is self.done:
            raise StopIteration
        if rec:
            _record_pop("python", self.q.qsize(),
                        _time.perf_counter() - t0)
        return item

    def __del__(self):
        try:
            if self.nq is not None:
                self.nq.close()
        except Exception:
            pass


class _MultiprocessIter:
    """Worker PROCESSES + in-order reassembly.

    The reference runs worker processes (io/dataloader/dataloader_iter.py
    _DataLoaderIterMultiProcess); thread workers are GIL-bound for
    Python-heavy __getitem__. Jobs are sequence-numbered and results
    reordered in the parent, so batch order is identical to the
    single-process loader regardless of worker scheduling."""

    def __init__(self, loader, index_iter, persistent=False):
        self.loader = loader
        self.index_iter = index_iter
        self.persistent = persistent
        n = max(1, loader.num_workers)
        # Plain fork is NOT safe here: the training process is heavily
        # multithreaded (XLA runtime), and a fork can inherit a lock held
        # mid-operation — observed as futex-deadlocked workers. _mp_context
        # therefore uses spawn (see its docstring for why forkserver was
        # rejected too); the startup cost is amortized by
        # persistent_workers.
        ctx = _mp_context()
        self.index_q = ctx.Queue()
        self.result_q = ctx.Queue()
        from ._mp_worker import worker_loop

        self.procs = []
        # A worker must never open the accelerator: the chip belongs to
        # this process, and a second one that touches it fails or hangs.
        # Unpickling a dataset that holds Tensors initialises a JAX
        # backend before worker_loop runs, so the platform is pinned in
        # the environment the workers are spawned with.
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for wid in range(n):
                p = ctx.Process(
                    target=worker_loop,
                    args=(loader.dataset, loader.worker_init_fn, wid, n,
                          self.index_q, self.result_q),
                    daemon=True)
                p.start()
                self.procs.append(p)
        finally:
            if prev is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = prev
        self._next_seq = 0      # next batch to hand out
        self._sent = 0          # jobs dispatched
        self._exhausted = False
        self._pending = {}      # seq -> batch (out-of-order arrivals)
        self._max_inflight = n * max(2, loader.prefetch_factor)
        self._fill()

    def _fill(self):
        while (not self._exhausted
               and self._sent - self._next_seq < self._max_inflight):
            try:
                idxs = next(self.index_iter)
            except StopIteration:
                self._exhausted = True
                break
            self.index_q.put((self._sent, list(idxs)))
            self._sent += 1

    def __iter__(self):
        return self

    def __next__(self):
        import queue as _q
        import time as _time

        if self._next_seq >= self._sent and self._exhausted:
            if not self.persistent:
                self._shutdown()
            raise StopIteration
        rec = _obs_state.on  # latch: obs toggled mid-pop must not record
        t0 = _time.perf_counter() if rec else 0.0
        stalled = 0.0
        while self._next_seq not in self._pending:
            try:
                seq, batch, err = self.result_q.get(timeout=5.0)
            except _q.Empty:
                # a worker killed by the OS (OOM, segfault in native code)
                # posts nothing: surface a diagnosis instead of hanging
                dead = [p.pid for p in self.procs if not p.is_alive()]
                if dead:
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader worker(s) {dead} exited abnormally "
                        "(killed?) without reporting a result")
                stalled += 5.0
                if stalled >= 120.0:
                    # workers alive but silent: deadlock/stuck __getitem__
                    # — fail loudly rather than hang the training job
                    self._shutdown()
                    raise RuntimeError(
                        "DataLoader workers produced no batch for 120s "
                        "(alive but stalled)")
                continue
            stalled = 0.0
            if err is not None:
                self._shutdown()
                raise RuntimeError(f"DataLoader worker failed:\n{err}")
            self._pending[seq] = batch
        batch = self._pending.pop(self._next_seq)
        self._next_seq += 1
        if rec:
            # depth = out-of-order arrivals already reassembled and
            # waiting, i.e. how far ahead the worker pool runs
            _record_pop("mp", len(self._pending),
                        _time.perf_counter() - t0)
        self._fill()
        return _tensorize(batch)

    def _attach(self, index_iter):
        """Persistent-worker epoch restart: reuse the live worker pool
        with a fresh index stream (reference persistent_workers).

        If the previous epoch was abandoned mid-iteration (``break``),
        jobs from the old index stream may still be queued or in flight;
        drain and discard them first so the new epoch never yields stale
        batches (mirrors the reference iterator reset)."""
        import queue as _q

        while self._next_seq < self._sent:
            if self._next_seq in self._pending:
                self._pending.pop(self._next_seq)
                self._next_seq += 1
                continue
            try:
                seq, _batch, _err = self.result_q.get(timeout=30.0)
            except _q.Empty:
                dead = [p.pid for p in self.procs if not p.is_alive()]
                self._shutdown()
                raise RuntimeError(
                    "DataLoader worker pool stalled while draining stale "
                    f"jobs on epoch restart (dead workers: {dead})")
            self._pending[seq] = None
        self._pending.clear()
        self.index_iter = index_iter
        self._exhausted = False
        self._fill()

    def _shutdown(self):
        for _ in self.procs:
            try:
                self.index_q.put(None)
            except Exception:
                pass
        for p in self.procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        self.procs = []

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass


def _mp_context():
    """spawn, deliberately. fork from this (XLA-threaded) process can
    inherit a lock held mid-operation — observed as futex-deadlocked
    workers under the full test suite; forkserver routes through spawn's
    main-module re-preparation anyway. spawn's per-worker startup cost is
    amortized by persistent_workers."""
    import multiprocessing as mp

    return mp.get_context("spawn")


def _mp_usable(loader) -> bool:
    """Process workers need the default (numpy) collate and a picklable
    dataset (forkserver/spawn both pickle job state); otherwise fall back
    to the thread prefetcher."""
    if loader.collate_fn is not None:
        return False
    import pickle

    try:
        pickle.dumps((loader.dataset, loader.worker_init_fn))
        return True
    except Exception:
        return False


class DataLoader:
    """Reference: python/paddle/io/DataLoader (places/return_list args kept
    for compatibility; on TPU there is one process per host, not per chip).
    num_workers > 0 spawns worker PROCESSES (numpy collate in workers,
    in-order reassembly in the parent); unpicklable datasets or custom
    collate_fns fall back to the thread prefetcher."""

    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_buffer_reader = use_buffer_reader
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
        elif not self._iterable_mode and batch_size is not None:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last
            )
        else:
            self.batch_sampler = None
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __len__(self):
        if self.batch_sampler is not None:
            return len(self.batch_sampler)
        raise TypeError("length unknown for iterable dataset")

    def __iter__(self):
        if self._iterable_mode:
            return self._iter_iterable()
        if self.batch_sampler is None:
            # batch_size=None → sample-at-a-time
            def gen():
                collate = self.collate_fn or (lambda x: x)
                for i in range(len(self.dataset)):
                    yield collate(self.dataset[i])

            return gen()
        if self.num_workers and self.num_workers > 0:
            if _mp_usable(self):
                if self.persistent_workers:
                    pool = getattr(self, "_persistent_pool", None)
                    if pool is not None and pool.procs:
                        pool._attach(iter(self.batch_sampler))
                        return pool
                    pool = _MultiprocessIter(self, iter(self.batch_sampler),
                                             persistent=True)
                    self._persistent_pool = pool
                    return pool
                return _MultiprocessIter(self, iter(self.batch_sampler))
            return _PrefetchIter(self, iter(self.batch_sampler))

        def gen():
            collate = self.collate_fn or default_collate_fn
            for idxs in self.batch_sampler:
                yield collate([self.dataset[i] for i in idxs])

        return gen()

    def _iter_iterable(self):
        collate = self.collate_fn or default_collate_fn
        if self.batch_size is None:
            yield from iter(self.dataset)
            return
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield collate(batch)
                batch = []
        if batch and not self.drop_last:
            yield collate(batch)


def get_worker_info():
    """Inside a worker process: (id, num_workers, dataset); else None
    (reference io/dataloader/worker.py get_worker_info)."""
    from ._mp_worker import _worker_info

    return _worker_info
