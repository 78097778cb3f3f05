"""DataLoader with multiprocess workers.

Reference: python/mxnet/gluon/data/dataloader.py:26-112 (worker pool +
shared-memory NDArray rebuild, default_batchify_fn, _MultiWorkerIter).

TPU rebuild: workers are forked processes that run ONLY host-side numpy
code (dataset indexing, decode, augment, batchify) — they never touch
the TPU client, the fork-safety contract the reference enforces with
pthread_atfork engine quiesce (src/initialize.cc:52; SURVEY.md §7 hard
parts). Batches cross the process boundary as numpy arrays and are
placed on device once, in the consumer process, as one contiguous
transfer per stream. Worker exceptions are captured and re-raised at
`next()` like the reference's prefetcher (docs/architecture/
exception_handling.md).
"""
from __future__ import annotations

import multiprocessing as mp
import traceback
import weakref

import numpy as np

from ... import ndarray as nd
from ...ndarray.ndarray import NDArray
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (reference dataloader.py:
    default_batchify_fn). Output stays numpy until device placement."""
    if isinstance(data[0], NDArray):
        return nd.stack(*data, axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return data


# Workers return numpy (picklable, no device handles); same function
# serves both sides here — kept as a distinct name for reference parity.
default_mp_batchify_fn = default_batchify_fn


class _WorkerError:
    """Pickled traceback from a worker (re-raised in the consumer)."""

    def __init__(self, exc):
        self.exc_type = type(exc).__name__
        self.msg = str(exc)
        self.tb = traceback.format_exc()

    def reraise(self):
        raise RuntimeError(
            "DataLoader worker raised %s: %s\n--- worker traceback ---\n%s"
            % (self.exc_type, self.msg, self.tb))


_worker_dataset = None


def _terminate_pool(pool):
    try:
        pool.terminate()
        pool.join()
    except Exception:
        pass


def _worker_initializer(dataset, is_child_process):
    # Dataset is sent once at pool startup, not per batch (reference
    # dataloader.py:worker_loop receives the dataset through the fork).
    global _worker_dataset
    _worker_dataset = dataset
    # Enforce the "workers never touch the TPU client" contract (the
    # reference quiesces its engine across fork, src/initialize.cc:52):
    # a worker process that accidentally calls into jax must not try to
    # grab the accelerator — pin any fresh backend resolution to cpu.
    # `is_child_process` is passed explicitly by the pool constructor:
    # with thread_pool=True this initializer runs on threads *inside the
    # training process*, whose env must stay untouched (querying
    # multiprocessing parentage here would misfire when the trainer
    # itself was spawned via multiprocessing).
    if is_child_process:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        # A chip belongs to one process, the trainer: a worker that
        # touched jax on the default platform would fail or hang on it.
        # jax reads JAX_PLATFORMS when it is imported, and a forked
        # worker inherits the parent's already-imported jax, so there
        # only the config API takes effect; the variable covers a
        # spawned worker and whatever the worker itself starts.
        try:
            import jax

            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass


def _worker_fn(samples, batchify_fn, dataset=None):
    """`dataset` is passed explicitly by thread pools (several loaders
    share one process, so a module global would be clobbered by the
    most recently constructed loader); process-pool workers use the
    per-process global installed by the initializer."""
    try:
        ds = dataset if dataset is not None else _worker_dataset
        batch = batchify_fn([ds[i] for i in samples])
        return _as_numpy(batch)
    except Exception as e:  # captured, not fatal to the pool
        return _WorkerError(e)


def _as_numpy(batch):
    if isinstance(batch, NDArray):
        return batch.asnumpy()
    if isinstance(batch, (list, tuple)):
        return [_as_numpy(b) for b in batch]
    return batch


def _to_ndarray(batch, pin=False):
    """Rebuild NDArrays from worker-produced numpy batches.

    ``pin=True`` is the TPU analogue of the reference's pinned-memory
    staging (cpu_pinned context): the host→HBM transfer for every array
    in the batch is *started now* (async device_put onto the
    accelerator), so it overlaps with the training step instead of
    happening lazily at first use. With ``pin=False`` placement follows
    the current context as usual.
    """
    if isinstance(batch, np.ndarray):
        return nd.array(batch, ctx=_accel_ctx()) if pin else nd.array(batch)
    if isinstance(batch, (list, tuple)):
        return [_to_ndarray(b, pin) for b in batch]
    return batch


def _accel_ctx():
    from ...context import Context, num_tpus

    return Context("tpu", 0) if num_tpus() else None


class _MultiWorkerIter:
    """Async iterator over a worker pool with bounded prefetch
    (reference dataloader.py:_MultiWorkerIter)."""

    def __init__(self, pool, batchify_fn, batch_sampler, prefetch,
                 pin_memory=False, dataset=None):
        self._pool = pool
        self._batchify_fn = batchify_fn
        self._pin_memory = pin_memory
        self._dataset = dataset          # non-None only for thread pools
        self._iter = iter(batch_sampler)
        self._data_buffer = {}
        self._rcvd_idx = 0
        self._sent_idx = 0
        for _ in range(prefetch):
            self._push_next()

    def _push_next(self):
        r = next(self._iter, None)
        if r is None:
            return
        async_ret = self._pool.apply_async(
            _worker_fn, (r, self._batchify_fn, self._dataset))
        self._data_buffer[self._sent_idx] = async_ret
        self._sent_idx += 1

    def __next__(self):
        self._push_next()
        if self._rcvd_idx == self._sent_idx:
            assert not self._data_buffer, \
                "Data buffer should be empty at this moment"
            raise StopIteration
        ret = self._data_buffer.pop(self._rcvd_idx)
        self._rcvd_idx += 1
        batch = ret.get()
        if isinstance(batch, _WorkerError):
            batch = batch.reraise()
        return _to_ndarray(batch, self._pin_memory)

    def __iter__(self):
        return self


class DataLoader:
    """Mini-batch loader over a Dataset (reference dataloader.py:
    DataLoader).

    Parameters follow the reference: dataset, batch_size, shuffle,
    sampler, last_batch, batch_sampler, batchify_fn, num_workers.
    """

    def __init__(self, dataset, batch_size=None, shuffle=False,
                 sampler=None, last_batch=None, batch_sampler=None,
                 batchify_fn=None, num_workers=0, pin_memory=False,
                 prefetch=None, thread_pool=False):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._thread_pool = thread_pool
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is "
                    "specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._prefetch = max(0, prefetch or 2 * self._num_workers)
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._pool = None
        if self._num_workers > 0:
            if thread_pool:
                from multiprocessing.pool import ThreadPool

                self._pool = ThreadPool(
                    self._num_workers,
                    initializer=_worker_initializer,
                    initargs=(dataset, False))
            else:
                # Default start method is fork (fast; workers run only
                # numpy by contract). Forking a process with live JAX
                # threads is flagged by CPython — set
                # MXNET_WORKER_START_METHOD=forkserver|spawn to trade
                # startup cost for a thread-clean child (then the
                # dataset must be picklable).
                import os

                method = os.environ.get("MXNET_WORKER_START_METHOD",
                                        "fork")
                ctx = mp.get_context(method)
                self._pool = ctx.Pool(
                    self._num_workers,
                    initializer=_worker_initializer,
                    initargs=(dataset, True))
            # finalize() runs at gc or atexit — BEFORE interpreter
            # teardown, unlike __del__, so the pool shuts down while
            # multiprocessing internals are still alive.
            self._finalizer = weakref.finalize(self, _terminate_pool,
                                               self._pool)

    def __iter__(self):
        if self._num_workers == 0:
            def same_process_iter():
                for batch in self._batch_sampler:
                    yield _to_ndarray(_as_numpy(self._batchify_fn(
                        [self._dataset[idx] for idx in batch])),
                        self._pin_memory)
            return same_process_iter()
        return _MultiWorkerIter(self._pool, self._batchify_fn,
                                self._batch_sampler, self._prefetch,
                                self._pin_memory,
                                dataset=self._dataset
                                if self._thread_pool else None)

    def __len__(self):
        return len(self._batch_sampler)

