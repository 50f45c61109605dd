"""DataLoader.

Parity surface: ``python/mxnet/gluon/data/dataloader.py`` — DataLoader with
multiprocessing workers, default/named batchify, pin-memory analog.

TPU-native design: workers produce **numpy** host batches (cheap to pickle /
share), and the main process uploads them to device once per batch — the
moral equivalent of the reference's shared-memory NDArray + ForkingPickler
rebuild (dataloader.py:28-140).  Device upload is a single
``jax.device_put`` per batch, which overlaps with compute thanks to JAX
async dispatch.

Unlike the reference, ``num_workers > 0`` defaults to a **thread** pool:
decode/augment is numpy code that releases the GIL, and ``os.fork()`` after
the JAX runtime has started (it always has — importing the package
initializes it) deadlocks in the child.  Pass ``thread_pool=False`` to get
real processes via the fork-safe *spawn* context; spawned workers are pinned
to the XLA-CPU backend so they never dial TPU hardware.
"""
from __future__ import annotations

import multiprocessing

import numpy as np

from ...ndarray import NDArray
from ...ndarray import ndarray as _nd
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (dataloader.py default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        return _nd.stack(*data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    out = np.asarray(data)
    return out


def _as_host_batch(batch):
    """Normalize a batchified sample tree to numpy for cheap IPC."""
    if isinstance(batch, NDArray):
        return batch.asnumpy()
    if isinstance(batch, (list, tuple)):
        return type(batch)(_as_host_batch(b) for b in batch)
    return batch


def _upload(batch):
    """numpy host batch → NDArray on default ctx (single device_put each)."""
    if isinstance(batch, np.ndarray):
        return _nd.array(batch)
    if isinstance(batch, (list, tuple)):
        return type(batch)(_upload(b) for b in batch)
    return batch


_worker_dataset = None


def _worker_initializer(dataset):
    # dataset shipped once at pool construction, not per batch.  A chip
    # belongs to ONE process — the parent that trains on it — so a
    # spawned worker pins itself to the cpu.  Unpickling this function
    # has imported jax already (no backend is up yet), hence the config
    # update beside the env var
    import os as _os

    import jax as _jax
    _os.environ["JAX_PLATFORMS"] = "cpu"
    _jax.config.update("jax_platforms", "cpu")
    global _worker_dataset
    _worker_dataset = dataset


def _worker_fn(samples, batchify_fn):
    batch = batchify_fn([_worker_dataset[i] for i in samples])
    return _as_host_batch(batch)


def _thread_worker_fn(samples, batchify_fn, dataset):
    return _as_host_batch(batchify_fn([dataset[i] for i in samples]))


class _MultiWorkerIter:
    """Out-of-order workers + in-order reorder buffer (dataloader.py:448)."""

    def __init__(self, worker_pool, batchify_fn, batch_sampler,
                 prefetch=0, dataset=None, thread_pool=False):
        self._pool = worker_pool
        self._batchify_fn = batchify_fn
        self._batch_sampler = batch_sampler
        self._data_buffer = {}
        self._rcvd_idx = 0
        self._sent_idx = 0
        self._iter = iter(self._batch_sampler)
        self._thread_pool = thread_pool
        self._dataset = dataset
        for _ in range(prefetch):
            self._push_next()

    def __len__(self):
        return len(self._batch_sampler)

    def _push_next(self):
        batch = next(self._iter, None)
        if batch is None:
            return
        if self._thread_pool:
            async_ret = self._pool.apply_async(
                _thread_worker_fn, (batch, self._batchify_fn, self._dataset))
        else:
            async_ret = self._pool.apply_async(
                _worker_fn, (batch, self._batchify_fn))
        self._data_buffer[self._sent_idx] = async_ret
        self._sent_idx += 1

    def __next__(self):
        self._push_next()
        if self._rcvd_idx == self._sent_idx:
            assert not self._data_buffer, "data buffer should be empty at this moment"
            raise StopIteration
        ret = self._data_buffer.pop(self._rcvd_idx)
        self._rcvd_idx += 1
        return _upload(ret.get())

    def __iter__(self):
        return self


class DataLoader:
    """Loads data from a Dataset and returns mini-batches (dataloader.py:169).

    Parameters mirror the reference: dataset, batch_size, shuffle, sampler,
    last_batch, batch_sampler, batchify_fn, num_workers, prefetch,
    thread_pool.
    """

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, prefetch=None, thread_pool=True):
        self._dataset = dataset
        self._thread_pool = thread_pool
        self._worker_pool = None

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is")
            if sampler is None:
                sampler = (RandomSampler(len(dataset)) if shuffle
                           else SequentialSampler(len(dataset)))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(
                sampler, batch_size, last_batch if last_batch else "keep")
        elif (batch_size is not None or shuffle or sampler is not None
              or last_batch is not None):
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")

        self._batch_sampler = batch_sampler
        self._num_workers = num_workers if num_workers >= 0 else 0
        self._prefetch = max(0, int(prefetch) if prefetch is not None
                             else 2 * self._num_workers)
        self._batchify_fn = batchify_fn or default_batchify_fn
        if self._num_workers > 0:
            if thread_pool:
                from multiprocessing.pool import ThreadPool
                self._worker_pool = ThreadPool(self._num_workers)
            else:
                # fork would deadlock under the multithreaded JAX runtime
                ctx = multiprocessing.get_context("spawn")
                self._worker_pool = ctx.Pool(
                    self._num_workers,
                    initializer=_worker_initializer, initargs=(dataset,))

    def __iter__(self):
        if self._num_workers == 0:
            def _same_process_iter():
                for batch in self._batch_sampler:
                    yield _upload(_as_host_batch(self._batchify_fn(
                        [self._dataset[i] for i in batch])))
            return _same_process_iter()
        return _MultiWorkerIter(
            self._worker_pool, self._batchify_fn, self._batch_sampler,
            prefetch=self._prefetch, dataset=self._dataset,
            thread_pool=self._thread_pool)

    def __len__(self):
        return len(self._batch_sampler)

    def __del__(self):
        pool = getattr(self, "_worker_pool", None)
        if pool is not None:
            try:
                pool.terminate()
            except Exception:
                pass
