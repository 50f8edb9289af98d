"""DataLoader (parity: python/mxnet/gluon/data/dataloader.py).

Reference architecture: fork workers + cpu_shared-storage NDArray rebuild
via a custom ForkingPickler (dataloader.py:55-120, POSIX shm under
src/storage/cpu_shared_storage_manager.h).  TPU redesign, same roles:

- fork workers batchify to numpy; large arrays cross the process
  boundary through multiprocessing.shared_memory blocks (one memcpy into
  shm, zero-copy attach in the parent) instead of being pickled through
  a pipe — the cpu_shared equivalent;
- an in-flight prefetch window keeps the pool busy ahead of the
  consumer (dmlc ThreadedIter's double buffering);
- optional ``device_prefetch``: batches are handed to jax.device_put as
  soon as the worker result lands, so the host→HBM copy of batch N+1
  overlaps the consumer's compute on batch N (the reference's
  iter_prefetcher.h pinned-memory stage).
"""
from __future__ import annotations

import contextlib
import multiprocessing
import os
import sys

import numpy as np

from ... import ndarray as nd
from ...ndarray import NDArray
from .sampler import BatchSampler, RandomSampler, SequentialSampler

# arrays below this many bytes just pickle (shm setup costs more)
_SHM_MIN_BYTES = 1 << 16


def default_batchify_fn(data):
    """Stack samples into a batch (parity: dataloader.py default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        return nd.stack(*data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return nd.array(data, dtype=data.dtype)


def default_mp_batchify_fn(data):
    """Worker-side batchify: stays in numpy (crosses the process boundary
    via shared memory; the reference rebuilds into cpu_shared NDArrays)."""
    if isinstance(data[0], NDArray):
        return np.stack([d.asnumpy() for d in data])
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_mp_batchify_fn(i) for i in data]
    return np.asarray(data)


class _ShmBatch:
    """Descriptor for a numpy array parked in a SharedMemory block."""

    __slots__ = ("name", "shape", "dtype")

    def __init__(self, name, shape, dtype):
        self.name = name
        self.shape = shape
        self.dtype = dtype


def _to_shm(obj):
    """Recursively move large numpy arrays into shared memory blocks."""
    from multiprocessing import shared_memory
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_shm(o) for o in obj)
    if isinstance(obj, np.ndarray) and obj.nbytes >= _SHM_MIN_BYTES:
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        view = np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)
        view[...] = obj
        desc = _ShmBatch(shm.name, obj.shape, obj.dtype)
        # ownership transfers to the parent (which unlinks after attach);
        # drop the creating process's resource-tracker registration so it
        # doesn't warn about the block it no longer owns
        try:
            from multiprocessing import resource_tracker
            resource_tracker.unregister(shm._name, "shared_memory")
        except (ImportError, AttributeError, KeyError):
            pass  # tracker absent or never registered the block
        shm.close()
        return desc
    return obj


def _from_shm(obj):
    """Attach descriptors, copy out (device_put consumes the copy), unlink."""
    from multiprocessing import shared_memory
    if isinstance(obj, (list, tuple)):
        return type(obj)(_from_shm(o) for o in obj)
    if isinstance(obj, _ShmBatch):
        shm = shared_memory.SharedMemory(name=obj.name)
        try:
            arr = np.ndarray(obj.shape, obj.dtype,
                             buffer=shm.buf).copy()
        finally:
            shm.close()
            shm.unlink()
        return arr
    return obj


_worker_dataset = None


@contextlib.contextmanager
def _workers_pinned_to_cpu():
    """``JAX_PLATFORMS=cpu`` in the environment while the pool's
    processes (and the forkserver they fork from) are started.  A worker
    that indexes a dataset holding NDArrays, or runs an ``nd`` transform,
    initialises a jax backend — already while unpickling the dataset,
    before any initializer runs — and the trainer process holds the
    chip.  This process read the variable when it imported jax, so its
    own backend choice does not change."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


def _worker_initializer(dataset):
    global _worker_dataset
    _worker_dataset = dataset


def _worker_fn(samples, batchify_fn, use_shm, dataset=None):
    """Worker target: fetch samples, batchify to numpy, park in shm."""
    global _worker_dataset
    ds = dataset if dataset is not None else _worker_dataset
    batch = batchify_fn([ds[i] for i in samples])
    return _to_shm(batch) if use_shm else batch


def _ctx_for_device(device):
    from ...context import Context
    plat = getattr(device, "platform", "cpu")
    dev_type = plat if plat in ("cpu", "gpu", "tpu") else "tpu"
    return Context(dev_type, getattr(device, "id", 0))


def _as_nd(batch, device=None):
    if isinstance(batch, (list, tuple)):
        return [_as_nd(b, device) for b in batch]
    if isinstance(batch, NDArray):
        return batch
    if device is not None:
        import jax
        arr = jax.device_put(np.asarray(batch), device)
        return NDArray(arr, _ctx_for_device(device))
    return nd.array(batch)


class DataLoader:
    """Loads data from a Dataset, returns mini-batches
    (parity: dataloader.py DataLoader).

    num_workers > 0 runs a worker pool (forkserver start method: fork
    after jax's XLA threads are live deadlocks — see __init__; like
    torch DataLoader on spawn platforms, user SCRIPTS therefore need
    the standard ``if __name__ == "__main__"`` guard; set
    MXNET_MP_START_METHOD=fork to restore the old behavior for
    non-picklable datasets).  Batches come back through shared memory.
    device_prefetch=True (or a jax device) starts the host→HBM
    transfer as soon as a batch is ready instead of when the consumer
    touches it."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=False, device_prefetch=False):
        self._dataset = dataset
        self._pin_memory = pin_memory  # staging is XLA-managed; accepted
        self._device = None
        if device_prefetch:
            import jax
            self._device = (device_prefetch if not isinstance(
                device_prefetch, bool) else jax.devices()[0])

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
                                         last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")

        self._batch_sampler = batch_sampler
        self._num_workers = num_workers if num_workers >= 0 else 0
        self._prefetch = max(0, int(prefetch) if prefetch is not None
                             else 2 * self._num_workers)
        if batchify_fn is None:
            if num_workers > 0:
                self._batchify_fn = default_mp_batchify_fn
            else:
                self._batchify_fn = default_batchify_fn
        else:
            self._batchify_fn = batchify_fn
        self._thread_pool = thread_pool
        self._pool = None
        self._use_shm = False
        if self._num_workers > 0:
            if thread_pool:
                from multiprocessing.pool import ThreadPool
                self._pool = ThreadPool(self._num_workers)
                _worker_initializer(dataset)
            else:
                # forkserver, NOT fork: by DataLoader-construction time
                # jax's XLA thread pools are usually live, and a fork
                # child inherits their held locks — measured hard
                # deadlock with the 8-device CPU backend initialized.
                # The forkserver process is spawned clean (fork+exec) and
                # children fork from IT; the dataset crosses once by
                # pickle. MXNET_MP_START_METHOD overrides (fork keeps
                # the old zero-pickle behavior for non-picklable
                # datasets created before any jax use).
                method = os.environ.get("MXNET_MP_START_METHOD",
                                        "forkserver")
                ctx = multiprocessing.get_context(method)
                with _workers_pinned_to_cpu():
                    self._pool = ctx.Pool(self._num_workers,
                                          initializer=_worker_initializer,
                                          initargs=(dataset,))
                self._use_shm = True

    def __iter__(self):
        if self._pool is None:
            for batch in self._batch_sampler:
                yield _as_nd(self._batchify_fn(
                    [self._dataset[i] for i in batch]), self._device)
            return

        # async prefetch window over the worker pool; completed batches
        # move straight to the device (double buffering: transfer of the
        # next batch overlaps compute on the current one).  The window
        # bounds TOTAL in-flight batches (pending + ready) so a slow
        # consumer cannot accumulate unbounded host/HBM memory.
        import collections
        pending = collections.deque()
        ready = collections.deque()
        it = iter(self._batch_sampler)
        window = max(1, self._prefetch)

        def submit():
            try:
                samples = next(it)
            except StopIteration:
                return False
            pending.append(self._pool.apply_async(
                _worker_fn, (samples, self._batchify_fn, self._use_shm)))
            return True

        def drain_ready():
            # move completed worker results into the device queue
            while pending and (pending[0].ready() or not ready):
                result = pending.popleft()
                batch = result.get()
                if self._use_shm:
                    batch = _from_shm(batch)
                ready.append(_as_nd(batch, self._device))
            while len(pending) + len(ready) < window:
                if not submit():
                    break

        try:
            for _ in range(window):
                if not submit():
                    break
            while pending or ready:
                drain_ready()
                yield ready.popleft()
        finally:
            # consumer stopped early (or a worker raised): attach+unlink
            # any in-flight shm blocks so /dev/shm does not leak
            if self._use_shm:
                import logging
                for result in pending:
                    try:
                        _from_shm(result.get(timeout=30))
                    except Exception as e:  # noqa: BLE001 — cleanup pass
                        logging.getLogger("mxnet_tpu.gluon.data").debug(
                            "dataloader drain: in-flight batch dropped "
                            "(%s: %s)", type(e).__name__, e)

    def __len__(self):
        return len(self._batch_sampler)

    def __del__(self):
        if self._pool is not None:
            self._pool.terminate()
