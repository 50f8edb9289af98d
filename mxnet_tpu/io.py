"""mx.io — data iterators.

Re-design of reference python/mxnet/io/io.py (DataIter/DataBatch/DataDesc,
NDArrayIter, PrefetchingIter, ResizeIter) + the C++ iterator chain
(src/io/iter_batchloader.h, iter_prefetcher.h). TPU-first notes: batches
stage host-side in numpy and transfer once per batch (PJRT pipelines the
copy); the prefetcher runs a Python thread per upstream iter (the role of
dmlc ThreadedIter's double buffering).
"""
from __future__ import annotations

import collections
import functools
import queue as _queue
import threading

import numpy as np

from . import ndarray as nd
from .base import MXNetError
from .ndarray import NDArray


class DataDesc(collections.namedtuple("DataDesc", ["name", "shape"])):
    """Data description incl. dtype/layout (parity: io.py DataDesc)."""

    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    def __repr__(self):
        return f"DataDesc[{self.name},{self.shape},{self.dtype},{self.layout}]"

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")


class DataBatch:
    """One mini-batch (parity: io.py DataBatch)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None and not isinstance(data, (list, tuple)):
            raise TypeError(f"Data must be list of NDArrays, got {type(data)}")
        if label is not None and not isinstance(label, (list, tuple)):
            raise TypeError(f"Label must be list of NDArrays, got {type(label)}")
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        data_shapes = [d.shape for d in self.data]
        if self.label:
            label_shapes = [l.shape for l in self.label]
        else:
            label_shapes = None
        return f"{self.__class__.__name__}: data shapes: {data_shapes} " \
               f"label shapes: {label_shapes}"


def stage_batch(batch, ctx):
    """Copy a DataBatch's data/label host->device ahead of need.

    ``jax.device_put`` is asynchronous under PJRT, so staging batch N+1
    while batch N's (fused) train step is still in flight overlaps the
    input feed with device compute — the double-buffer half of the
    one-dispatch train step (fused_step.py).  Arrays already on ``ctx``'s
    device pass through untouched; the returned DataBatch keeps
    pad/index/bucket_key/provide_* so it is a drop-in replacement."""
    import jax

    if ctx is None:
        return batch
    dev = ctx.jax_device
    import time as _time

    from . import telemetry as _telemetry
    from .chaos.failpoints import failpoint as _failpoint
    _failpoint("io/stage")
    staged_bytes = [0]

    def put(arrs):
        if not arrs:
            return arrs
        out = []
        for a in arrs:
            if isinstance(a, NDArray):
                buf = a._data
                if dev in buf.devices():
                    out.append(a)
                    continue
            else:
                buf = np.asarray(a)
            with _telemetry.span("io/stage_batch/device_put"):
                out.append(NDArray(jax.device_put(buf, dev), ctx))
            staged_bytes[0] += int(np.prod(buf.shape or (1,))) * \
                np.dtype(buf.dtype).itemsize
        return out

    # io staging wait: the host time spent issuing the (async) H2D copies
    # — telemetry's mxnet_io_stage_* lane, the raw material behind the
    # fit loop's h2d_stage breakdown
    with _telemetry.span("io/stage_batch"):
        t0 = _time.perf_counter()
        staged = DataBatch(data=put(batch.data),
                           label=put(batch.label) if batch.label
                           else batch.label,
                           pad=batch.pad, index=batch.index,
                           bucket_key=batch.bucket_key,
                           provide_data=batch.provide_data,
                           provide_label=batch.provide_label)
        # graftlint: disable=raw-phase-timing -- this IS telemetry's collection point for the io staging wait
        _telemetry.record_io_stage(_time.perf_counter() - t0,
                                   staged_bytes[0])
    return staged


def make_batch_stager(ctx):
    """A ``batch -> staged batch`` callable for the fit loop's input
    double-buffer, or None for a module type that has no context.
    A context that denotes no device raises at the first staged batch."""
    if ctx is None:
        return None
    return lambda batch: stage_batch(batch, ctx)


class SuperBatch:
    """A window of K*M DataBatches staged as ONE stacked array per
    data/label position (leading dim = number of batches): a device
    array stacked on the device from the K*M batches' own copies, or a
    numpy stack where the window was staged with ``host=True``.
    Consumed by the scanned train step (fused_step.ScanTrainStep); the
    stacked label/output arrays also feed the boundary metric flush —
    stable data, so buffer-reusing iterators can't clobber a deferred
    metric read."""

    __slots__ = ("data", "label", "count")

    def __init__(self, data, label, count):
        self.data = data
        self.label = label
        self.count = count


@functools.cache
def _device_stack():
    """The program that stacks same-shape device arrays to
    ``(len(xs), *shape)``; jit caches it by count, shape and dtype."""
    import jax
    import jax.numpy as jnp

    def stage_super_stack(*xs):
        return jnp.stack(xs)
    return jax.jit(stage_super_stack)


def stage_super_batch(batches, ctx, host=False):
    """Stage a window of DataBatches as ONE ``(len(batches), *shape)``
    array per data/label position.

    This is the window-granular sibling of :func:`stage_batch`: while a
    K-step scan is in flight the fit loop stages the NEXT super-batch
    (PyGraph's whole-iteration-capture argument applied to the input
    feed).  Each batch's array goes to ``jax.device_put`` as it is — a
    numpy array or a zero-copy numpy view of an ``NDArray`` on the CPU
    backend; one already on ``ctx``'s device passes through untouched,
    as in :func:`stage_batch` — and the copies are stacked ON THE DEVICE
    by one cached program: no stacked copy is made on the host.

    ``host=True`` stacks with numpy and stops there: the SuperBatch
    holds numpy arrays.  The mesh fused window wants this — its
    ``run_window`` re-places the stacked feeds itself
    (``DeviceMesh.put_batch`` shards the batch axis across the mesh), so
    a device placement here would just be copied straight back out.

    Counted in the span ``io/stage_super``: every array's bytes once in
    ``mxnet_io_stage_bytes_total``, and the window in
    ``mxnet_io_stage_windows_total{when="at_need"}`` (the fit loop and
    the window feed, which stage while the previous window's scan runs,
    count theirs under ``when="ahead"``)."""
    return _stage_window(batches, ctx, host, "at_need")


def _stage_window(batches, ctx, host, when):
    import time as _time

    import jax

    from . import telemetry as _telemetry

    dev = ctx.jax_device if ctx is not None else None
    from .chaos.failpoints import failpoint as _failpoint
    _failpoint("io/stage")

    def as_host(a):
        return a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)

    def ready(a):
        # what device_put takes with no copy made here: the array
        # already on the device, else numpy (a view, for a CPU-backed
        # NDArray: taking it waits for whatever still writes that
        # array, such as the iterator's own asynchronous host copy)
        if isinstance(a, NDArray) and dev in a._data.devices():
            return a._data
        return as_host(a)

    def stack_on_host(arrs):
        with _telemetry.span("io/stage_super/host_stack"):
            return np.stack([as_host(a) for a in arrs])

    def stack_on_device(arrs):
        with _telemetry.span("io/stage_super/host_stack"):
            bufs = [ready(a) for a in arrs]
        with _telemetry.span("io/stage_super/device_put"):
            return _device_stack()(
                *[b if isinstance(b, jax.Array) else jax.device_put(b, dev)
                  for b in bufs])

    stack = stack_on_host if host else stack_on_device
    with _telemetry.span("io/stage_super"):
        t0 = _time.perf_counter()
        first = batches[0]
        data = [stack([b.data[i] for b in batches])
                for i in range(len(first.data))]
        label = [stack([b.label[i] for b in batches])
                 for i in range(len(first.label or ()))]
        # graftlint: disable=raw-phase-timing -- this IS telemetry's collection point for the io staging wait
        _telemetry.record_io_stage(_time.perf_counter() - t0,
                                   sum(a.nbytes for a in data + label))
        _telemetry.record_io_stage_window(when)
    return SuperBatch(data, label, len(batches))


class DataIter:
    """Base data iterator (parity: io.py DataIter)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()


class ResizeIter(DataIter):
    """Resize a DataIter to the given number of batches
    (parity: io.py ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Thread-based prefetcher over one or more DataIters
    (parity: io.py PrefetchingIter; C++ iter_prefetcher.h double-buffers via
    dmlc ThreadedIter)."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.current_batch = [None for _ in range(self.n_iter)]
        self._spawn()

    def _spawn(self):
        """Fresh queues + prefetch threads for one generation.  The
        stop event and queue list are captured AT SPAWN TIME: a
        straggler thread from a previous generation can never observe
        the new generation's state and keep producing into its queues
        (the pre-fix reset bug — the 1 s join timeout was load-bearing)."""
        queues = [_queue.Queue(maxsize=2) for _ in range(self.n_iter)]
        stop = threading.Event()

        def prefetch_func(it, q):
            while not stop.is_set():
                try:
                    batch = it.next()
                except StopIteration:
                    batch = None
                # bounded put: a stopped generation must exit even if
                # nobody ever drains its queue again
                while not stop.is_set():
                    try:
                        q.put(batch, timeout=0.1)
                        break
                    except _queue.Full:
                        continue
                if batch is None:
                    break

        self._queues = queues
        self._stop = stop
        self._started = True
        self.prefetch_threads = []
        for i in range(self.n_iter):
            t = threading.Thread(target=prefetch_func,
                                 args=(self.iters[i], queues[i]),
                                 daemon=True)
            t.start()
            self.prefetch_threads.append(t)

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def __del__(self):
        self._started = False
        self._stop.set()
        for q in self._queues:
            try:
                q.get_nowait()
            except _queue.Empty:
                pass

    def reset(self):
        # signal FIRST, then drain while joining: a thread blocked on a
        # full queue sees the stop event on its bounded put, so the old
        # generation is provably gone before the upstream iters rewind
        # and the next generation spawns
        self._started = False
        self._stop.set()
        for t in self.prefetch_threads:
            while t.is_alive():
                for q in self._queues:
                    try:
                        while True:
                            q.get_nowait()
                    except _queue.Empty:
                        pass
                t.join(timeout=0.2)
        for i in self.iters:
            i.reset()
        self._spawn()

    def iter_next(self):
        batches = [q.get() for q in self._queues]
        if any(b is None for b in batches):
            return False
        self.current_batch = batches
        return True

    def next(self):
        if self.iter_next():
            if self.n_iter == 1:
                return self.current_batch[0]
            return DataBatch(
                data=sum([b.data for b in self.current_batch], []),
                label=sum([(b.label or []) for b in self.current_batch], []),
                pad=self.current_batch[0].pad,
                index=self.current_batch[0].index)
        raise StopIteration

    def getdata(self):
        return sum([b.data for b in self.current_batch], [])

    def getlabel(self):
        return sum([(b.label or []) for b in self.current_batch], [])

    def getindex(self):
        return self.current_batch[0].index

    def getpad(self):
        return self.current_batch[0].pad


def _init_data(data, allow_empty, default_name):
    """Normalize input data to list of (name, numpy array)
    (parity: io_utils.py _init_data)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {f"_{i}_{default_name}": d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError(
            f"Input must be NDArray, numpy.ndarray, a list of them or dict "
            f"with them as values, got {type(data)}")
    out = []
    for k, v in data.items():
        if isinstance(v, NDArray):
            v = v.asnumpy()
        out.append((k, np.asarray(v)))
    return out


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (parity: io.py NDArrayIter incl.
    pad/discard/roll_over last-batch handling)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.idx = np.arange(self.data[0][1].shape[0])
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.batch_size = batch_size
        self.cursor = -batch_size
        self.num_data = self.idx.shape[0]
        self._cache_data = None
        self._cache_label = None
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.label]

    def hard_reset(self):
        if self.shuffle:
            self._shuffle_data()
        self.cursor = -self.batch_size
        self._cache_data = None
        self._cache_label = None

    def reset(self):
        if self.shuffle:
            self._shuffle_data()
        if self.last_batch_handle == "roll_over" and \
                0 < self.cursor < self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % \
                self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if not self.iter_next():
            raise StopIteration
        data = self.getdata()
        label = self.getlabel()
        if data[0].shape[0] != self.batch_size:
            if self.last_batch_handle == "discard":
                raise StopIteration
            # pad from the start (parity: last_batch_handle='pad')
            pad = self.getpad()
            first_data = self._batchify(self.data, 0, pad)
            first_label = self._batchify(self.label, 0, pad)
            data = [nd.array(np.concatenate([d.asnumpy(), fd.asnumpy()]))
                    for d, fd in zip(data, first_data)]
            label = [nd.array(np.concatenate([l.asnumpy(), fl.asnumpy()]))
                     for l, fl in zip(label, first_label)]
            if self.last_batch_handle == "roll_over":
                self._cache_data = data
                self._cache_label = label
        return DataBatch(data=data, label=label, pad=self.getpad(),
                         index=None)

    def _batchify(self, data_source, start, count):
        end = start + count
        return [nd.array(x[1][start:end]) for x in data_source]

    def getdata(self):
        end = min(self.cursor + self.batch_size, self.num_data)
        return self._batchify(self.data, max(self.cursor, 0),
                              end - max(self.cursor, 0))

    def getlabel(self):
        end = min(self.cursor + self.batch_size, self.num_data)
        return self._batchify(self.label, max(self.cursor, 0),
                              end - max(self.cursor, 0))

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        if self.last_batch_handle == "roll_over" and -self.batch_size < \
                self.cursor < 0:
            return -self.cursor
        return 0

    def _shuffle_data(self):
        np.random.shuffle(self.idx)
        self.data = [(k, v[self.idx]) for k, v in self.data]
        self.label = [(k, v[self.idx]) for k, v in self.label]


class CSVIter(DataIter):
    """CSV file iterator (parity: src/io/iter_csv.cc, numpy-backed)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
        self._inner = NDArrayIter(
            data, label, batch_size,
            last_batch_handle="pad" if round_batch else "discard")
        super().__init__(batch_size)

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()


class LibSVMIter(DataIter):
    """LibSVM sparse iterator (parity: src/io/iter_libsvm.cc — the Criteo
    data path, BASELINE.json configs[4]).

    Parses ``data_libsvm`` ("label idx:val idx:val ..." lines, or
    feature-only when label_libsvm supplies labels separately) into one
    CSR arena up-front, then serves batches as CSRNDArray slices —
    indptr arithmetic only, no per-batch re-parse.  Sharding for
    distributed training via num_parts/part_index (line-level split,
    same contract as the reference's InputSplit)."""

    def __init__(self, data_libsvm, data_shape, label_libsvm=None,
                 label_shape=(1,), batch_size=1, num_parts=1, part_index=0,
                 round_batch=True, **kwargs):
        from .ndarray import sparse as _sp
        self._batch_size = batch_size
        ncol = int(np.prod(data_shape))
        labels, data, indices, indptr = [], [], [], [0]
        with open(data_libsvm) as f:
            lines = f.read().splitlines()
        lines = [l for l in lines if l.strip()]
        lines = lines[part_index::num_parts]
        has_inline_label = label_libsvm is None
        for line in lines:
            parts = line.split()
            start = 0
            if has_inline_label:
                labels.append(float(parts[0]))
                start = 1
            for tok in parts[start:]:
                idx, val = tok.split(":")
                indices.append(int(idx))
                data.append(float(val))
            indptr.append(len(indices))
        if label_libsvm is not None:
            with open(label_libsvm) as f:
                lab_lines = [l for l in f.read().splitlines() if l.strip()]
            lab_lines = lab_lines[part_index::num_parts]
            labels = [float(t) for l in lab_lines for t in l.split()]
        self._data = np.asarray(data, np.float32)
        self._indices = np.asarray(indices, np.int64)
        self._indptr = np.asarray(indptr, np.int64)
        self._labels = np.asarray(labels, np.float32).reshape(
            (-1,) + tuple(label_shape))
        self._ncol = ncol
        self._n = len(self._indptr) - 1
        self._round_batch = round_batch
        self._csr = _sp.csr_matrix
        self._cursor = 0
        super().__init__(batch_size)

    @property
    def provide_data(self):
        return [DataDesc("data", (self._batch_size, self._ncol))]

    @property
    def provide_label(self):
        return [DataDesc("label",
                         (self._batch_size,) + self._labels.shape[1:])]

    def reset(self):
        self._cursor = 0

    def iter_next(self):
        return self._cursor < self._n

    def next(self):
        if not self.iter_next():
            raise StopIteration
        i0 = self._cursor
        i1 = min(i0 + self._batch_size, self._n)
        self._cursor += self._batch_size
        rows = np.arange(i0, i1)
        pad = 0
        if i1 - i0 < self._batch_size:
            if not self._round_batch:
                raise StopIteration
            pad = self._batch_size - (i1 - i0)
            rows = np.concatenate([rows, np.arange(pad) % self._n])  # wrap
        # slice the CSR arena by indptr arithmetic
        ptr = [0]
        dat, ind = [], []
        for r in rows:
            s, e = self._indptr[r], self._indptr[r + 1]
            dat.append(self._data[s:e])
            ind.append(self._indices[s:e])
            ptr.append(ptr[-1] + (e - s))
        batch = self._csr(
            (np.concatenate(dat) if dat else np.zeros(0, np.float32),
             np.concatenate(ind) if ind else np.zeros(0, np.int64),
             np.asarray(ptr, np.int64)),
            shape=(self._batch_size, self._ncol))
        label = nd.array(self._labels[rows])
        return DataBatch(data=[batch], label=[label], pad=pad)


class MXDataIter(DataIter):
    """Placeholder for C++-registered iterators (parity: io.py MXDataIter).
    The RecordIO-backed ImageRecordIter lives in mxnet_tpu.image."""

    def __init__(self, *args, **kwargs):
        raise MXNetError(
            "MXDataIter: use mxnet_tpu.io.NDArrayIter, mxnet_tpu.io.CSVIter "
            "or mxnet_tpu.image.ImageRecordIter")


def ImageRecordIter(**kwargs):
    """Factory kept at io level for source compatibility
    (reference registers ImageRecordIter via MXNET_REGISTER_IO_ITER)."""
    from .image import ImageRecordIter as _IRI
    return _IRI(**kwargs)


class RawRecordIter(DataIter):
    """Pipelined iterator over RAW-pixel RecordIO files: the whole hot
    path — sharded read, IRHeader parse, mirror/normalize, HWC→NCHW
    pack, batch assembly — runs in C++ worker threads ahead of the
    consumer (reference: src/io/iter_image_recordio_2.cc
    ImageRecordIOParser2). Records must hold IRHeader + h*w*c uint8
    pixels (recordio.pack(header, arr.tobytes())); JPEG-compressed
    records go through image.ImageRecordIter instead (decode needs a
    codec library). Falls back to a Python reader when the native
    library is unavailable.
    """

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, rand_mirror=False, seed=0, mean=None,
                 std=None, prefetch=4, preprocess_threads=2):
        super().__init__(batch_size)
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self._path = str(path_imgrec)
        from . import _native
        self._pipe = _native.RecordPipe.create(
            self._path, batch_size, self.data_shape, label_width,
            shuffle=shuffle, rand_mirror=rand_mirror, seed=seed,
            mean=mean, std=std, prefetch=prefetch,
            num_threads=preprocess_threads)
        if self._pipe is None:  # pure-Python fallback — STREAMS by
            # offset table, never holds the dataset in memory
            self._py_offsets = self._py_scan_offsets()
            self._py_cursor = 0
            self._py_rng = np.random.RandomState(seed)
            self._py_shuffle = shuffle
            self._py_mirror = rand_mirror
            self._py_order = np.arange(len(self._py_offsets))
            self._mean = (np.asarray(mean, np.float32)
                          if mean is not None else None)
            self._std = (np.asarray(std, np.float32)
                         if std is not None else None)
            if shuffle:
                self._py_rng.shuffle(self._py_order)

    def _py_scan_offsets(self):
        """Frame table (offset, length) per whole record — dmlc recordio
        framing, the Python twin of mxio_scan_records."""
        import struct
        out = []
        with open(self._path, "rb") as f:
            while True:
                head = f.read(8)
                if len(head) < 8:
                    break
                magic, lrec = struct.unpack("<II", head)
                if magic != 0xced7230a:
                    raise MXNetError(f"bad recordio magic in {self._path}")
                cflag, ln = lrec >> 29, lrec & ((1 << 29) - 1)
                if cflag == 0:
                    out.append((f.tell(), ln))
                f.seek(ln + ((4 - ln % 4) % 4), 1)
        return out

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label",
                         (self.batch_size, self.label_width))]

    def reset(self):
        if self._pipe is not None:
            self._pipe.reset()
        else:
            self._py_cursor = 0
            if self._py_shuffle:
                self._py_rng.shuffle(self._py_order)

    def next(self):
        if self._pipe is not None:
            got = self._pipe.next_batch()
            if got is None:
                raise StopIteration
            data, label = got
        else:
            data, label = self._py_next()
        from . import ndarray as nd
        return DataBatch(data=[nd.array(data)], label=[nd.array(label)],
                         pad=0)

    def _py_next(self):
        from . import recordio
        c, h, w = self.data_shape
        n = self.batch_size
        if self._py_cursor + n > len(self._py_order):
            raise StopIteration
        data = np.empty((n, c, h, w), np.float32)
        label = np.zeros((n, self.label_width), np.float32)
        with open(self._path, "rb") as f:
            for i in range(n):
                off, ln = self._py_offsets[
                    self._py_order[self._py_cursor + i]]
                f.seek(off)
                header, body = recordio.unpack(f.read(ln))
                lbl = np.asarray(header.label).ravel()
                label[i, :min(len(lbl), self.label_width)] = \
                    lbl[:self.label_width]
                img = np.frombuffer(body, np.uint8).reshape(h, w, c)
                if self._py_mirror and self._py_rng.rand() < 0.5:
                    img = img[:, ::-1]
                x = img.astype(np.float32)
                if self._mean is not None:
                    x = x - self._mean
                if self._std is not None:
                    x = x / self._std
                data[i] = x.transpose(2, 0, 1)
        self._py_cursor += n
        return data, label
