"""mxnet_tpu.io_pipeline — sharded streaming data plane (ISSUE 19).

PRs 6/9/11 drove the device side to 1/K dispatches per step; the input
feed stayed a serial prefix on the train thread — it read, decoded,
stacked and staged every super-batch while the accelerator idled.  This
module pipelines that last serial stage:

* a **shard source** splits the dataset into independently readable
  shards (in-memory arrays or raw-pixel RecordIO byte ranges);
* a **seeded per-epoch shard order** (``MXNET_DATA_SHARD_SEED``) fixes
  the batch sequence BEFORE any worker runs — the same order is
  produced for any worker count, which is the load-bearing invariant
  behind the bitwise fit-parity guarantee (docs/data.md);
* a pool of **reader workers** (``MXNET_DATA_WORKERS``) claims shard
  positions — each worker statically prefers its own slice of the
  order (position ``p`` with ``p % workers == wid``) so a healthy pool
  never contends, and steals the earliest eligible position otherwise;
* each position owns a **bounded output queue**
  (``MXNET_DATA_QUEUE_DEPTH`` batches) and only positions inside a
  bounded **in-flight window** are claimable, so total buffered
  batches — and host RSS under the PR-13 sampler — stay capped no
  matter how far the readers could run ahead;
* the **assembler** (the consumer side of :class:`DataPipeline`)
  drains queues in global order, so the delivered batch sequence is
  identical to a serial read of the same order;
* a dead or poisoned reader is **rebalanced**: its in-progress shard
  is requeued (resuming at the first undelivered batch — every sample
  delivered exactly once) and its remaining slice is absorbed by the
  survivors' steal path; a typed :class:`DataReaderError` is raised
  only when ALL readers are gone — a starved consumer never stalls;
* :class:`WindowFeed` applies the PR-10 stage/dispatch thread-pair
  idiom to training input: a staging thread collects K*M batches and
  runs ``io.stage_super_batch`` OFF the train thread, double-buffered
  so window N+1 stages while window N executes.

Chaos site ``io/reader/read`` fires in the reader loop per batch
(delay = slow reader, raise = dead reader).  Telemetry:
``mxnet_data_wait_seconds`` / ``mxnet_data_queue_depth`` /
``mxnet_data_batches_total`` / ``mxnet_data_rebalance_total``.
"""
from __future__ import annotations

import logging
import queue as _queue
import struct
import threading
import time
import weakref

import numpy as np

from . import io as mx_io
from . import ndarray as nd
from .base import MXNetError
from .io import DataBatch, DataDesc, DataIter


class DataReaderError(MXNetError):
    """Typed: every reader worker of a :class:`DataPipeline` died.

    Raised from the consumer side (``next()``) once the buffered
    batches are drained — a job-level failure the caller can retry or
    surface, never a silent stall."""


#: live pipelines, for the ``mxnet_data_queue_depth`` alert probe
#: (weak: pipelines come and go with fits)
_ACTIVE = weakref.WeakSet()

#: a pipeline that made no put/get progress for this long stops
#: answering the queue-depth probe — an absence rule on
#: ``mxnet_data_queue_depth`` then sees the family go silent
#: (docs/observability.md)
PROBE_FRESH_S = 15.0

_END_OF_SHARD = object()


class _Shutdown(Exception):
    """Internal: reader told to exit (reset/close); not an error."""


def queue_depth_samples():
    """``(labels, value)`` rows for the alert engine's
    ``mxnet_data_queue_depth`` probe: one row per live pipeline role
    that made progress within :data:`PROBE_FRESH_S`.  A wedged
    assembler stops refreshing its row, so an ``absence`` rule fires
    while the train/fit watchdog walks up to its page."""
    now = time.monotonic()
    out = []
    for pipe in list(_ACTIVE):
        if now - pipe._last_progress <= PROBE_FRESH_S:
            out.append(({"role": "shards"}, float(pipe.buffered())))
    return out


# -- shard sources ------------------------------------------------------------
class ShardSource:
    """A dataset split into independently readable shards.

    Subclasses fix ``num_shards`` at construction and implement
    :meth:`read_shard` as a generator of :class:`io.DataBatch`; the
    ``start`` argument skips already-delivered batches when a shard is
    requeued after a reader death (the exactly-once contract)."""

    batch_size = 0

    @property
    def provide_data(self):
        raise NotImplementedError()

    @property
    def provide_label(self):
        raise NotImplementedError()

    def num_shards(self):
        raise NotImplementedError()

    def read_shard(self, shard, start=0):
        raise NotImplementedError()


class NDArraySource(ShardSource):
    """In-memory arrays as a shard source (the NDArrayIter twin).

    Batches are ``batch_size`` consecutive rows; a shard is
    ``batches_per_shard`` consecutive batches; trailing rows that do
    not fill a batch are discarded (``last_batch_handle='discard'``
    semantics — shards must be uniform for the window path anyway)."""

    def __init__(self, data, label=None, batch_size=1, batches_per_shard=1,
                 data_name="data", label_name="softmax_label"):
        if batch_size < 1 or batches_per_shard < 1:
            raise MXNetError("NDArraySource: batch_size and "
                             "batches_per_shard must be >= 1")
        self.data = mx_io._init_data(data, allow_empty=False,
                                     default_name=data_name)
        self.label = mx_io._init_data(label, allow_empty=True,
                                      default_name=label_name)
        self.batch_size = batch_size
        self.batches_per_shard = batches_per_shard
        self.num_batches = self.data[0][1].shape[0] // batch_size
        self._n_shards = -(-self.num_batches // batches_per_shard) \
            if self.num_batches else 0

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def num_shards(self):
        return self._n_shards

    def read_shard(self, shard, start=0):
        first = shard * self.batches_per_shard
        last = min(first + self.batches_per_shard, self.num_batches)
        for b in range(first + start, last):
            r0 = b * self.batch_size
            r1 = r0 + self.batch_size
            yield DataBatch(
                data=[nd.array(v[r0:r1]) for _, v in self.data],
                label=[nd.array(v[r0:r1]) for _, v in self.label],
                pad=0, index=np.arange(r0, r1))


class RecordFileSource(ShardSource):
    """RAW-pixel RecordIO file as a shard source.

    Scans the dmlc recordio framing once (the offset-table twin of
    ``io.RawRecordIter._py_scan_offsets``), then serves shards as
    contiguous record ranges — each reader seeks into its own range,
    so shards decode independently and in parallel.  Records must hold
    IRHeader + h*w*c uint8 pixels (``recordio.pack``)."""

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 batches_per_shard=1, mean=None, std=None):
        self._path = str(path_imgrec)
        self.data_shape = tuple(data_shape)
        self.batch_size = batch_size
        self.label_width = label_width
        self.batches_per_shard = batches_per_shard
        self._mean = np.asarray(mean, np.float32) if mean is not None \
            else None
        self._std = np.asarray(std, np.float32) if std is not None else None
        self._offsets = self._scan_offsets()
        self.num_batches = len(self._offsets) // batch_size
        self._n_shards = -(-self.num_batches // batches_per_shard) \
            if self.num_batches else 0

    def _scan_offsets(self):
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

    def num_shards(self):
        return self._n_shards

    def read_shard(self, shard, start=0):
        from . import recordio
        c, h, w = self.data_shape
        n = self.batch_size
        first = shard * self.batches_per_shard
        last = min(first + self.batches_per_shard, self.num_batches)
        with open(self._path, "rb") as f:
            for b in range(first + start, last):
                data = np.empty((n, c, h, w), np.float32)
                label = np.zeros((n, self.label_width), np.float32)
                for i in range(n):
                    off, ln = self._offsets[b * n + i]
                    f.seek(off)
                    header, body = recordio.unpack(f.read(ln))
                    lbl = np.asarray(header.label).ravel()
                    label[i, :min(len(lbl), self.label_width)] = \
                        lbl[:self.label_width]
                    x = np.frombuffer(body, np.uint8).reshape(h, w, c) \
                        .astype(np.float32)
                    if self._mean is not None:
                        x = x - self._mean
                    if self._std is not None:
                        x = x / self._std
                    data[i] = x.transpose(2, 0, 1)
                yield DataBatch(data=[nd.array(data)],
                                label=[nd.array(label)], pad=0,
                                index=np.arange(b * n, b * n + n))


# -- the pipeline -------------------------------------------------------------
class _ShardJob:
    """One position of the epoch shard order: its bounded output queue
    plus the delivered-batch watermark that makes requeue-after-death
    exactly-once (the new owner resumes at ``delivered``)."""

    __slots__ = ("shard", "queue", "delivered", "state", "owner",
                 "inline", "idle_polls")

    def __init__(self, shard, depth):
        self.shard = shard
        # +1: the end-of-shard sentinel rides the same queue but must
        # not eat a batch slot (``depth`` means depth BATCHES buffered)
        self.queue = _queue.Queue(maxsize=depth + 1)
        self.delivered = 0     # batches put into the queue so far
        self.state = "pending"  # pending -> active -> produced -> consumed
        self.owner = None
        self.inline = None     # assembler-rescue generator
        self.idle_polls = 0


def epoch_shard_order(num_shards, seed, epoch, num_parts=1, part_index=0):
    """The seeded per-epoch shard order — the determinism contract.

    A function of ``(num_shards, seed, epoch)`` ONLY: worker count,
    queue depth and scheduling never enter, so every configuration
    replays the same batch sequence.  Multi-process meshes slice the
    one global permutation per rank (``order[part_index::num_parts]``,
    the LibSVMIter num_parts contract) so ranks read disjoint shards
    of the same epoch."""
    rng = np.random.RandomState((int(seed) + int(epoch)) & 0x7fffffff)
    order = rng.permutation(num_shards)
    if num_parts > 1:
        order = order[part_index::num_parts]
    return [int(s) for s in order]


class DataPipeline(DataIter):
    """Multi-worker streaming iterator over a :class:`ShardSource`.

    ``workers=0`` reads the same seeded shard order serially on the
    calling thread — the bitwise-identical baseline.  ``workers>0`` runs
    the reader pool described in the module docstring; the delivered
    sequence is identical in both modes."""

    def __init__(self, source, workers=None, queue_depth=None, seed=None,
                 num_parts=1, part_index=0, max_inflight=None):
        from . import config as _config
        super().__init__(source.batch_size)
        self._source = source
        self._workers = int(_config.get("MXNET_DATA_WORKERS")
                            if workers is None else workers)
        self._depth = max(1, int(_config.get("MXNET_DATA_QUEUE_DEPTH")
                                 if queue_depth is None else queue_depth))
        self._seed = int(_config.get("MXNET_DATA_SHARD_SEED")
                         if seed is None else seed)
        self._num_parts = int(num_parts)
        self._part_index = int(part_index)
        self._max_inflight = int(max_inflight) if max_inflight else \
            max(2 * self._workers, self._workers + 2)
        self._epoch = 0
        self._cond = threading.Condition()
        self._threads = []
        self._stop = threading.Event()
        self._jobs = []
        self._buffered = 0          # batches in queues (backpressure gauge)
        self._last_progress = time.monotonic()
        self._fatal = None          # the last reader's fatal exception
        self._live = 0
        self._pos = 0               # assembler cursor into the order
        self._base = 0              # first unconsumed position
        self._serial = None         # workers==0 generator
        self._started = False
        _ACTIVE.add(self)
        self._begin_epoch()

    # -- epoch lifecycle -----------------------------------------------------
    @property
    def provide_data(self):
        return self._source.provide_data

    @property
    def provide_label(self):
        return self._source.provide_label

    @property
    def workers(self):
        return self._workers

    def epoch_order(self):
        """This epoch's shard order for THIS rank (testing hook)."""
        return epoch_shard_order(self._source.num_shards(), self._seed,
                                 self._epoch, self._num_parts,
                                 self._part_index)

    def _begin_epoch(self):
        order = self.epoch_order()
        with self._cond:
            self._jobs = [_ShardJob(s, self._depth) for s in order]
            self._pos = 0
            self._base = 0
            self._buffered = 0
            self._fatal = None
            self._serial = None
            self._started = False

    def _start(self):
        with self._cond:
            if self._started:
                return
            self._started = True
            jobs = list(self._jobs)
            if self._workers <= 0:
                def serial():
                    from . import telemetry as _telemetry
                    for job in jobs:
                        for b in self._source.read_shard(job.shard):
                            _telemetry.record_data_batches(1)
                            yield b
                self._serial = serial()
                return
            self._stop = threading.Event()
            self._live = self._workers
            stop = self._stop
        threads = []
        for wid in range(self._workers):
            t = threading.Thread(
                target=self._reader, args=(wid, stop),
                name=f"mx-data-reader-{wid}", daemon=True)
            t.start()
            threads.append(t)
        with self._cond:
            self._threads = threads

    def _shutdown(self):
        """Stop this epoch's readers: signal, drain (a put-blocked
        reader needs queue space to see the stop), then join."""
        with self._cond:
            self._stop.set()
            threads = list(self._threads)
            jobs = list(self._jobs)
            self._cond.notify_all()
        for t in threads:
            while t.is_alive():
                for job in jobs:
                    try:
                        while True:
                            job.queue.get_nowait()
                    except _queue.Empty:
                        pass
                t.join(timeout=0.2)
        with self._cond:
            self._threads = []
            self._serial = None

    def reset(self):
        with self._cond:
            started = self._started
        if started:
            self._shutdown()
        self._epoch += 1
        self._begin_epoch()

    def close(self):
        """Tear the pool down without starting another epoch."""
        with self._cond:
            started = self._started
            self._started = False
        if started:
            self._shutdown()

    def __del__(self):
        try:
            self.close()
        except Exception as e:  # noqa: BLE001 — interpreter-teardown best effort
            logging.getLogger(__name__).debug(
                "DataPipeline teardown: %r", e)

    # -- reader workers ------------------------------------------------------
    def _claim(self, wid):
        """Next shard position for worker ``wid``: its own slice of the
        order first (``pos % workers == wid`` — zero contention while
        the pool is healthy), else the earliest eligible position (the
        steal path that absorbs a dead peer's slice).  Only positions
        inside the in-flight window are claimable — the backpressure
        bound.  None = no work will ever remain."""
        with self._cond:
            while True:
                if self._stop.is_set():
                    return None
                hi = min(len(self._jobs), self._base + self._max_inflight)
                eligible = [p for p in range(self._base, hi)
                            if self._jobs[p].state == "pending"]
                if eligible:
                    own = [p for p in eligible
                           if p % self._workers == wid]
                    p = own[0] if own else eligible[0]
                    job = self._jobs[p]
                    job.state = "active"
                    job.owner = wid
                    return p, job
                if all(j.state in ("produced", "consumed")
                       for j in self._jobs):
                    return None
                self._cond.wait(timeout=0.1)

    def _put(self, job, item, stop):
        while True:
            try:
                job.queue.put(item, timeout=0.1)
                break
            except _queue.Full:
                if stop.is_set():
                    raise _Shutdown() from None
        if item is not _END_OF_SHARD:
            with self._cond:
                self._buffered += 1
                depth = self._buffered
            self._note_progress(depth)

    def _note_progress(self, depth):
        from . import telemetry as _telemetry
        self._last_progress = time.monotonic()
        _telemetry.record_data_queue_depth(depth)

    def _reader(self, wid, stop):
        from . import telemetry as _telemetry
        from .chaos.failpoints import failpoint as _failpoint
        pos = None
        try:
            while True:
                claimed = self._claim(wid)
                if claimed is None:
                    return
                pos, job = claimed
                for batch in self._source.read_shard(job.shard,
                                                     start=job.delivered):
                    # the chaos reader site: delay = slow reader,
                    # raise = this reader dies and its work rebalances
                    _failpoint("io/reader/read")
                    self._put(job, batch, stop)
                    job.delivered += 1
                    _telemetry.record_data_batches(1)
                self._put(job, _END_OF_SHARD, stop)
                with self._cond:
                    job.state = "produced"
                    self._cond.notify_all()
        except _Shutdown:
            return
        except BaseException as e:  # noqa: BLE001 — any reader fault rebalances
            self._on_reader_death(wid, pos, e)

    def _on_reader_death(self, wid, pos, exc):
        from . import telemetry as _telemetry
        with self._cond:
            self._live -= 1
            if pos is not None and self._jobs[pos].state == "active" \
                    and self._jobs[pos].owner == wid:
                # requeue the in-progress shard; ``delivered`` makes the
                # next owner resume at the first undelivered batch —
                # exactly-once.  The dead worker's untouched slice needs
                # nothing: survivors steal it position by position.
                self._jobs[pos].state = "pending"
                self._jobs[pos].owner = None
            unfinished = any(j.state not in ("produced", "consumed")
                             for j in self._jobs)
            if self._live <= 0 and unfinished:
                self._fatal = exc
            self._cond.notify_all()
        _telemetry.record_data_rebalance()

    # -- the assembler (consumer side) --------------------------------------
    def next(self):
        from . import telemetry as _telemetry
        self._start()  # idempotent: no-op once this epoch is running
        with self._cond:
            serial = self._serial
        if serial is not None:
            return next(serial)
        t0 = time.perf_counter()
        try:
            while True:
                with self._cond:
                    if self._pos >= len(self._jobs):
                        raise StopIteration
                    job = self._jobs[self._pos]
                    inline = job.inline
                if inline is not None:
                    # assembler rescue: this position's reader is gone
                    # and nobody claimed it — read it in-thread so the
                    # epoch keeps moving (never a stall)
                    try:
                        batch = next(inline)
                    except StopIteration:
                        self._consume_job(job)
                        continue
                    _telemetry.record_data_batches(1)
                    self._note_progress(self.buffered())
                    return batch
                try:
                    item = job.queue.get(timeout=0.05)
                except _queue.Empty:
                    self._on_starved(job)
                    continue
                job.idle_polls = 0
                if item is _END_OF_SHARD:
                    self._consume_job(job)
                    continue
                with self._cond:
                    self._buffered -= 1
                    depth = self._buffered
                self._note_progress(depth)
                return item
        finally:
            # graftlint: disable=raw-phase-timing -- this IS telemetry's collection point for the data_wait lane
            _telemetry.record_data_wait(time.perf_counter() - t0)

    def _consume_job(self, job):
        with self._cond:
            job.state = "consumed"
            job.inline = None
            self._pos += 1
            self._base = self._pos
            self._cond.notify_all()

    def _on_starved(self, job):
        """The head-of-line queue timed out.  Three cases: the pool is
        entirely dead (typed error — never a silent stall), the head
        position has an owner (it is producing or briefly scheduled —
        keep waiting), or it is ownerless and stayed that way across
        two polls while every survivor is busy elsewhere (claim it for
        the assembler and read it inline)."""
        with self._cond:
            if self._fatal is not None and job.queue.empty() \
                    and job.state != "produced":
                raise DataReaderError(
                    f"all {self._workers} data reader workers died "
                    f"(epoch {self._epoch}, shard position {self._pos}"
                    f"/{len(self._jobs)})") from self._fatal
            if job.state == "pending":
                job.idle_polls += 1
                if job.idle_polls >= 2:
                    job.state = "active"
                    job.owner = -1
                    job.inline = self._drain_then_read(job)

    def _drain_then_read(self, job):
        # leftovers a dead owner already queued come first (order), then
        # read from the delivered watermark — exactly-once either way
        try:
            while True:
                item = job.queue.get_nowait()
                if item is _END_OF_SHARD:
                    return
                with self._cond:
                    self._buffered -= 1
                yield item
        except _queue.Empty:
            pass
        for batch in self._source.read_shard(job.shard,
                                             start=job.delivered):
            job.delivered += 1
            yield batch

    def buffered(self):
        """Batches currently queued (the backpressure bound under
        test: <= max_inflight * queue_depth)."""
        with self._cond:
            return self._buffered


# -- window feed (stage half of the stage/dispatch thread pair) --------------
class WindowFeed:
    """Collect-and-stage thread for the scanned fit loop.

    Pulls batches from ``data_iter`` (any iterator — a
    :class:`DataPipeline` assembler or a plain DataIter), groups them
    into W-batch windows exactly like ``Module._fit_epoch_scan_inner``
    .collect(), and runs ``io.stage_super_batch``'s staging OFF the
    train thread: the same as the fit loop's own (each batch put on
    the device as it is and the window stacked there; a numpy stack
    under ``host``), counted in ``mxnet_io_stage_windows_total`` as
    staged ``ahead``.  Without the feed the fit loop stages window N+1
    itself, on the train thread, between window N's dispatch and its
    boundary; the feed takes collecting and staging off that thread
    altogether.  A 2-deep bounded queue double-buffers: window N+1 is
    collected and staged while window N's scan executes.  Items:

    * ``("window", batches, sbatch, (t0, t1))`` — a full staged window
      (raw batches ride along for the per-batch fallback path);
    * ``("fallback", batches, None, (t0, t1))`` — a short or
      shape-mismatched group that must run per-batch;
    * ``("end", ...)`` — upstream exhausted;
    * ``("error", exc, ...)`` — upstream raised; re-raised on the
      train thread.
    """

    def __init__(self, data_iter, window, ctx, batch_ok, depth=2,
                 host=False):
        self._iter = iter(data_iter)
        self._window = int(window)
        self._ctx = ctx
        self._host = host
        self._batch_ok = batch_ok
        self._q = _queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="mx-window-feed", daemon=True)
        self._thread.start()

    def _run(self):
        from . import telemetry as _telemetry
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter()
                batches, full = [], True
                ended = False
                while len(batches) < self._window:
                    try:
                        b = next(self._iter)
                    except StopIteration:
                        ended = True
                        break
                    batches.append(b)
                    if not self._batch_ok(b):
                        full = False
                        break
                span = (t0, time.perf_counter())
                if len(batches) == self._window and full:
                    sbatch = mx_io._stage_window(batches, self._ctx,
                                                 self._host, "ahead")
                    _telemetry.record_data_queue_depth(
                        self._q.qsize() + 1, role="feed")
                    self._put(("window", batches, sbatch, span))
                elif batches:
                    self._put(("fallback", batches, None, span))
                if ended:
                    self._put(("end", None, None, None))
                    return
        except _Shutdown:
            pass
        except BaseException as e:  # noqa: BLE001 — surfaced on the train thread
            try:
                self._put(("error", e, None, None))
            except _Shutdown:
                pass

    def _put(self, item):
        while True:
            try:
                self._q.put(item, timeout=0.1)
                return
            except _queue.Full:
                if self._stop.is_set():
                    raise _Shutdown() from None

    def get(self):
        """Next item, blocking; the caller charges the blocked time to
        the ``data_wait`` lane (it wraps this call)."""
        from . import telemetry as _telemetry
        t0 = time.perf_counter()
        while True:
            try:
                item = self._q.get(timeout=0.5)
                break
            except _queue.Empty:
                if not self._thread.is_alive():
                    # feed thread died without an item: surface typed
                    # rather than spin forever
                    raise DataReaderError(
                        "window-feed staging thread died") from None
        # graftlint: disable=raw-phase-timing -- this IS telemetry's collection point for the data_wait lane
        _telemetry.record_data_wait(time.perf_counter() - t0)
        if item[0] == "error":
            raise item[1]
        return item

    def close(self):
        self._stop.set()
        while self._thread.is_alive():
            try:
                while True:
                    self._q.get_nowait()
            except _queue.Empty:
                pass
            self._thread.join(timeout=0.2)


def feed_enabled():
    """Whether the fit loop should stage windows off-thread
    (``MXNET_DATA_WORKERS > 0`` — one knob arms both halves of the
    data plane)."""
    from . import config as _config
    return int(_config.get("MXNET_DATA_WORKERS")) > 0


# -- smoke -------------------------------------------------------------------
def _smoke():
    """CI gate: order determinism across worker counts, exactly-once
    under a mid-epoch reader death, and the backpressure bound."""
    from .chaos import failpoints as _fp

    rng = np.random.RandomState(7)
    x = rng.rand(64 * 4, 5).astype(np.float32)
    y = rng.rand(64 * 4, 1).astype(np.float32)

    def seq(workers, **kw):
        src = NDArraySource(x, y, batch_size=4, batches_per_shard=2)
        pipe = DataPipeline(src, workers=workers, queue_depth=2, seed=3,
                            **kw)
        out = []
        for b in pipe:
            out.append(np.concatenate([a.asnumpy().ravel()
                                       for a in b.data + b.label]))
        pipe.close()
        return out

    base = seq(0)
    assert len(base) == 64, len(base)
    for w in (1, 2, 4):
        got = seq(w)
        assert len(got) == len(base) and \
            all(np.array_equal(a, b) for a, b in zip(base, got)), \
            f"shard order diverged at workers={w}"

    # one reader dies mid-epoch: every batch still arrives exactly once
    _fp.arm("io/reader/read", "raise", hits=13, count=1)
    try:
        got = seq(2)
    finally:
        _fp.disarm("io/reader/read")
    assert len(got) == len(base) and \
        all(np.array_equal(a, b) for a, b in zip(base, got)), \
        "dead-reader rebalance lost or duplicated batches"

    # stalled consumer: buffered batches stay inside the bound
    src = NDArraySource(x, y, batch_size=4, batches_per_shard=2)
    pipe = DataPipeline(src, workers=2, queue_depth=2, seed=3)
    next(pipe)
    time.sleep(0.5)
    bound = pipe._max_inflight * pipe._depth
    assert pipe.buffered() <= bound, (pipe.buffered(), bound)
    pipe.close()
    print("io_pipeline smoke OK: determinism x {0,1,2,4} workers, "
          "exactly-once under reader death, backpressure bound",
          flush=True)


if __name__ == "__main__":
    _smoke()
