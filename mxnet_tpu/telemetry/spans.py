"""Span tracer: ``with telemetry.span("fit/step/h2d"): ...``.

Thread-safe, nestable, and ~zero-cost when telemetry is disabled: the
disabled path is one module-global check and a shared no-op context
manager (no allocation, well under a microsecond — asserted by
``tests/test_telemetry.py``).

An enabled span is one ``jax.profiler.TraceAnnotation`` — an inactive
``TraceMe`` outside a profiler session, a host event on the profiler's
own clock inside one, whoever started it (``profiler.start`` with
``MXNET_PROFILER_XPLANE_DIR``, the benchmark, TensorBoard) — so spans sit
next to the XLA device timeline and name the device's idle gaps.  On
exit it fans out to every other sink at once:

* a record in a bounded in-memory ring (:func:`span_records`): name,
  start and end (``time.perf_counter_ns``), self time (the duration
  minus what child spans cover), the parent span's name, the thread,
  the thread's step id (:func:`next_step`) and the counter deltas made
  while it was innermost (:func:`count_in_span`);
* the profiler's chrome-trace stream (``profiler.record_op`` with
  ``cat="span"``) — spans land in the same ``profiler.dump()`` JSON and
  ``profiler.dumps()`` aggregate table as op dispatches, on the thread's
  own lane, so nesting renders natively in chrome://tracing;
* the ``mxnet_span_seconds`` histogram in the global registry
  (label ``span=<name>``), which is what ``snapshot()`` /
  ``prometheus_dump()`` expose.

Naming convention (docs/observability.md): slash-separated paths,
``<subsystem>/<operation>[/<phase>]`` — e.g. ``fit/step/prepare``,
``io/stage_batch/device_put``, ``serving/batch/run``.
"""
from __future__ import annotations

import collections
import threading
import time

import jax
from jax.profiler import TraceAnnotation

from .. import profiler as _profiler

_enabled = False
_tls = threading.local()

# filled in by telemetry/__init__ (one histogram family for all spans)
_span_hist = None

# finished spans, oldest dropped first: a gluon step leaves ~10 records,
# a fit step ~12, so this holds the last few thousand steps
RING_SIZE = 1 << 16
_ring = collections.deque(maxlen=RING_SIZE)


def enable():
    """Turn the span tracer + step-time breakdown on for this process."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled():
    return _enabled


def _stack():
    s = getattr(_tls, "spans", None)
    if s is None:
        s = _tls.spans = []
    return s


def current_span():
    """Name of the innermost open span on this thread (None outside)."""
    s = getattr(_tls, "spans", None)
    return s[-1].name if s else None


def span_stack():
    """Open span names on this thread, outermost first."""
    return tuple(sp.name for sp in getattr(_tls, "spans", ()) or ())


def current_step():
    """This thread's step id: what every span of one training step (or
    one scanned window) shares."""
    return getattr(_tls, "step", 0)


def next_step():
    """Close this thread's step: spans opened from here on belong to the
    next one.  The fit loop's ``StepTimer`` calls it at every
    ``begin_step``/``end_step``; ``gluon.Trainer.step`` and
    ``spmd.TrainStep.__call__``, which have no timer, call it
    themselves."""
    _tls.step = getattr(_tls, "step", 0) + 1


_FIELDS = ("name", "start_ns", "end_ns", "self_ns", "parent", "thread",
           "step", "counts")


def span_records():
    """The finished spans still in the ring, oldest first, as dicts:
    ``name``, ``start_ns``, ``end_ns``, ``self_ns``, ``parent`` (name or
    None), ``thread`` (ident), ``step`` and ``counts`` ({counter name:
    delta})."""
    return [dict(zip(_FIELDS, rec)) for rec in list(_ring)]


def reset_span_records():
    _ring.clear()


def count_in_span(counter, n, labels=None):
    """Add ``n`` to a registry counter and, when a span is open on this
    thread, to that span's record: a reader can then take the counter's
    delta over the very steps it takes the span's time over."""
    counter.inc(n, labels=labels)
    s = getattr(_tls, "spans", None)
    if s:
        counts = s[-1]._counts
        if counts is None:
            counts = s[-1]._counts = {}
        counts[counter.name] = counts.get(counter.name, 0) + n


def host_arg_stats(args, devices):
    """``(leaves, bytes)`` of the leaves of a jitted call's arguments
    that are not ``jax.Array``s already on ``devices`` (a set): Python
    and numpy scalars, numpy arrays, arrays on the host CPU device.  Each
    is a transfer made inside the call, where no Python span reaches."""
    leaves = nbytes = 0
    for leaf in jax.tree_util.tree_leaves(args):
        if isinstance(leaf, jax.Array) and leaf.devices() <= devices:
            continue
        leaves += 1
        nbytes += getattr(leaf, "nbytes", 8)   # a Python scalar: 8
    return leaves, nbytes


class _NullSpan:
    """Shared no-op for the disabled path — nothing allocated per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One timed region.  ``lane`` is ``(StepTimer, lane name)`` for the
    spans ``StepTimer.lane`` opens: their duration is also the lane's."""

    __slots__ = ("name", "_lane", "_parent", "_step", "_t0", "_child_ns",
                 "_counts", "_jax")

    def __init__(self, name, lane=None):
        self.name = name
        self._lane = lane
        self._child_ns = 0
        self._counts = None

    def __enter__(self):
        s = _stack()
        self._parent = s[-1] if s else None
        s.append(self)
        self._step = getattr(_tls, "step", 0)
        self._jax = TraceAnnotation(self.name)
        self._jax.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._jax.__exit__(*exc)
        dur_ns = t1 - self._t0
        s = _stack()
        if s and s[-1] is self:
            s.pop()
        parent = self._parent
        if parent is not None:
            parent._child_ns += dur_ns
        _ring.append((self.name, self._t0, t1, dur_ns - self._child_ns,
                      parent.name if parent is not None else None,
                      threading.get_ident(), self._step, self._counts))
        dur_s = dur_ns / 1e9
        if self._lane is not None:
            self._lane[0].add(self._lane[1], dur_s)
        if _span_hist is not None:
            _span_hist.observe(dur_s, labels={"span": self.name})
        _profiler.record_op(self.name, dur_s * 1e6, cat="span")
        return False


def span(name):
    """Context manager timing one named region (no-op while disabled)."""
    if not _enabled:
        return _NULL_SPAN
    return _Span(name)
