"""End-to-end tracing: one trace context per serving request / train
window, decomposable into stage spans (ISSUE 12 tentpole).

The PR-5 span tracer answers "how long does stage X take, in
aggregate"; it cannot answer "why was THIS request slow".  A trace
context is the per-unit-of-work answer: :func:`start` mints a
``trace_id`` and the context object rides the work itself — a serving
request carries it from ``ModelServer.predict_async`` through admission,
routing (surviving spill hops to sibling replicas), the batcher queue,
the stage/dispatch pipeline and the result fan-out; a scanned training
window carries it from batch collection through staging, the multi-host
rendezvous, the donated dispatch and the boundary metric flush.  Each
stage records an absolute ``(t0, t1)`` interval, so a finished trace
decomposes its end-to-end latency into named, tiling stages:

    serving: submit -> queue_wait -> stage -> staged_wait -> dispatch
             -> resolve        (+ events: admission verdict, route,
                                 spill hops, shed, timeout)
    train:   collect -> stage -> rendezvous -> dispatch
             -> boundary_flush

Stage exits reuse the span fan-out: every stage duration lands in the
``mxnet_trace_stage_seconds{kind,stage}`` histogram and the profiler's
chrome-trace stream (``cat="span"``); finished traces feed
``mxnet_trace_e2e_seconds{kind}`` plus the **exemplar store** —
``MXNET_TRACE_SAMPLE`` (default ``head=8,tail=64``) keeps the first
``head`` traces per kind and the ``tail`` slowest by e2e latency, so a
p99 outlier can be pulled from ``telemetry.snapshot()["trace"]`` and
read stage by stage.

Disabled (``MXNET_TRACE`` unset, the default) :func:`start` is one
module-global check returning the shared :data:`NULL_TRACE`, whose
methods are allocation-free no-ops — the same < 1 µs bar as a disabled
telemetry span / chaos failpoint (test-asserted).
"""
from __future__ import annotations

import itertools
import os
import threading
import time

from .. import profiler as _profiler

_enabled = False
_tls = threading.local()
_seq = itertools.count(1)

# filled in by telemetry/__init__ (shared histogram families)
_stage_hist = None
_e2e_hist = None


def enable():
    """Arm the trace context machinery for this process."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled():
    return _enabled


class _NullStage:
    """Shared no-op stage for the disabled path (nothing allocated)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_STAGE = _NullStage()


class _NullTrace:
    """Shared no-op trace: every call site records unconditionally and
    pays one attribute lookup + call when tracing is off."""

    __slots__ = ()
    trace_id = None
    kind = None
    t0 = 0.0

    def stage(self, name):
        return _NULL_STAGE

    def add_stage(self, name, t0, t1):
        pass

    def event(self, name, **fields):
        pass

    def finish(self, status="ok"):
        pass

    def finished(self):
        return True


NULL_TRACE = _NullTrace()


class _Stage:
    __slots__ = ("_trace", "_name", "_t0")

    def __init__(self, trace, name):
        self._trace = trace
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._trace.add_stage(self._name, self._t0, time.perf_counter())
        return False


class Trace:
    """One traced unit of work.  Thread-safe: a serving request's stages
    are recorded from the submit, stage and dispatch threads in turn."""

    __slots__ = ("trace_id", "kind", "name", "t0", "t_wall", "t_end",
                 "status", "stages", "events", "_lock")

    def __init__(self, kind, name=""):
        self.trace_id = f"{os.getpid():x}-{next(_seq):08d}"
        self.kind = str(kind)
        self.name = str(name)
        self.t0 = time.perf_counter()
        self.t_wall = time.time()
        self.t_end = None
        self.status = None
        self.stages = []   # (name, t0, t1) absolute perf_counter times
        self.events = []   # (t, name, fields)
        self._lock = threading.Lock()

    # -- recording -----------------------------------------------------------
    def stage(self, name):
        """Context manager recording one named stage interval."""
        return _Stage(self, name)

    def add_stage(self, name, t0, t1):
        """Record a stage from externally-measured endpoints (the queue
        wait is timed by whoever *claims* the request, not by a context
        manager the waiting thread could hold open)."""
        with self._lock:
            self.stages.append((name, float(t0), float(t1)))
        dur = max(0.0, t1 - t0)
        if _stage_hist is not None:
            _stage_hist.observe(dur, labels={"kind": self.kind,
                                             "stage": name})
        _profiler.record_op(f"trace/{self.kind}/{name}", dur * 1e6,
                            cat="span")

    def event(self, name, **fields):
        """Record a point event (admission verdict, spill hop, shed)."""
        with self._lock:
            self.events.append((time.perf_counter(), str(name),
                                {k: _native(v) for k, v in fields.items()}))

    def finish(self, status="ok"):
        """Close the trace (idempotent, first writer wins) and hand it
        to the exemplar store + e2e histogram."""
        with self._lock:
            if self.t_end is not None:
                return
            self.t_end = time.perf_counter()
            self.status = str(status)
        if _e2e_hist is not None:
            _e2e_hist.observe(self.e2e_s(), labels={"kind": self.kind})
        _EXEMPLARS.add(self)

    def finished(self):
        with self._lock:
            return self.t_end is not None

    # -- decomposition -------------------------------------------------------
    def e2e_s(self):
        with self._lock:
            end = self.t_end
        if end is None:
            end = time.perf_counter()
        return max(0.0, end - self.t0)

    def stage_total_s(self):
        with self._lock:
            return sum(max(0.0, t1 - t0) for _n, t0, t1 in self.stages)

    def coverage(self):
        """Fraction of the end-to-end latency the stage spans account
        for (>= 0.95 is the acceptance bar for a served request; small
        overlaps at hand-off points can push it past 1.0)."""
        e2e = self.e2e_s()
        return self.stage_total_s() / e2e if e2e > 0 else 1.0

    def to_dict(self):
        with self._lock:
            stages = [{"stage": n, "start_ms": round((t0 - self.t0) * 1e3, 4),
                       "dur_ms": round(max(0.0, t1 - t0) * 1e3, 4)}
                      for n, t0, t1 in self.stages]
            events = [{"t_ms": round((t - self.t0) * 1e3, 4),
                       "event": n, **f} for t, n, f in self.events]
            e2e = ((self.t_end - self.t0) * 1e3
                   if self.t_end is not None else None)
            status = self.status
        return {"trace_id": self.trace_id, "kind": self.kind,
                "name": self.name, "time": self.t_wall,
                "status": status,
                "e2e_ms": round(e2e, 4) if e2e is not None else None,
                "stage_total_ms": round(self.stage_total_s() * 1e3, 4),
                "coverage": round(self.coverage(), 4),
                "stages": stages, "events": events}


def _native(v):
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    item = getattr(v, "item", None)
    if callable(item) and getattr(v, "ndim", 1) == 0:
        try:
            return item()
        except Exception:  # graftlint: disable=swallowed-error -- best-effort coercion; the str fallback below always works
            pass
    return str(v)


# -- exemplar store -----------------------------------------------------------
def _sample_policy():
    from .. import config as _config
    head, tail = 8, 64
    for part in str(_config.get("MXNET_TRACE_SAMPLE")).split(","):
        part = part.strip()
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        if k.strip() == "head":
            head = max(0, int(v))
        elif k.strip() == "tail":
            tail = max(0, int(v))
    return head, tail


class _ExemplarStore:
    """Head+tail sampling per trace kind: the first ``head`` traces
    (startup behaviour: cold compiles, first windows) plus the ``tail``
    slowest by e2e (the outliers a p99 decomposition needs)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._kinds = {}   # kind -> {"head": [], "slow": [(e2e, seq, dict)]}
        self._policy = None

    def add(self, trace):
        doc = trace.to_dict()
        e2e = doc["e2e_ms"] or 0.0
        with self._lock:
            if self._policy is None:
                self._policy = _sample_policy()
            head_n, tail_n = self._policy
            k = self._kinds.setdefault(
                trace.kind, {"count": 0, "head": [], "slow": [],
                             "last": None})
            k["count"] += 1
            k["last"] = doc
            if len(k["head"]) < head_n:
                k["head"].append(doc)
            elif tail_n:
                slow = k["slow"]
                slow.append((e2e, doc))
                if len(slow) > tail_n:
                    slow.sort(key=lambda t: t[0])
                    del slow[0: len(slow) - tail_n]

    def snapshot(self):
        with self._lock:
            out = {}
            for kind, k in sorted(self._kinds.items()):
                out[kind] = {
                    "count": k["count"],
                    "last": k["last"],
                    "head": list(k["head"]),
                    "slowest": [d for _e, d in
                                sorted(k["slow"], key=lambda t: -t[0])],
                }
            return out

    def reset(self):
        with self._lock:
            self._kinds.clear()
            self._policy = None


_EXEMPLARS = _ExemplarStore()


def exemplars():
    """{kind: {count, last, head[], slowest[]}} of finished traces —
    the payload behind ``telemetry.snapshot()["trace"]``."""
    return _EXEMPLARS.snapshot()


def reset_exemplars():
    _EXEMPLARS.reset()


# -- entry points -------------------------------------------------------------
def start(kind, name=""):
    """Mint a trace (or the shared no-op when tracing is disabled)."""
    if not _enabled:
        return NULL_TRACE
    return Trace(kind, name)


def current():
    """The thread's ambient trace (train windows propagate through the
    fit thread; serving traces ride the request object instead)."""
    tr = getattr(_tls, "trace", None)
    return tr if tr is not None else NULL_TRACE


def set_current(trace):
    """Install (or clear, with None) this thread's ambient trace."""
    _tls.trace = trace
