"""Per-train-step time breakdown: where did the 2.7 ms go?

``Module.fit`` opens a :class:`StepTimer` per fit call; each loop
iteration attributes its wall time to named *lanes*:

* ``data_wait``     — blocking in ``next(data_iter)``
* ``h2d_stage``     — host->device staging of the next batch (io.stage_batch)
* ``step_dispatch`` — host time dispatching forward/backward/update
                      (the fused jit call included)
* ``comm_collective`` — gradient-synchronization time: the wall time of
                      the residual per-param kvstore push/pull loop, or
                      the calibrated standalone cost of the mesh fused
                      step's bucketed collectives (reattributed out of
                      ``step_dispatch`` so the lane sum stays exact)
* ``device_block``  — waiting for device results before metric math
                      (the sync the metric flush forces)
* ``metric_flush``  — host-side metric math after arrays landed
* ``ckpt_block``    — checkpoint snapshot time charged to the train thread

Anything unattributed lands in ``other`` (loop bookkeeping, callbacks) —
``step_breakdown()`` reports it explicitly so the lanes are auditable
against wall time (the acceptance bar: named lanes >= 90% of step wall).

Deep call sites (``update_metric``, ``CheckpointManager.save``) find the
fit loop's timer through a thread-local (``current_step_timer()``), so
the attribution needs no plumbing through the Module API.  When
telemetry is disabled the fit loop gets the shared ``_NULL_TIMER`` whose
lanes are no-op context managers.

A lane holds no clock of its own: ``StepTimer.lane(name)`` opens the
span ``fit/lane/<name>`` (spans.py), and ``begin_step``/``end_step``
advance the thread's step id that every span record carries.
"""
from __future__ import annotations

import threading
import time

from . import spans as _spans

LANES = ("data_wait", "h2d_stage", "step_dispatch", "comm_collective",
         "device_block", "metric_flush", "ckpt_block")

_tls = threading.local()
_agg_lock = threading.Lock()
_agg = {"steps": 0, "wall_s": 0.0,
        "lanes": {lane: 0.0 for lane in LANES}, "other_s": 0.0,
        "last": {}}

# filled in by telemetry/__init__
_lane_hist = None
_step_hist = None


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class _NullStepTimer:
    """Disabled-telemetry stand-in: every call is a cheap no-op."""

    __slots__ = ()
    active = False

    def lane(self, name):
        return _NULL_CTX

    def add(self, name, seconds):
        pass

    def begin_step(self):
        pass

    def end_step(self, steps=1):
        pass

    def close(self):
        pass


_NULL_TIMER = _NullStepTimer()


class StepTimer:
    """Accumulates one fit loop's lane times; folds them into the global
    breakdown (and the registry histograms) at every ``end_step``."""

    active = True

    def __init__(self):
        self._cur = {}
        self._step_start = None
        self._prev = getattr(_tls, "timer", None)
        _tls.timer = self

    def lane(self, name):
        """A span named ``fit/lane/<name>`` whose duration is added to
        the lane when it closes: a lane is a span, on the same clock, in
        the same records and the same trace."""
        return _spans._Span("fit/lane/" + name, lane=(self, name))

    def add(self, name, seconds):
        self._cur[name] = self._cur.get(name, 0.0) + seconds

    def begin_step(self):
        """(Re-)anchor the step wall clock; lane time already accumulated
        (e.g. an epoch-end checkpoint) stays and folds into the next
        step rather than being dropped."""
        self._step_start = time.perf_counter()
        _spans.next_step()

    def end_step(self, steps=1):
        """Close out a timed unit covering ``steps`` train steps (1 for
        the per-batch loop; K*M for a scanned window).  Totals accumulate
        un-amortized — the lanes-vs-wall audit stays exact — while
        ``last`` and the histograms record PER-STEP amortized values so
        StepTimeline output and the step-seconds distribution keep
        meaning \"one train step\" at any window size."""
        now = time.perf_counter()
        _spans.next_step()
        n = max(1, int(steps))
        if self._step_start is None:
            self._step_start = now
            return
        wall = now - self._step_start
        self._step_start = now
        cur, self._cur = self._cur, {}
        lane_sum = 0.0
        with _agg_lock:
            _agg["steps"] += n
            _agg["wall_s"] += wall
            for lane, dur in cur.items():
                _agg["lanes"][lane] = _agg["lanes"].get(lane, 0.0) + dur
                lane_sum += dur
            _agg["other_s"] += max(0.0, wall - lane_sum)
            _agg["last"] = {"wall_s": wall / n,
                            "lanes": {lane: dur / n
                                      for lane, dur in cur.items()},
                            "window_steps": n}
        if _lane_hist is not None:
            for lane, dur in cur.items():
                _lane_hist.observe(dur / n, labels={"lane": lane})
        if _step_hist is not None:
            _step_hist.observe(wall / n)

    def close(self):
        _tls.timer = self._prev


def step_timer():
    """A live :class:`StepTimer` (telemetry enabled) or the shared no-op
    one; either way it becomes this thread's ``current_step_timer()``."""
    if not _spans.enabled():
        return _NULL_TIMER
    return StepTimer()


def current_step_timer():
    """The fit loop's timer on this thread (``_NULL_TIMER`` outside)."""
    return getattr(_tls, "timer", None) or _NULL_TIMER


def step_breakdown():
    """Accumulated breakdown: steps, total wall, per-lane totals, the
    unattributed remainder, and the last step's split."""
    with _agg_lock:
        return {"steps": _agg["steps"], "wall_s": _agg["wall_s"],
                "lanes": dict(_agg["lanes"]), "other_s": _agg["other_s"],
                "last": {"wall_s": _agg["last"].get("wall_s"),
                         "lanes": dict(_agg["last"].get("lanes", {})),
                         "window_steps": _agg["last"].get(
                             "window_steps", 1)}}


def reset_step_stats():
    with _agg_lock:
        _agg["steps"] = 0
        _agg["wall_s"] = 0.0
        _agg["lanes"] = {lane: 0.0 for lane in LANES}
        _agg["other_s"] = 0.0
        _agg["last"] = {}
