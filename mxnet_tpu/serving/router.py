"""ReplicaPool: load-aware routing + SLO admission over batcher replicas.

PR 1's serving stack was ONE ``DynamicBatcher`` per (model, signature)
on one host — throughput capped by a single worker loop, tail latency
queue-depth-bound.  The pool puts a router in front of K batcher
replicas (one per device, or K per device for intra-device concurrency:
Opara's stream-concurrency argument, PAPERS.md, maps to running
independent micro-batches concurrently while the shared executor lock
serializes only the device program itself):

* **load-aware routing** — each submit goes to the replica with the
  smallest *predicted drain time*: ``occupancy()`` (queued + staged +
  executing requests) x the pool's per-request service-time EWMA.  Ties
  break by replica id, so an idle pool round-robins trivially.
* **graceful spill** — a replica that sheds (``ServingOverloadError``),
  is draining (``ServingClosedError``), has failed fast
  (``ServingWorkerError(exhausted=True)``) or takes an injected
  dispatch fault spills the request to the next-least-loaded sibling;
  ``mxnet_serving_router_spill_total{model}`` counts every rescued hop.
  Only when EVERY replica refuses does the pool re-raise.  A malformed
  request (validator rejection) still fails alone — never spilled.
* **SLO admission control** — ``slo_p99_ms`` (or
  ``MXNET_SERVING_SLO_P99_MS``) sheds on *predicted* p99: the
  service-rate EWMA (sampled from the same metrics the telemetry
  registry exports) x pool occupancy, per model — so the shed
  watermark self-tunes to the model's measured service rate instead of
  a hand-picked queue depth.  Excess traffic fails as typed
  ``ServingOverloadError`` carrying ``predicted_p99_ms``/``slo_ms``.
* **drain-on-removal** — ``remove_replica`` stops intake on that
  replica and drains everything it admitted before returning; requests
  are never dropped by a scale-down or a kill (chaos scenario
  ``replica_kill_mid_burst``).

Telemetry: ``mxnet_serving_replica_occupancy{model,replica}``,
``mxnet_serving_router_spill_total{model}`` and
``mxnet_serving_predicted_p99_ms{model}`` export through the process
registry (docs/observability.md).
"""
from __future__ import annotations

import threading
import time

from ..base import MXNetError
from ..chaos.failpoints import ChaosInjectedError
from ..chaos.failpoints import failpoint as _failpoint
from ..telemetry import flight as _flight
from ..telemetry import trace as _trace
from .batcher import (DynamicBatcher, ServingClosedError,
                      ServingOverloadError, ServingWorkerError)
from .metrics import ServingMetrics


def _registry():
    from .. import telemetry as _telemetry
    return _telemetry.REGISTRY


def _occupancy_gauge():
    return _registry().gauge(
        "mxnet_serving_replica_occupancy",
        "requests owned by each serving replica (queued + staged + "
        "executing), sampled at every routing decision")


def _spill_counter():
    return _registry().counter(
        "mxnet_serving_router_spill_total",
        "requests the router re-routed to a sibling replica after the "
        "chosen replica shed, drained, failed fast, or took an injected "
        "dispatch fault")


def _predicted_p99_gauge():
    return _registry().gauge(
        "mxnet_serving_predicted_p99_ms",
        "the admission controller's predicted p99 (pool occupancy / "
        "service-rate EWMA) at the last admission decision, per model; "
        "requests are shed as ServingOverloadError once this crosses "
        "the MXNET_SERVING_SLO_P99_MS SLO")


class AdmissionController:
    """Predicted-p99 SLO admission for one pool (one model).

    The predictor is deliberately simple and self-tuning: a new request
    admitted behind ``occupancy`` in-flight requests waits roughly
    ``occupancy / service_rate`` — the time the pool needs to drain
    everything ahead of it.  ``service_rate`` (responses/s) is an EWMA
    sampled from the pool's response counter, so a slower model (or a
    degraded pool) AUTOMATICALLY lowers the depth at which shedding
    starts; no hand-tuned watermark tracks the model's speed.
    Prediction leads measurement: the request that WOULD have blown the
    p99 is shed before it queues, which is what keeps the spike p99
    bounded.
    """

    # ignore samples shorter than this (rate estimates from sub-20ms
    # windows are dominated by scheduler jitter)
    MIN_SAMPLE_S = 0.02

    def __init__(self, name, slo_p99_ms=None, alpha=None):
        from .. import config as _config
        self.name = name
        self.slo_p99_ms = float(
            slo_p99_ms if slo_p99_ms is not None
            else _config.get("MXNET_SERVING_SLO_P99_MS"))
        self.alpha = float(alpha if alpha is not None
                           else _config.get("MXNET_SERVING_SLO_EWMA_ALPHA"))
        self._lock = threading.Lock()
        self._rate_ewma = None   # responses / s
        self._last = None        # (responses_total, perf_counter)

    def reset(self):
        """Forget the learned service rate (hot-swap rebuild: a new
        model version re-learns its own rate before shedding on it)."""
        with self._lock:
            self._rate_ewma = None
            self._last = None

    def observe(self, responses_total, occupancy, now=None):
        """Feed one (response counter, occupancy) sample; updates the
        service-rate EWMA.  Idle windows (no completions, nothing
        pending) only advance the sample anchor — they must not decay
        the learned rate, or every burst would start with a shed storm."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            if self._last is None:
                self._last = (responses_total, now)
                return
            r0, t0 = self._last
            dt = now - t0
            if dt < self.MIN_SAMPLE_S:
                return
            dresp = responses_total - r0
            if dresp > 0:
                inst = dresp / dt
            elif occupancy > 0:
                # work pending but nothing completed across the window:
                # the true rate is below 1/dt — decay toward it
                inst = 1.0 / dt
            else:
                self._last = (responses_total, now)
                return
            if self._rate_ewma is None:
                self._rate_ewma = inst
            else:
                self._rate_ewma = (self.alpha * inst
                                   + (1.0 - self.alpha) * self._rate_ewma)
            self._last = (responses_total, now)

    def service_rate(self):
        with self._lock:
            return self._rate_ewma

    def predicted_p99_ms(self, occupancy):
        """Predicted wait (ms) for a request admitted NOW behind
        ``occupancy`` pending requests; None while the rate is unknown
        (cold pools admit — there is nothing to predict from)."""
        with self._lock:
            rate = self._rate_ewma
        if rate is None or rate <= 0:
            return None
        return (occupancy + 1) / rate * 1e3

    def check(self, occupancy):
        """Admission decision; returns the predicted p99 (ms) it was
        made on (None = no prediction yet).  Raises
        ``ServingOverloadError`` when the prediction breaches the SLO."""
        predicted = self.predicted_p99_ms(occupancy)
        if predicted is not None:
            _predicted_p99_gauge().set(predicted,
                                       labels={"model": self.name})
        if self.slo_p99_ms > 0 and predicted is not None \
                and predicted > self.slo_p99_ms:
            raise ServingOverloadError(
                self.name, occupancy, None,
                predicted_p99_ms=predicted, slo_ms=self.slo_p99_ms)
        return predicted


class ReplicaPool:
    """K ``DynamicBatcher`` replicas behind one load-aware router.

    ``runner_factory(replica_id)`` builds each replica's runner (the
    same callable may be shared — the executor cache already serializes
    the device program; replicas then overlap all the HOST work:
    coalescing, stacking, padding, validation, result fan-out).
    Replicas share the pool's ``ServingMetrics``, so ``stats()`` stays
    one aggregate per model endpoint.
    """

    def __init__(self, runner_factory, num_replicas=None, name="pool",
                 model=None, metrics=None, validator=None,
                 slo_p99_ms=None, **batcher_kw):
        from .. import config as _config
        n = int(num_replicas if num_replicas is not None
                else _config.get("MXNET_SERVING_REPLICAS"))
        if n <= 0:
            raise MXNetError("serving: num_replicas must be positive")
        self.name = name
        self.model = str(model if model is not None else name)
        self._runner_factory = runner_factory
        self.metrics = metrics or ServingMetrics(name)
        self._validator = validator
        self._batcher_kw = dict(batcher_kw)
        self.admission = AdmissionController(self.model,
                                             slo_p99_ms=slo_p99_ms)
        self._lock = threading.Lock()
        self._replicas = {}   # rid -> DynamicBatcher
        self._next_rid = 0
        self._closed = False
        # pool-local completion counter: the admission EWMA must see
        # THIS model's service rate even when the ServingMetrics object
        # is shared server-wide across models
        self._responses = 0
        self._route_n = 0
        for _ in range(n):
            self.add_replica()

    # -- replica lifecycle ---------------------------------------------------
    def _counted(self, runner):
        def run(feed, n_real):
            out = runner(feed, n_real)
            with self._lock:
                self._responses += n_real
            return out
        return run

    def responses(self):
        """Requests this pool completed (the admission EWMA's input)."""
        with self._lock:
            return self._responses

    def _make_replica(self, rid):
        return DynamicBatcher(
            self._counted(self._runner_factory(rid)),
            name=f"{self.name}/r{rid}", metrics=self.metrics,
            validator=self._validator, **self._batcher_kw)

    def add_replica(self):
        """Scale up by one replica; returns its id."""
        with self._lock:
            if self._closed:
                raise ServingClosedError(self.name)
            rid = self._next_rid
            self._next_rid += 1
            self._replicas[rid] = self._make_replica(rid)
        _flight.record("serving", "replica_added", model=self.model,
                       replica=rid)
        return rid

    def remove_replica(self, rid, drain=True, timeout=30.0):
        """Scale down: stop intake on replica ``rid``, drain everything
        it admitted (default), and drop it from routing.  Returns the
        closed batcher.  Requests in its queue run to completion —
        removal never drops admitted work."""
        with self._lock:
            b = self._replicas.pop(int(rid), None)
            live = sorted(self._replicas)
        if b is None:
            raise MXNetError(
                f"serving[{self.name}]: no replica {rid}; live: {live}")
        b.close(drain=drain, timeout=timeout)
        _occupancy_gauge().set(0, labels={"model": self.model,
                                          "replica": str(rid)})
        _flight.record("serving", "replica_removed", model=self.model,
                       replica=rid, drained=bool(drain))
        return b

    def resize(self, num_replicas, drain=True):
        """Grow or shrink to ``num_replicas`` (highest-id replicas are
        drained first on shrink)."""
        n = int(num_replicas)
        if n <= 0:
            raise MXNetError("serving: num_replicas must be positive")
        while len(self.replica_ids()) < n:
            self.add_replica()
        while len(self.replica_ids()) > n:
            self.remove_replica(max(self.replica_ids()), drain=drain)

    def replica_ids(self):
        with self._lock:
            return sorted(self._replicas)

    def replica(self, rid):
        with self._lock:
            return self._replicas[int(rid)]

    # how often the routing path exports the per-replica occupancy
    # gauges: every submit would double the lock traffic of a
    # fully-shedding overload loop for a metric nobody reads at that
    # granularity (a scrape sees one sample either way)
    _GAUGE_EVERY = 32

    # -- routing -------------------------------------------------------------
    def _ranked_replicas(self):
        """Live replicas ranked by predicted drain time (occupancy x
        the shared service-time EWMA — with one EWMA per pool the rank
        reduces to occupancy, ties broken by id), periodically exporting
        the occupancy gauges as a side effect."""
        with self._lock:
            replicas = sorted(self._replicas.items())
            self._route_n += 1
            export = self._route_n % self._GAUGE_EVERY == 1
        ranked = []
        gauge = _occupancy_gauge() if export else None
        for rid, b in replicas:
            occ = b.occupancy()
            if gauge is not None:
                gauge.set(occ, labels={"model": self.model,
                                       "replica": str(rid)})
            ranked.append((occ, rid, b))
        ranked.sort(key=lambda t: (t[0], t[1]))
        return ranked

    def submit(self, inputs, timeout_ms=None, trace=None):
        """Route one request: SLO admission, then least-predicted-drain
        replica, spilling to siblings on shed/drain/failure.  Raises
        ``ServingOverloadError`` (typed, synchronous) when admission
        predicts an SLO breach or every replica sheds.

        ``trace`` (an end-to-end trace context) survives spill hops:
        the SAME context rides the resubmission to each sibling, so a
        request that sheds, spills and resolves elsewhere is still ONE
        trace with its hops recorded as events."""
        tr = trace if trace is not None else _trace.NULL_TRACE
        ranked = self._ranked_replicas()
        if not ranked:
            self.metrics.incr("rejected_total")
            raise ServingClosedError(self.name)
        total_occ = sum(occ for occ, _rid, _b in ranked)
        self.admission.observe(self.responses(), total_occ)
        try:
            predicted = self.admission.check(total_occ)
        except ServingOverloadError as e:
            self.metrics.incr("shed_total")
            self.metrics.incr("slo_shed_total")
            tr.event("admission", verdict="shed",
                     predicted_p99_ms=e.predicted_p99_ms,
                     slo_ms=e.slo_ms)
            tr.finish(status="shed")
            _flight.record("serving", "slo_shed", severity="warn",
                           model=self.model, occupancy=total_occ,
                           predicted_p99_ms=e.predicted_p99_ms)
            raise
        tr.event("admission", verdict="admit", occupancy=total_occ,
                 predicted_p99_ms=predicted)
        last_exc = None
        for hop, (_occ, rid, b) in enumerate(ranked):
            if b.failed:
                last_exc = ServingWorkerError(b.name, exhausted=True)
                continue
            try:
                _failpoint("serving/router/dispatch")
                tr.event("route", replica=rid, hop=hop)
                fut = b.submit(inputs, timeout_ms=timeout_ms, trace=tr)
            except (ServingOverloadError, ServingClosedError,
                    ServingWorkerError, ChaosInjectedError) as e:
                # shed / draining / failed-fast / injected dispatch
                # fault: spill to the next-least-loaded sibling.  Any
                # other error (validator rejection, malformed inputs)
                # is about THIS request and propagates — a bad request
                # fails alone, it is never spilled K times
                tr.event("spill", replica=rid, hop=hop,
                         cause=type(e).__name__)
                last_exc = e
                continue
            if hop > 0:
                self.metrics.incr("spill_total", hop)
                _spill_counter().inc(hop, labels={"model": self.model})
                _flight.record("serving", "spill", severity="warn",
                               model=self.model, hops=hop, replica=rid)
            return fut
        tr.event("refused", hops=len(ranked),
                 cause=type(last_exc).__name__)
        tr.finish(status="refused")
        raise last_exc  # every replica refused (all typed errors)

    # -- observability / lifecycle -------------------------------------------
    def stats(self):
        with self._lock:
            replicas = sorted(self._replicas.items())
        occ = {rid: b.occupancy() for rid, b in replicas}
        return {
            "replicas": len(replicas),
            "replica_ids": [rid for rid, _ in replicas],
            "occupancy": occ,
            "failed_replicas": [rid for rid, b in replicas if b.failed],
            "service_rate_rps": self.admission.service_rate(),
            "predicted_p99_ms":
                self.admission.predicted_p99_ms(sum(occ.values())),
            "slo_p99_ms": self.admission.slo_p99_ms,
        }

    def close(self, drain=True, timeout=30.0):
        """Stop intake pool-wide and drain (default) every replica."""
        with self._lock:
            self._closed = True
            replicas = list(self._replicas.items())
            self._replicas.clear()
        for rid, b in replicas:
            b.close(drain=drain, timeout=timeout)
            _occupancy_gauge().set(0, labels={"model": self.model,
                                              "replica": str(rid)})

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
