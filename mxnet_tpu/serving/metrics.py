"""Serving observability: counters, gauges, latency percentiles.

Every ``ServingMetrics`` instance keeps its own thread-safe counters and
a bounded latency reservoir, mirrors every update into the profiler's
chrome-trace counter lanes (``profiler.record_counter``) so a running
``mx.profiler`` trace shows serving queue depth / throughput next to the
op timeline, and renders a ``snapshot()`` dict — the payload behind
``mx.serving.stats()``.
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

from .. import profiler as _profiler

# latency samples kept per metrics instance; percentile error from
# windowing is irrelevant at serving timescales and the bound keeps
# snapshot() O(window) regardless of uptime
_LATENCY_WINDOW = 4096

# all live metrics instances, for the module-level serving.stats()
_REGISTRY = weakref.WeakValueDictionary()
_REGISTRY_LOCK = threading.Lock()


def _percentile(sorted_vals, q):
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class ServingMetrics:
    """Counters + latency reservoir for one server / batcher."""

    def __init__(self, name="serving"):
        self.name = name
        self._lock = threading.Lock()
        self._counters = collections.Counter()
        self._gauges = {}
        self._latencies_ms = collections.deque(maxlen=_LATENCY_WINDOW)
        self._reservoirs = {}   # name -> bounded deque (observe())
        self._batch_items = 0
        self._batch_slots = 0
        self._t_start = time.perf_counter()
        with _REGISTRY_LOCK:
            # last writer wins on a name collision (e.g. test reruns)
            _REGISTRY[name] = self

    # -- updates ------------------------------------------------------------
    def incr(self, key, n=1):
        with self._lock:
            self._counters[key] += n
            value = self._counters[key]
        _profiler.record_counter(f"serving:{self.name}:{key}", value)

    def gauge(self, key, value):
        with self._lock:
            self._gauges[key] = value
        _profiler.record_counter(f"serving:{self.name}:{key}", value)

    def get(self, key):
        with self._lock:
            return self._counters.get(key, self._gauges.get(key, 0))

    def observe_latency(self, ms):
        with self._lock:
            self._latencies_ms.append(float(ms))

    def observe(self, key, value):
        """Named bounded reservoir alongside the request-latency one —
        e.g. the generation engine's per-token ``intertoken_ms`` gaps;
        ``snapshot()`` renders p50/p90/p99 per key (ISSUE 16)."""
        with self._lock:
            res = self._reservoirs.get(key)
            if res is None:
                res = self._reservoirs[key] = collections.deque(
                    maxlen=_LATENCY_WINDOW)
            res.append(float(value))

    def drain_observations(self, key):
        """Return AND clear one named reservoir (windowed percentile
        measurement)."""
        with self._lock:
            res = self._reservoirs.get(key)
            out = list(res) if res else []
            if res:
                res.clear()
        return out

    def observe_batch(self, n_real, n_slots):
        """One executed batch: ``n_real`` live requests in ``n_slots``
        padded slots (batch-occupancy accounting)."""
        with self._lock:
            self._counters["batches_total"] += 1
            self._batch_items += int(n_real)
            self._batch_slots += int(n_slots)
            occ = self._batch_items / max(1, self._batch_slots)
        _profiler.record_counter(
            f"serving:{self.name}:batch_occupancy", round(occ, 4))

    # -- snapshot -----------------------------------------------------------
    def snapshot(self):
        # copy the percentile reservoir UNDER the lock, sort OUTSIDE it:
        # a concurrent submit()/observe_latency() can never mutate the
        # sequence mid-sort, and the batcher's hot path never waits on an
        # O(n log n) sort held inside its metrics lock
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            lat = list(self._latencies_ms)
            reservoirs = {k: list(v)
                          for k, v in self._reservoirs.items() if v}
            items, slots = self._batch_items, self._batch_slots
            elapsed = max(1e-9, time.perf_counter() - self._t_start)
        lat.sort()
        responses = counters.get("responses_total", 0)
        snap = {
            "name": self.name,
            "uptime_s": round(elapsed, 3),
            "throughput_rps": round(responses / elapsed, 3),
            "latency_ms": {
                "p50": _percentile(lat, 50),
                "p90": _percentile(lat, 90),
                "p99": _percentile(lat, 99),
                "samples": len(lat),
            },
            "batch_occupancy": round(items / slots, 4) if slots else None,
        }
        for key, vals in sorted(reservoirs.items()):
            vals.sort()
            snap[key] = {"p50": _percentile(vals, 50),
                         "p90": _percentile(vals, 90),
                         "p99": _percentile(vals, 99),
                         "samples": len(vals)}
        snap.update(counters)
        snap.update(gauges)
        return snap


def stats():
    """Snapshot of every live metrics instance, keyed by name — the
    module-level ``mx.serving.stats()`` entry point.  This same payload
    feeds ``telemetry.snapshot()["serving"]`` and the Prometheus
    ``mxnet_serving_*`` families: once this module is imported, the
    telemetry registry's ``serving`` collector pulls from here, so the
    dict shape below IS the cross-subsystem contract."""
    with _REGISTRY_LOCK:
        instances = list(_REGISTRY.values())
    return {m.name: m.snapshot() for m in instances}
