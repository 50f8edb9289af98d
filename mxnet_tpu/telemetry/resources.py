"""Resource accounting plane: device-buffer ledger + host sampler
(ISSUE 13 tentpole, half one).

Everything before this PR measured *work* (dispatches, latencies,
traces); nothing measured *footprint*.  A leaking fit loop, an executor
cache pinning a retired version's buffers, or a checkpoint directory
quietly filling a disk all presented identically: fine until OOM.  Two
instruments close that gap:

* **device-buffer ledger** (:data:`LEDGER`) — subsystems that own
  long-lived device buffers register their byte footprint by
  ``(owner, kind)``: the fused / scanned / mesh train steps account
  their params / optimizer-state / aux / residual carry at every
  (re)build, the serving executor cache accounts each entry at insert
  and decrements at evict, and AOT warmup records per-model compiled
  HBM estimates via ``compiled.memory_analysis()`` where jax exposes
  it.  All byte math is host shape arithmetic (``shape`` x
  ``dtype.itemsize``) — never a device sync.
* **host sampler** (:func:`start` / :func:`sample_now`) — a daemon
  thread (``MXNET_RESOURCE_SAMPLE_S``) samples RSS, open fds, thread
  count and registered checkpoint-dir disk usage into a bounded
  sliding window, and a least-squares estimator over that window
  (:func:`slope_bytes_per_s`) turns the RSS series into a *leak slope*
  — the signal the alert engine's ``rss_slope`` rule and the soak
  harness gate on (docs/observability.md resource catalog).

Export: one ``resources`` telemetry collector feeding
``snapshot()["resources"]``, the ``mxnet_resource_*`` Prometheus
families, and — because collector samples ride
``MetricsRegistry.sample_families()`` — the PR-12 fleet push, so the
leader's ``/fleet.json`` carries every rank's footprint.
"""
from __future__ import annotations

import collections
import logging
import os
import threading
import time

import numpy as np

log = logging.getLogger("mxnet_tpu.telemetry.resources")

_PAGE_SIZE = 4096
try:
    _PAGE_SIZE = os.sysconf("SC_PAGE_SIZE")
except (ValueError, OSError, AttributeError):
    pass


# -- byte math (host-side only, never a device sync) --------------------------
def nbytes(leaf):
    """Byte footprint of one array-like leaf from shape metadata alone;
    0 for leaves without (shape, dtype)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    except (TypeError, ValueError):
        return 0


def pytree_nbytes(tree):
    """Total byte footprint of a nested structure of array-like leaves
    (dicts / lists / tuples walked; NDArray-style ``._data`` unwrapped)."""
    if tree is None:
        return 0
    if isinstance(tree, dict):
        return sum(pytree_nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(pytree_nbytes(v) for v in tree)
    inner = getattr(tree, "_data", None)
    if inner is not None and nbytes(tree) == 0:
        return nbytes(inner)
    return nbytes(tree)


# -- device-buffer ledger ------------------------------------------------------
class DeviceLedger:
    """Registered long-lived device-buffer footprints by (owner, kind).

    ``set`` replaces (a train-step rebuild re-states its whole
    footprint), ``add`` accumulates (executor-cache inserts), and
    ``release`` decrements with a floor at zero (evictions must never
    drive a footprint negative even if an entry was never accounted —
    the ledger is an estimator, not an allocator).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}   # (owner, kind) -> bytes
        self._hbm = {}       # owner -> {section: bytes} (compiled estimates)

    def set(self, owner, kind, n):
        with self._lock:
            self._entries[(str(owner), str(kind))] = max(0, int(n))

    def add(self, owner, kind, n):
        key = (str(owner), str(kind))
        with self._lock:
            self._entries[key] = max(0, self._entries.get(key, 0) + int(n))

    def release(self, owner, kind, n):
        self.add(owner, kind, -int(n))

    def clear(self, owner=None):
        with self._lock:
            if owner is None:
                self._entries.clear()
                self._hbm.clear()
            else:
                owner = str(owner)
                for key in [k for k in self._entries if k[0] == owner]:
                    del self._entries[key]
                self._hbm.pop(owner, None)

    def note_hbm_estimate(self, owner, sections):
        """Record a compiled program's HBM estimate for ``owner`` —
        ``sections`` is a {section: bytes} dict (arguments / outputs /
        temp / code / total)."""
        clean = {str(k): int(v) for k, v in sections.items()
                 if isinstance(v, (int, float)) and v >= 0}
        if not clean:
            return
        with self._lock:
            self._hbm[str(owner)] = clean

    def total(self):
        with self._lock:
            return sum(self._entries.values())

    def snapshot(self):
        with self._lock:
            owners = {}
            for (owner, kind), n in sorted(self._entries.items()):
                owners.setdefault(owner, {})[kind] = n
            return {"total_bytes": sum(self._entries.values()),
                    "owners": owners,
                    "hbm_estimates": {o: dict(s)
                                      for o, s in sorted(self._hbm.items())}}

    def samples(self):
        with self._lock:
            entries = dict(self._entries)
            hbm = {o: dict(s) for o, s in self._hbm.items()}
        out = [("mxnet_resource_device_total_bytes", "gauge",
                "total registered long-lived device-buffer bytes",
                {}, sum(entries.values()))]
        for (owner, kind), n in sorted(entries.items()):
            out.append(("mxnet_resource_device_bytes", "gauge",
                        "registered device-buffer bytes, by owner and kind",
                        {"owner": owner, "kind": kind}, n))
        for owner, sections in sorted(hbm.items()):
            for section, n in sorted(sections.items()):
                out.append(("mxnet_resource_hbm_estimate_bytes", "gauge",
                            "compiled-program HBM estimate "
                            "(compiled.memory_analysis), by owner/section",
                            {"owner": owner, "section": section}, n))
        return out


LEDGER = DeviceLedger()


def account_train_step(owner, params=(), opt_state=None, aux=(),
                       extra=None):
    """One train-step (re)build states its whole carry footprint:
    params / optimizer state / aux stats, plus any step-specific
    ``extra`` {kind: bytes} (mesh gradient buckets, codec residuals).
    Called at build time only — never on the per-step hot path."""
    LEDGER.set(owner, "params", pytree_nbytes(list(params)))
    LEDGER.set(owner, "opt_state", pytree_nbytes(opt_state))
    LEDGER.set(owner, "aux", pytree_nbytes(list(aux)))
    for kind, n in (extra or {}).items():
        LEDGER.set(owner, kind, n)


def note_compiled(owner, compiled):
    """Record a compiled executable's HBM estimate where jax exposes
    ``memory_analysis()`` (AOT warmup calls this per warmed model);
    silently a no-op on backends/versions that do not."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001 — optional introspection; absence is normal on some backends
        log.debug("memory_analysis unavailable for %s: %s", owner, e)
        return None
    sections = {}
    for section, attr in (("arguments", "argument_size_in_bytes"),
                          ("outputs", "output_size_in_bytes"),
                          ("temp", "temp_size_in_bytes"),
                          ("code", "generated_code_size_in_bytes"),
                          ("alias", "alias_size_in_bytes")):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)) and v >= 0:
            sections[section] = int(v)
    if sections:
        sections["total"] = sum(v for k, v in sections.items()
                                if k != "alias")
        LEDGER.note_hbm_estimate(owner, sections)
    return sections or None


# -- host sampler --------------------------------------------------------------
def read_rss_bytes():
    """Current resident set size.  /proc on Linux; best-effort (peak
    RSS via getrusage) elsewhere — the slope estimator only needs a
    consistent series, and 0 simply disables the leak signal."""
    try:
        with open("/proc/self/statm", "rb") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    except Exception:  # graftlint: disable=swallowed-error -- best-effort sampling; 0 disables the leak signal cleanly
        return 0


def read_open_fds():
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return 0


def dir_bytes(path):
    """Recursive byte usage of a directory (best-effort; races with
    concurrent GC/commits are fine — this is a trend signal)."""
    total = 0
    try:
        for dirpath, _dirnames, filenames in os.walk(path):
            for fn in filenames:
                try:
                    total += os.path.getsize(os.path.join(dirpath, fn))
                except OSError:
                    continue
    except OSError:
        return 0
    return total


def slope_bytes_per_s(points):
    """Least-squares slope of an ``[(t_seconds, bytes), ...]`` series —
    the leak estimator.  Returns 0.0 for fewer than 3 points or a
    degenerate (zero-span) time axis, so startup noise never fabricates
    a leak."""
    if len(points) < 3:
        return 0.0
    ts = np.asarray([p[0] for p in points], np.float64)
    ys = np.asarray([p[1] for p in points], np.float64)
    ts = ts - ts[0]
    span = float(ts[-1])
    if span <= 0:
        return 0.0
    t_mean = ts.mean()
    denom = float(((ts - t_mean) ** 2).sum())
    if denom <= 0:
        return 0.0
    return float(((ts - t_mean) * (ys - ys.mean())).sum() / denom)


class HostSampler:
    """Sliding-window host resource sampler.  ``sample_now()`` is also
    callable directly (the collector takes one on-demand sample when no
    thread is running)."""

    def __init__(self, window=240):
        self._lock = threading.Lock()
        self._window = collections.deque(maxlen=max(8, int(window)))
        self._thread = None
        self._stop = None
        self._samples = 0
        self.interval_s = 0.0

    def _ckpt_dirs(self):
        from . import _ckpt_managers
        dirs = []
        for mgr in list(_ckpt_managers):
            d = getattr(mgr, "directory", None)
            if d:
                dirs.append(str(d))
        return sorted(set(dirs))

    def sample_now(self, rss=None, t=None, disk=True):
        """Take one sample (synthetic ``rss``/``t`` overrides keep the
        leak-slope tests deterministic); returns the sample dict."""
        entry = {
            "t": time.monotonic() if t is None else float(t),
            "rss_bytes": read_rss_bytes() if rss is None else int(rss),
            "open_fds": read_open_fds(),
            "threads": threading.active_count(),
            "ckpt_disk_bytes": {},
        }
        if disk:
            for d in self._ckpt_dirs():
                entry["ckpt_disk_bytes"][d] = dir_bytes(d)
        with self._lock:
            self._window.append(entry)
            self._samples += 1
        return entry

    def leak_slope(self):
        """RSS leak slope (bytes/s) over the current window."""
        with self._lock:
            pts = [(e["t"], e["rss_bytes"]) for e in self._window
                   if e["rss_bytes"] > 0]
        return slope_bytes_per_s(pts)

    def last(self):
        with self._lock:
            return dict(self._window[-1]) if self._window else None

    def reset(self):
        with self._lock:
            self._window.clear()
            self._samples = 0

    def running(self):
        with self._lock:
            return self._thread is not None and self._thread.is_alive()

    def start(self, interval_s):
        """Start (or retune) the sampling thread; 0 stops it."""
        interval_s = float(interval_s)
        if interval_s <= 0:
            self.stop()
            return
        with self._lock:
            self.interval_s = interval_s
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="mx-resource-sampler")
            self._thread.start()

    def stop(self):
        with self._lock:
            stop, self._stop = self._stop, None
            thread, self._thread = self._thread, None
        if stop is not None:
            stop.set()
        if thread is not None:
            thread.join(timeout=5)

    def _loop(self):
        while True:
            with self._lock:
                stop = self._stop
                interval = self.interval_s
            if stop is None or stop.wait(max(0.01, interval)):
                return
            try:
                self.sample_now()
            except Exception as e:  # noqa: BLE001 — one failed sample must not kill the sampler
                log.debug("resource sample failed: %s", e)


SAMPLER = HostSampler()


def start(interval_s=None):
    """Arm the host sampler (MXNET_RESOURCE_SAMPLE_S default)."""
    if interval_s is None:
        from .. import config as _config
        interval_s = float(_config.get("MXNET_RESOURCE_SAMPLE_S"))
    SAMPLER.start(interval_s)
    return SAMPLER.running()


def stop():
    SAMPLER.stop()


def sample_now(**kw):
    return SAMPLER.sample_now(**kw)


def leak_slope():
    return SAMPLER.leak_slope()


# -- telemetry collector hooks -------------------------------------------------
def _collector_snapshot():
    last = SAMPLER.last()
    if last is None:
        # no sampler thread and nobody sampled yet: one on-demand
        # sample keeps /snapshot.json meaningful on any process (no
        # history -> slope reads 0, never a fabricated leak)
        last = SAMPLER.sample_now()
    out = {"device": LEDGER.snapshot(),
           "host": dict(last),
           "rss_slope_bytes_per_s": SAMPLER.leak_slope(),
           "sampler_running": SAMPLER.running(),
           "samples": SAMPLER._samples}
    return out


def _collector_samples():
    out = list(LEDGER.samples())
    last = SAMPLER.last() or SAMPLER.sample_now()
    out.append(("mxnet_resource_rss_bytes", "gauge",
                "resident set size at the last host sample", {},
                last["rss_bytes"]))
    out.append(("mxnet_resource_open_fds", "gauge",
                "open file descriptors at the last host sample", {},
                last["open_fds"]))
    out.append(("mxnet_resource_threads", "gauge",
                "live threads at the last host sample", {},
                last["threads"]))
    for d, n in sorted(last.get("ckpt_disk_bytes", {}).items()):
        out.append(("mxnet_resource_ckpt_disk_bytes", "gauge",
                    "disk bytes under each registered checkpoint "
                    "directory", {"directory": d}, n))
    out.append(("mxnet_resource_rss_slope_bytes_per_s", "gauge",
                "least-squares RSS slope over the sampler window "
                "(the leak estimator)", {}, SAMPLER.leak_slope()))
    out.append(("mxnet_resource_samples_total", "counter",
                "host resource samples taken", {}, SAMPLER._samples))
    return out
