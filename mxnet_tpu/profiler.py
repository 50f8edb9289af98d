"""Profiler (parity: python/mxnet/profiler.py + src/profiler/profiler.h:260).

The reference writes chrome://tracing JSON from an in-engine profiler with
device/engine lanes + an aggregate stats table. TPU redesign: the heavy
lifting is jax.profiler (XLA xplane → TensorBoard/perfetto); this module
keeps the mx.profiler API surface (set_config/start/stop/dump/dumps) and
adds a lightweight host-side op-dispatch recorder producing the same
chrome-trace JSON + aggregate table the reference emits.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time

from .base import MXNetError

_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": False,
    # block after each profiled op so durations include device execution
    # (reference per-opr profiling also serialises the engine)
    "profile_device_sync": True,
    "continuous_dump": False,
    "dump_period": 1.0,
}
_state = {"running": False, "jax_trace_dir": None, "dump_timer": None,
          "kvstore": None, "last_mem_sample": 0.0}
_records = []
_records_lock = threading.Lock()
_last_counters = {}
_t0 = None

KWARGS = _config  # parity alias


def set_config(**kwargs):
    """Configure the profiler (parity: profiler.py set_config). Forwards
    to the kvstore servers too once ``set_kvstore_handle`` was called
    (reference KVStoreServerProfilerCommand::kSetConfig)."""
    for k, v in kwargs.items():
        if k in _config:
            _config[k] = v
        elif k in ("profile_process",):
            pass  # accepted for API parity
        else:
            raise MXNetError(f"unknown profiler option {k}")
    _forward_to_server("profiler_set_config", kwargs)


def set_kvstore_handle(kv):
    """Route subsequent profiler set_config/set_state/dump calls to the
    dist kvstore servers as well (parity: reference profiler.py
    set_kvstore_handle + KVStoreServerProfilerCommand,
    include/mxnet/kvstore.h:49)."""
    _state["kvstore"] = kv


def _forward_to_server(head, payload):
    kv = _state["kvstore"]
    if kv is None:
        return
    try:
        import pickle
        kv._send_command_to_servers(head, pickle.dumps(payload))
    except Exception as e:  # noqa: BLE001 — best-effort forwarding
        logging.getLogger("mxnet_tpu.profiler").debug(
            "server-side profiler command %r dropped: %s", head, e)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Deprecated API (parity: profiler.py profiler_set_config)."""
    set_config(filename=filename)


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def start(profile_process="worker"):
    """Start profiling (parity: profiler.py start). Also starts a JAX/XLA
    device trace when a directory is configured via MXNET_PROFILER_XPLANE_DIR."""
    global _t0
    _t0 = time.perf_counter()
    _state["running"] = True
    _state["dump_deadline"] = None  # re-anchor the continuous-dump grid
    xdir = os.environ.get("MXNET_PROFILER_XPLANE_DIR")
    if xdir:
        import jax
        jax.profiler.start_trace(xdir)
        _state["jax_trace_dir"] = xdir
    if _config["continuous_dump"]:
        _schedule_dump()
    _forward_to_server("profiler_set_state", "run")


def _next_dump_deadline(deadline, period, now):
    """The next monotonic dump deadline: ``deadline + period`` normally;
    when a dump overran one or more whole periods, realign to the
    original grid without firing a catch-up burst."""
    nxt = deadline + period
    if nxt <= now:
        nxt = now + period - ((now - deadline) % period)
    return nxt


def _schedule_dump():
    """Background periodic dump (reference continuous_dump/dump_period).

    Each timer re-arms from a MONOTONIC deadline carried in
    ``_state["dump_deadline"]`` — the old ``Timer(period)``-after-dump
    scheme added every dump's own write time to the cadence, so a 50 ms
    dump on a 1 s period drifted ~3 min/hour."""
    t = _state.get("dump_timer")
    if t is not None:
        t.cancel()
    now = time.monotonic()
    if _state.get("dump_deadline") is None:
        _state["dump_deadline"] = now + float(_config["dump_period"])

    def tick():
        if not _state["running"]:
            return
        try:
            dump(finished=False)
        except Exception as e:  # noqa: BLE001 — keep the timer alive
            logging.getLogger("mxnet_tpu.profiler").warning(
                "continuous profiler dump failed: %s", e)
        _state["dump_deadline"] = _next_dump_deadline(
            _state["dump_deadline"], float(_config["dump_period"]),
            time.monotonic())
        _arm()

    def _arm():
        delay = max(0.0, _state["dump_deadline"] - time.monotonic())
        timer = threading.Timer(delay, tick)
        timer.daemon = True
        timer.start()
        _state["dump_timer"] = timer

    _arm()


def stop(profile_process="worker"):
    """Stop profiling."""
    _state["running"] = False
    t = _state.get("dump_timer")
    if t is not None:
        t.cancel()
        _state["dump_timer"] = None
    _state["dump_deadline"] = None
    if _state["jax_trace_dir"]:
        import jax
        jax.profiler.stop_trace()
        _state["jax_trace_dir"] = None
    _forward_to_server("profiler_set_state", "stop")


def is_running():
    return _state["running"]


def jax_trace_dir():
    """Directory of the live jax xplane trace (None when no device trace
    is running) — telemetry spans mirror themselves into it."""
    return _state["jax_trace_dir"]


def _reset_after_fork():
    """Clear per-process profiling state in a forked child (called by
    initialize.py's at-fork handler): the child must not append to the
    parent's trace buffers or try to stop the parent's jax trace."""
    _state["running"] = False
    _state["jax_trace_dir"] = None
    with _records_lock:
        _records.clear()
        _dispatch_counts.clear()


def device_sync_enabled():
    return _config["profile_device_sync"]


def record_synced(name, t0, arrays):
    """Block on ``arrays`` (when device-sync profiling is on) and record
    the op with duration measured from ``t0``.  Errors re-surface at the
    user's sync point as MXNetError, not here."""
    import time as _time
    if _config["profile_device_sync"]:
        try:
            import jax
            jax.block_until_ready(
                [a for a in arrays
                 if not isinstance(a, jax.core.Tracer)])
        except Exception:
            pass
    record_op(name, (_time.perf_counter() - t0) * 1e6)


def record_op(name, dur_us, cat="operator"):
    """Internal hook: record one op dispatch (called from ndarray.invoke
    when profiling is on)."""
    if not _state["running"]:
        return
    with _records_lock:
        _records.append({
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": (time.perf_counter() - _t0) * 1e6 - dur_us,
            "dur": dur_us,
            "pid": os.getpid(),
            "tid": threading.get_ident() % 100000,
        })
    if _config["profile_memory"]:
        _sample_device_memory()


def record_counter(name, value, args_key="value"):
    """Append one counter-lane sample ("C" event) to the trace (parity:
    the reference profiler's counter lanes, src/profiler/profiler.h
    ProfileCounter).  Module-level entry point so subsystems (serving
    metrics, checkpoint, storage, …) can emit counters without holding a
    Domain/Counter object.  The last value per counter is always kept
    (``last_counters()``) so monitoring can read e.g.
    ``checkpoint:save_blocking_ms`` without a running trace; trace
    events are only appended while the profiler runs."""
    with _records_lock:
        _last_counters[name] = value
    if not _state["running"]:
        return
    with _records_lock:
        _records.append({
            "name": name, "cat": "counter", "ph": "C",
            "ts": (time.perf_counter() - _t0) * 1e6,
            "pid": os.getpid(), "args": {args_key: value},
        })


_dispatch_counts = {}


def record_dispatch(kind="op"):
    """Count one framework-issued XLA computation launch (an eager op
    ``invoke``, a compiled executor forward/backward, a fused train
    step).  Unlike trace events these are counted even while the
    profiler is stopped, so tests and the CI smokes can count
    dispatches per step (fused_step.py's budget) without arming a trace.
    Host<->device transfers are deliberately NOT counted — they overlap
    compute under PJRT; this lane measures computation launches."""
    with _records_lock:
        _dispatch_counts[kind] = _dispatch_counts.get(kind, 0) + 1
        _dispatch_counts["total"] = _dispatch_counts.get("total", 0) + 1


def dispatch_counts():
    """Snapshot of launch counts by kind plus a running ``total``."""
    with _records_lock:
        return dict(_dispatch_counts)


def reset_dispatch_counts():
    with _records_lock:
        _dispatch_counts.clear()


def last_counters():
    """Snapshot of the most recent value of every counter ever recorded
    (e.g. ``checkpoint:save_blocking_ms``, ``serving:*``) — maintained
    even while the profiler is stopped, so save-latency/bytes lanes are
    observable without arming a trace."""
    with _records_lock:
        return dict(_last_counters)


def record_api(name, dur_us=0.0):
    """Record a frontend/API event (waitall, asnumpy, bind, …) when
    profile_api is on (parity: the reference's MXAPIThreadLocal API-call
    profiling under profile_api, src/c_api/c_api_profile.cc)."""
    if _config["profile_api"] or _config["profile_all"]:
        record_op(name, dur_us, cat="api")


_MEM_SAMPLE_PERIOD_S = 0.01  # at most 100 samples/s — PJRT stats aren't free


def _sample_device_memory():
    """Append a chrome-trace counter sample of device bytes in use
    (parity: the reference memory profiler, src/profiler/storage_profiler.h,
    rendered as a counter lane). Throttled; silently skipped when the
    backend exposes no allocator stats."""
    now = time.perf_counter()
    if now - _state["last_mem_sample"] < _MEM_SAMPLE_PERIOD_S:
        return
    _state["last_mem_sample"] = now
    try:
        from .context import device_memory_info
        info = device_memory_info()
        used = int(info.get("bytes_in_use", 0))
    except Exception:
        return
    with _records_lock:
        _records.append({
            "name": "device_memory",
            "cat": "memory",
            "ph": "C",
            "ts": (now - _t0) * 1e6,
            "pid": os.getpid(),
            "args": {"bytes_in_use": used},
        })


def pause(profile_process="worker"):
    _state["running"] = False
    t = _state.get("dump_timer")
    if t is not None:
        t.cancel()
        _state["dump_timer"] = None
    _state["dump_deadline"] = None


def resume(profile_process="worker"):
    _state["running"] = True
    if _config["continuous_dump"]:
        _schedule_dump()


def dump(finished=True, profile_process="worker"):
    """Write chrome://tracing JSON (parity: profiler.py dump →
    profile.json format of src/profiler/profiler.h:460)."""
    with _records_lock:
        events = list(_records)
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    # atomic temp + os.replace: the continuous-dump timer rewrites this
    # file periodically — chrome://tracing must never load a torn JSON
    fname = _config["filename"]
    tmp = f"{fname}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, fname)
    _forward_to_server("profiler_dump", bool(finished))


def dumps(reset=False, format="table", sort_by="total", ascending=False,
          aggregate=False):
    """Return aggregate stats as an ASCII table, or a dict when
    format="json" (parity: profiler.py dumps → aggregate_stats.cc table
    and json dump modes).  ``aggregate=True`` additionally folds the
    dispatch-count lanes (``record_dispatch``) into the output — without
    it only per-op duration rows make the table, so launches-per-step
    was invisible in the very output meant to summarize the trace
    (json: under the ``"dispatch_counts"`` key; table: a trailing
    "Dispatch Counts" section)."""
    with _records_lock:
        events = list(_records)
        if reset:
            _records.clear()
    counts = dispatch_counts() if aggregate else {}
    agg = {}
    for e in events:
        if e.get("ph") != "X":
            continue  # counter/memory samples have no duration
        st = agg.setdefault(e["name"], [0, 0.0, float("inf"), 0.0])
        st[0] += 1
        st[1] += e["dur"]
        st[2] = min(st[2], e["dur"])
        st[3] = max(st[3], e["dur"])
    if format == "json":
        out = {name: {"count": c, "total_ms": t / 1e3, "min_ms": mn / 1e3,
                      "max_ms": mx / 1e3, "avg_ms": t / c / 1e3}
               for name, (c, t, mn, mx) in agg.items()}
        if counts:
            out["dispatch_counts"] = counts
        return out
    lines = ["Profile Statistics:",
             f"{'Name':<40}{'Total Count':>12}{'Time (ms)':>14}"
             f"{'Min (ms)':>12}{'Max (ms)':>12}{'Avg (ms)':>12}"]
    items = sorted(agg.items(),
                   key=lambda kv: kv[1][1] if sort_by == "total" else kv[1][0],
                   reverse=not ascending)
    for name, (cnt, tot, mn, mx) in items:
        lines.append(f"{name:<40}{cnt:>12}{tot/1e3:>14.4f}"
                     f"{mn/1e3:>12.4f}{mx/1e3:>12.4f}{tot/cnt/1e3:>12.4f}")
    if counts:
        lines.append("")
        lines.append("Dispatch Counts:")
        lines.append(f"{'Kind':<40}{'Count':>12}")
        for kind in sorted(counts):
            lines.append(f"{kind:<40}{counts[kind]:>12}")
    return "\n".join(lines)


class Profiler:
    """Context-manager convenience."""

    def __init__(self, **kwargs):
        set_config(**kwargs)

    def __enter__(self):
        start()
        return self

    def __exit__(self, *args):
        stop()


# -- scoped domains / tasks / frames / markers (API parity) ------------------
class Domain:
    def __init__(self, name):
        self.name = name

    def __str__(self):
        return self.name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    def __init__(self, domain, name):
        self.name = name
        self.domain = domain
        self._start = None

    def start(self):
        self._start = time.perf_counter()

    def stop(self):
        if self._start is not None and _state["running"]:
            dur_us = (time.perf_counter() - self._start) * 1e6
            record_op(f"{self.domain}:{self.name}", dur_us, cat="task")
        self._start = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *args):
        self.stop()


class Task(_Span):
    pass


class Frame(_Span):
    pass


class Event(_Span):
    def __init__(self, name):
        super().__init__("event", name)


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self.value = value or 0

    def _emit(self):
        # counters render as a chrome-trace counter lane ("C" events),
        # like the reference's profiler counters
        record_counter(f"{self.domain}:{self.name}", self.value)

    def set_value(self, value):
        self.value = value
        self._emit()

    def increment(self, delta=1):
        self.value += delta
        self._emit()

    def decrement(self, delta=1):
        self.value -= delta
        self._emit()

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        record_op(f"{self.domain}:{self.name}", 0, cat="marker")


# -- env autostart (parity: MXNET_PROFILER_AUTOSTART / MXNET_PROFILER_MODE,
#    reference docs/faq/env_var.md:193-197). Parsed through the config
#    registry so every documented bool spelling (1/true/yes/on) works.
from .config import get as _cfg_get  # noqa: E402

if _cfg_get("MXNET_PROFILER_AUTOSTART"):
    if _cfg_get("MXNET_PROFILER_MODE") in ("all", "1"):
        _config["profile_all"] = True
        _config["profile_api"] = True
    start()
