"""Profiler (parity: python/mxnet/profiler.py + src/profiler/profiler.h:260).

The reference writes chrome://tracing JSON from an in-engine profiler with
device/engine lanes + an aggregate stats table. TPU redesign: the heavy
lifting is jax.profiler (XLA xplane → TensorBoard/perfetto); this module
keeps the mx.profiler API surface (set_config/start/stop/dump/dumps) and
adds a lightweight host-side op-dispatch recorder producing the same
chrome-trace JSON + aggregate table the reference emits.
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import json
import logging
import os
import re
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
          "kvstore": None, "last_mem_sample": 0.0,
          # where the last start() traced the device to: dumps() reads
          # the newest xplane under it once the trace has stopped
          "xplane_dir": None}
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
        _state["jax_trace_dir"] = _state["xplane_dir"] = xdir
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


# -- the device trace, by the program's own scopes ---------------------------
# What a jax name stack holds that is jax's own and no scope of the
# program's: the call a component names (``jit(step)``), the transforms
# wrapped round a scope (``transpose(jvp(op/Convolution))``), and the
# components control flow, calls and ``jax.checkpoint`` put in.
_CALL = re.compile(r"(^|/)(jit|pjit|pmap)\([^()]*\)")
_TRANSFORM = re.compile(r"\w+\(|\)")
_JAX_OWN = re.compile(
    r"^(while|body|cond|body_pred|branch_\d+_fun|closed_call|core_call|"
    r"checkpoint|rematted_computation|pjit|shard_map|custom_jvp_call|"
    r"custom_vjp_call|custom_vjp_call_jaxpr)$")
RECOMPUTE = "rematted_computation"   # what jax.checkpoint's backward re-runs
# an op that only holds other ops of its line (a scanned window's ``while``)
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* = ")
PHASES = ("forward", "backward", "recompute", "other")

DeviceOp = collections.namedtuple(
    "DeviceOp", "device start_ns duration_ns name program scopes phases")


@functools.lru_cache(maxsize=None)
def parse_op_name(op_name):
    """``(scopes, phases)`` of one op-name statistic of a device trace: the
    ``;``-joined jax name stacks of the primitives XLA fused into the op.
    ``scopes[i]`` is the i-th name's path of ``jax.named_scope`` names
    (``"nemotron/attention/granite/attention/op/_contrib_flash_attention"``,
    ``""`` where the primitive ran under none) with jax's own components
    and the primitive's name stripped; ``phases[i]`` says which pass put
    it there: ``recompute`` (under ``jax.checkpoint``'s
    ``rematted_computation``), ``backward`` (``transpose(...)``),
    ``forward`` (``jvp(...)``), else ``other`` (the update, a metric)."""
    scopes, phases = [], []
    for name in (op_name or "").split(";"):
        name = name.strip().rstrip(":")   # the statistic is "<name>:<type>"
        if not name:
            continue
        if RECOMPUTE in name:
            phases.append("recompute")
        elif "transpose(" in name:
            phases.append("backward")
        elif "jvp(" in name:
            phases.append("forward")
        else:
            phases.append("other")
        # the last component is the primitive (a call's is ``jit(f)``)
        parts = _TRANSFORM.sub("", _CALL.sub(r"\1", name)).split("/")[:-1]
        scopes.append("/".join(
            p for p in parts if p and not _JAX_OWN.match(p)))
    return tuple(scopes), tuple(phases)


def _wire(buf, pos, end):
    """``(field, value)`` of one protobuf message in ``buf[pos:end]``: an
    int for a varint, ``(start, end)`` for a length-delimited field,
    fixed-width fields skipped."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        kind = key & 7
        if kind == 2 or kind == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            if kind == 2:
                yield key >> 3, (pos, pos + val)
                pos += val
            else:
                yield key >> 3, val
        else:
            pos += 8 if kind == 1 else 4


def device_ops(xplane_path):
    """Every executed HLO operation of a profiler trace (``.xplane.pb``),
    by the program's own names: for each ``/device:TPU:<n>`` plane the
    events of its ``XLA Ops`` line as ``DeviceOp(device, start_ns,
    duration_ns, name, program, scopes, phases)``: the clock and the HLO
    name as ``jax.profiler.ProfileData`` gives them, ``program`` the
    ``XLA Modules`` run the op started in, ``scopes`` and ``phases`` from
    the op's ``tf_op`` statistic (``parse_op_name``), which
    ``ProfileData`` does not hand out.  Ordered by device, then start.
    ``[]`` for a trace with no TPU plane.

    The file is an ``XSpace`` message (tsl/profiler/protobuf/
    xplane.proto); read by walking its wire format, since the generated
    classes live in packages a training machine need not have:
    ``XSpace.planes = 1``; ``XPlane.name = 2, lines = 3, event_metadata
    = 4, stat_metadata = 5``; ``XLine.name = 2, timestamp_ns = 3, events
    = 4``; ``XEvent.metadata_id = 1, offset_ps = 2, duration_ps = 3``;
    ``XEventMetadata.id = 1, name = 2, stats = 5``; ``XStat.metadata_id
    = 1, str_value = 5, ref_value = 7``; ``XStatMetadata.id = 1, name =
    2``."""
    with open(xplane_path, "rb") as f:
        buf = f.read()

    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    def entry(span):   # a map entry: key = 1, value = 2
        return dict(_wire(buf, *span))

    out = []
    for field, plane in _wire(buf, 0, len(buf)):
        if field != 1:
            continue
        parts = collections.defaultdict(list)
        for f_, v in _wire(buf, *plane):
            parts[f_].append(v)
        m = re.match(r"^/device:TPU:(\d+)$", text(parts[2][0])) \
            if parts[2] else None
        if not m:
            continue
        stat_names = {}
        for span in parts[5]:
            meta = dict(_wire(buf, *entry(span)[2]))
            stat_names[meta.get(1, 0)] = text(meta[2]) if 2 in meta else ""
        events = {}     # metadata id -> (HLO name, op-name statistic)
        for span in parts[4]:
            name, op_name, mid = "", "", 0
            for f_, v in _wire(buf, *entry(span)[2]):
                if f_ == 1:
                    mid = v
                elif f_ == 2:
                    name = text(v)
                elif f_ == 5:
                    stat = dict(_wire(buf, *v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op_name = text(stat[5]) if 5 in stat else \
                            stat_names.get(stat.get(7), "")
            events[mid] = (name, op_name)
        lines = {}
        for span in parts[3]:
            line = collections.defaultdict(list)
            for f_, v in _wire(buf, *span):
                line[f_].append(v)
            name = text(line[2][0]) if line[2] else ""
            if name in ("XLA Ops", "XLA Modules"):
                t0 = line[3][0] if line[3] else 0
                rows = []
                for ev in line[4]:
                    e = dict(_wire(buf, *ev))
                    rows.append((int(t0 + e.get(2, 0) / 1000),
                                 int(e.get(3, 0) / 1000), e.get(1, 0)))
                lines[name] = sorted(rows)
        runs = lines.get("XLA Modules", [])
        run_starts = [r[0] for r in runs]
        for start, dur, mid in lines.get("XLA Ops", []):
            name, op_name = events.get(mid, ("", ""))
            i = bisect.bisect_right(run_starts, start) - 1
            program = ""
            if i >= 0 and start < runs[i][0] + max(runs[i][1], 1):
                program = events.get(runs[i][2], ("", ""))[0]
            scopes, phases = parse_op_name(op_name)
            out.append(DeviceOp(int(m.group(1)), start, dur, name,
                                program.partition("(")[0], scopes, phases))
    out.sort(key=lambda op: (op.device, op.start_ns))
    return out


def device_time_by_scope(xplane_path):
    """``{scope path: [ops, seconds]}`` over a trace's TPU planes, most
    time first: an op fused from primitives of several scopes gives each
    an equal part; ``"(unscoped)"`` holds what ran under none.  Ops that
    only hold others (the ``while`` of a scanned window) are left out."""
    by = {}
    for op in device_ops(xplane_path):
        if _CONTAINER.match(op.name):
            continue
        paths = sorted({s for s in op.scopes if s}) or ["(unscoped)"]
        for path in paths:
            row = by.setdefault(path, [0, 0.0])
            row[0] += 1
            row[1] += op.duration_ns / 1e9 / len(paths)
    return dict(sorted(by.items(), key=lambda kv: -kv[1][1]))


def _last_xplane():
    """The newest xplane under the directory the last ``start()`` traced
    to, once that trace has stopped; None without one."""
    xdir = _state["xplane_dir"]
    if not xdir or _state["jax_trace_dir"]:
        return None
    files = glob.glob(os.path.join(xdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


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
    "Dispatch Counts" section).  Where ``start()`` traced the device
    (``MXNET_PROFILER_XPLANE_DIR``) and the trace has stopped, the output
    also holds the device's own time by the program's scopes
    (``device_time_by_scope``: an operator is ``op/<name>``; json: under
    ``"device_time_by_scope"``; table: a trailing "Device time by scope"
    section) — the rows above are a host clock around blocking calls."""
    xplane = _last_xplane()
    by_scope = device_time_by_scope(xplane) if xplane else {}
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
        if by_scope:
            out["device_time_by_scope"] = {
                path: {"count": n, "total_ms": sec * 1e3}
                for path, (n, sec) in by_scope.items()}
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
    if by_scope:
        whole = sum(sec for _n, sec in by_scope.values()) or 1.0
        lines += ["", f"Device time by scope ({os.path.basename(xplane)}):",
                  f"{'Scope':<72}{'Ops':>10}{'Time (ms)':>14}{'Share (%)':>11}"]
        for path, (n, sec) in by_scope.items():
            lines.append(f"{path:<72}{n:>10}{sec * 1e3:>14.4f}"
                         f"{100 * sec / whole:>11.2f}")
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
