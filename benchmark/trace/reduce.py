"""From a profiler trace to the numbers the per-layer readers take.

Two steps, so that the arithmetic can be checked on a trace small enough
to work out by hand:

``load_xplane(path)`` reads a ``.xplane.pb`` with ``jax.profiler
.ProfileData`` into plain lists, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``;
``reduce(trace, n_devices, steps_per_sync)`` does the rest on those
lists and never looks at a file.

What it takes from a trace of a TPU (looked at by hand first, PERF.md
section 6): a plane ``/device:TPU:<n>`` per chip whose line ``XLA Ops``
holds one event per executed HLO operation and whose line ``XLA
Modules`` holds one event per program run; host planes whose lines are
threads, carrying the runtime's own events and the benchmark's
``bench/...`` annotations.  All on one clock, in nanoseconds.  The
window of the reduction runs from the first ``bench/sync`` annotation to
the last: whole steps, no start-up or shut-down of the profiler.
"""
from __future__ import annotations

import math
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = "bench/sync"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
# an op that only holds other ops of the same line (the scanned window is
# one ``while``): its time is its body's, which is listed op by op
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* = ")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
# host events that only say "the profiler is on" or wrap the whole run
# explain no gap
_NOT_A_CAUSE = re.compile(r"^(bench/sync$|\$|Thread |ProfilerSession)")
MIN_GAP_NS = 50_000
SHORT_GAPS = "between operations (under 50 us each)"
NO_EVENT = "no host event"


def load_xplane(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- interval arithmetic -------------------------------------------------------
def union(intervals):
    """Sorted, merged ``[start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def total(merged):
    return sum(b - a for a, b in merged)


def subtract(merged, holes):
    """The part of ``merged`` that no interval of ``holes`` (merged
    too) covers."""
    out, j = [], 0
    for a, b in merged:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        i = j
        while i < len(holes) and holes[i][0] < b:
            if holes[i][0] > cur:
                out.append([cur, holes[i][0]])
            cur = max(cur, holes[i][1])
            i += 1
        if cur < b:
            out.append([cur, b])
    return out


def gaps(merged, lo, hi):
    """The idle stretches of ``[lo, hi)`` between busy intervals."""
    return subtract([[lo, hi]], merged)


# -- the reduction ---------------------------------------------------------------
def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(trace, n_devices, steps_per_sync=1):
    """Everything the readers take, in seconds unless named otherwise.
    ``n_devices`` is the number of chips the cell runs on: the first
    that many device planes are averaged over (busy time, op times);
    collectives are taken on device 0 alone."""
    devices, hosts = {}, []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            devices[int(m.group(1))] = plane
        elif plane["name"].startswith("/host:"):
            hosts.append(plane)
    host_events = [(name, start, start + dur)
                   for plane in hosts for line in plane["lines"]
                   for name, start, dur in line["events"]]
    syncs = sorted(s for name, s, _e in host_events if name == SYNC)
    out = {"syncs": len(syncs), "steps": 0, "window_s": 0.0, "busy_s": 0.0,
           "device_ops": [], "idle_gaps": [], "devices": 0}
    if len(syncs) < 2:
        return out
    lo, hi = syncs[0], syncs[-1]
    steps = (len(syncs) - 1) * steps_per_sync
    out.update(steps=steps, window_s=(hi - lo) / 1e9)
    used = [devices[i] for i in sorted(devices)][:n_devices]
    out["devices"] = len(used)
    if not used:
        return out

    by_name, busy_total, ops0 = {}, 0.0, None
    for plane in used:
        ops = [(name, max(s, lo), min(s + d, hi))
               for name, s, d in _line(plane, OPS_LINE)
               if s + d > lo and s < hi]
        if ops0 is None:
            ops0 = ops
        busy_total += total(union((a, b) for _n, a, b in ops))
        for name, a, b in ops:
            if not CONTAINER.match(name):
                by_name[name] = by_name.get(name, 0.0) + (b - a)
    n = len(used)
    out["busy_s"] = busy_total / n / 1e9
    out["device_ops"] = [[label(name), ns / n / 1e9] for name, ns in sorted(
        by_name.items(), key=lambda kv: -kv[1])]
    out["program_runs"] = sum(
        1 for _n, s, _d in _line(used[0], MODULES_LINE) if lo <= s < hi)

    # device 0: collectives, what hides them, and the idle gaps
    coll = union((a, b) for name, a, b in ops0 if COLLECTIVE.match(name))
    rest = union((a, b) for name, a, b in ops0
                 if not COLLECTIVE.match(name) and not CONTAINER.match(name))
    out["collective_s"] = total(coll) / 1e9
    out["collective_exposed_s"] = total(subtract(coll, rest)) / 1e9
    busy0 = union((a, b) for _n, a, b in ops0)
    out["idle_gaps"] = attribute_gaps(
        gaps(busy0, lo, hi),
        [ev for ev in host_events if ev[2] > lo and ev[1] < hi
         and not _NOT_A_CAUSE.match(ev[0])
         and (ev[2] - ev[1]) < 0.5 * (hi - lo)])
    return out


def attribute_gaps(idle, host_events):
    """Seconds of device idleness by what the host was doing, most
    first.  A gap of at least ``MIN_GAP_NS`` goes to the host event that
    covers most of it; among events that cover nearly as much (90 % of
    the best) the shortest, which is the innermost.  The stretches
    between one operation and the next are summed under one name."""
    import numpy as np
    by_cause = {}
    names = [ev[0] for ev in host_events]
    starts = np.array([ev[1] for ev in host_events], dtype=np.int64)
    ends = np.array([ev[2] for ev in host_events], dtype=np.int64)
    for a, b in idle:
        if b - a < MIN_GAP_NS:
            cause = SHORT_GAPS
        elif not names:
            cause = NO_EVENT
        else:
            cover = np.minimum(ends, b) - np.maximum(starts, a)
            best = cover.max()
            if best <= 0:
                cause = NO_EVENT
            else:
                near = np.flatnonzero(cover >= 0.9 * best)
                cause = names[near[np.argmin((ends - starts)[near])]]
        by_cause[cause] = by_cause.get(cause, 0) + (b - a)
    return [[name, ns / 1e9] for name, ns in sorted(
        by_cause.items(), key=lambda kv: -kv[1])]


def label(name):
    """An op's event name is its whole HLO line; keep the instruction's
    name, what it is, and the largest array it produces."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:96]
    kind = re.search(r"\s([a-z][a-z0-9\-_]*)\(", " " + rest)
    produced = rest[:kind.start()] if kind else rest
    shapes = [(m.group(0), math.prod(int(d) for d in m.group(1).split(",")
                                     if d))
              for m in _SHAPE.finditer(produced)]
    big = max(shapes, key=lambda sh: sh[1])[0] if shapes else ""
    return f"{head} {kind.group(1) if kind else ''} {big}".strip()[:96]
