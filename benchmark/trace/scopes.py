"""Device time by the program's own scopes: from the traced run's
``.xplane.pb`` to milliseconds a step by class and by phase.

``reduce.py`` reads a trace through ``jax.profiler.ProfileData``, which
hands out an op's HLO name and nothing of where in the program it came
from.  The program does say: every registered operator runs under
``op/<name>``, the step's phases under ``step/...``, the language models'
blocks under their own names (docs/observability.md has the table), and
``mxnet_tpu.profiler.device_ops`` reads those name stacks back from the
trace's ``tf_op`` statistic.  Here they are clipped to the window
``reduce.py`` uses (first to last ``bench/sync``), ops that only hold
other ops are dropped (``reduce.CONTAINER``), and the rest is averaged
over the cell's first ``chips`` device planes, as ``busy_s`` is.

A scope path's class is the first of ``CLASSES`` found anywhere in it,
``other`` if none is; an op fused from primitives of several classes
gives each an equal part of its time; an op none of whose primitives ran
under a scope of the program's is ``unscoped``.  So the classes,
``other`` and ``unscoped`` are a partition of the op time.  The phase
(forward, backward, recompute, other) is a second reading of the same
ops, divided the same way.

Every function returns None, and never raises, where there is nothing to
read: no trace file, no TPU plane (a CPU dry drive), fewer than two
syncs, or no ``op/`` scope anywhere in the trace.  The last is an
executable from before the operators named themselves, which a
persistent compile cache filled by an older tree serves to a newer one
(the name stack is debug information, and jax leaves that out of the
cache key): its model scopes alone are not read, a part would pass for
the whole.  One line on the standard output says which it was.
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import os
import re
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "benchmark_trace_reduce", os.path.join(_HERE, "reduce.py"))
reduce = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reduce)


def _has(*components):
    """A pattern that finds one of the scope components, whole, anywhere
    in a path (``*`` inside one stands for letters, digits and ``_``)."""
    alts = "|".join(re.escape(c).replace(r"\*", r"\w*") for c in components)
    return re.compile(rf"(^|/)({alts})(/|$)")


CLASSES = (
    ("optimizer", _has("step/optimizer", "op/*_update")),
    ("moe", _has("moe")),
    ("scan", _has("mamba/ssd", "kda/scan")),
    ("attention", _has("attention")),
    ("head", _has("head", "step/loss")),
    ("conv", _has("op/Convolution")),
    ("batchnorm", _has("op/BatchNorm")),
    ("pool", _has("op/Pooling")),
)
OTHER, UNSCOPED = "other", "unscoped"
OPERATOR = _has("op/*")


@functools.lru_cache(maxsize=None)
def class_of(path):
    for name, pattern in CLASSES:
        if pattern.search(path):
            return name
    return OTHER


def newest_trace():
    """The ``.xplane.pb`` the traced run of this process just wrote:
    ``run.py`` traces into ``<tmp>/bench-trace-*`` and does not hand the
    path to the readers, so take the newest written since the process
    started."""
    try:
        import psutil
        since = psutil.Process().create_time() - 1.0
    except Exception:
        since = 0.0
    newest, at = None, since
    for path in glob.glob(os.path.join(
            tempfile.gettempdir(), "bench-trace-*", "plugins", "profile",
            "*", "*.xplane.pb")):
        try:
            mtime = os.path.getmtime(path)
        except OSError:     # another process's run, just removed
            continue
        if mtime >= at:
            newest, at = path, mtime
    return newest


def syncs_of(path):
    """Start of every ``bench/sync`` annotation on the host planes, as
    ``reduce.reduce`` takes them."""
    from jax.profiler import ProfileData
    return sorted(
        int(ev.start_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name == reduce.SYNC)


def split(ops, lo, hi, n_devices):
    """``(by class, by phase, op ns)`` of ``profiler.device_ops`` rows
    clipped to ``[lo, hi)``: nanoseconds summed over the first
    ``n_devices`` planes and divided by their number."""
    used = sorted({op.device for op in ops})[:n_devices]
    by_class, by_phase, whole = {}, {}, 0.0
    for op in ops:
        a, b = max(op.start_ns, lo), min(op.start_ns + op.duration_ns, hi)
        if op.device not in used or b <= a or reduce.CONTAINER.match(op.name):
            continue
        ns = (b - a) / len(used)
        whole += ns
        classes = {class_of(s) for s in op.scopes if s} or {UNSCOPED}
        for c in classes:
            by_class[c] = by_class.get(c, 0.0) + ns / len(classes)
        phases = set(op.phases) or {"other"}
        for p in phases:
            by_phase[p] = by_phase.get(p, 0.0) + ns / len(phases)
    return by_class, by_phase, whole


@functools.lru_cache(maxsize=None)
def _read(path, chips, steps_per_sync):
    """Parsed once a path however many readers ask, and one line said."""
    t0 = time.perf_counter()
    try:
        out, why = _split_file(path, chips, steps_per_sync)
    except Exception as e:   # a reader never takes the run down
        out, why = None, f"not read ({type(e).__name__}: {e})"
    if out is None:
        why = "not available: " + why
    print(f"  device time by scope ({os.path.basename(path)}, "
          f"{time.perf_counter() - t0:.2f} s to read): {why}", flush=True)
    return out


def _split_file(path, chips, steps_per_sync):
    from mxnet_tpu import profiler
    device_ops = getattr(profiler, "device_ops", None)
    if device_ops is None:
        return None, "the program has no profiler.device_ops"
    ops = device_ops(path)
    if not ops:
        return None, "the trace has no TPU plane"
    if not any(OPERATOR.search(s) for op in ops for s in op.scopes):
        return None, ("no op/ scope in the trace: an executable compiled "
                      "before the operators named themselves (a compile "
                      "cache an older tree filled serves it: clear it)")
    syncs = syncs_of(path)
    if len(syncs) < 2:
        return None, "fewer than two bench/sync annotations"
    steps = (len(syncs) - 1) * steps_per_sync
    by_class, by_phase, whole = split(ops, syncs[0], syncs[-1], chips)
    if not whole:
        return None, "no device op inside the window"
    ms = 1e-6 / steps
    out = {"classes": {k: v * ms for k, v in by_class.items()},
           "phases": {k: v * ms for k, v in by_phase.items()},
           "op_ms": whole * ms, "steps": steps}

    def row(part):
        return " ".join(f"{k} {v:.3f}" for k, v in sorted(
            part.items(), key=lambda kv: -kv[1]))
    return out, (f"{os.path.getsize(path) / 1e6:.1f} MB, {len(ops)} ops; "
                 f"ms a step over {steps} steps: {row(out['classes'])} | "
                 f"{row(out['phases'])} | all ops {out['op_ms']:.3f}")


def read(data):
    """``{"classes": {class: ms a step}, "phases": {phase: ms a step},
    "op_ms": ms a step of all ops, "steps"}`` of this run's trace, or
    None."""
    path = newest_trace()
    if path is None:
        return None
    return _read(path, int(data["cell"]["chips"]),
                 int(data["cell"]["steps_per_sync"]))


def ms_per_step(data, part, name):
    """Device milliseconds a step of one of the ``"classes"`` or of the
    ``"phases"``; 0.0 where the trace has the program's scopes and
    nothing under this one."""
    out = read(data)
    return None if out is None else out[part].get(name, 0.0)


def share_pct(data, part, name):
    """The same as a share of the time of all ops."""
    out = read(data)
    return None if out is None else \
        100.0 * out[part].get(name, 0.0) / out["op_ms"]
