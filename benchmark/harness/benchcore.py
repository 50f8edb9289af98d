"""What every driver of the benchmark shares: finding a cell's files by
the names in ``BENCHMARK.json``, the seeded pool of host batches, the
recorder that turns per-step syncs into the window, the metrics and the
checks, and the last line of a run.

Nothing here imports jax or mxnet_tpu at import time: ``run.py`` has to
set the environment before either is loaded.  The timing arithmetic
(set-up apart from steps, marks at synced reads, no compile after the
warm-up) is ``chip_smoke.py``'s ``leg_train``/``leg_spmd``, copied so
that the benchmark imports nothing from it.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import time

# this file is <checkout>/benchmark/harness/benchcore.py
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a warm-up that has not settled after this many syncs is a fault of the
# program (it keeps compiling), not something to wait out
MAX_WARMUP_SYNCS = 64


class BenchFailure(RuntimeError):
    """The run cannot give a result: no chip, a missing file, a driver
    that did not do what the cell is about."""


def say(msg):
    print(msg, flush=True)


# -- files found by name -------------------------------------------------------
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_py(path, name):
    """A module of the benchmark loaded from its file: configurations,
    drivers and per-layer readers are data the harness finds by name,
    not a package somebody has to register them in."""
    if not os.path.isfile(path):
        raise BenchFailure(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, its job (the
    traffic) and the metrics ``BENCHMARK.json`` lists for it."""

    def __init__(self, name, root=CHECKOUT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if len(rows) != 1:
            raise BenchFailure(
                f"workload {name!r} is not in BENCHMARK.json (it has "
                f"{[w['name'] for w in self.bench['workloads']]})")
        self.name = name
        self.row = rows[0]
        self.chips = int(self.row["chips"])
        conf = [c for c in self.bench["configs"]
                if c["name"] == self.row["config"]]
        if len(conf) != 1:
            raise BenchFailure(f"configuration {self.row['config']!r} is "
                               "not in BENCHMARK.json")
        self.config_file = os.path.join(root, conf[0]["file"])
        self.config = load_json(self.config_file)
        self.job = load_json(os.path.join(
            root, "benchmark", "jobs", self.row["traffic"] + ".json"))
        if int(self.job["chips"]) != self.chips:
            raise BenchFailure(
                f"job {self.row['traffic']} is written for "
                f"{self.job['chips']} chip(s), the cell says {self.chips}")

    def metrics(self, group):
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def config_module(self):
        return load_py(os.path.splitext(self.config_file)[0] + ".py",
                       "benchmark_config_" + _ident(self.row["config"]))

    def driver_module(self):
        return load_py(os.path.join(self.root, "benchmark", "drivers",
                                    self.job["driver"] + ".py"),
                       "benchmark_driver_" + _ident(self.job["driver"]))

    def reader(self, metric):
        path = os.path.join(self.root, "benchmark", "layer_metrics",
                            metric + ".py")
        return load_py(path, "benchmark_metric_" + _ident(metric)).read


def _ident(name):
    return "".join(c if c.isalnum() else "_" for c in name)


def peak_flops(device_kind, root=CHECKOUT):
    """Peak dense bf16 FLOP/s of one chip from ``peaks.json``; a device
    that is not in the table is an error, never a default."""
    table = load_json(os.path.join(root, "benchmark", "harness",
                                   "peaks.json"))["peaks"]
    if device_kind not in table:
        raise BenchFailure(
            f"no table peak for device_kind {device_kind!r}: add it to "
            "benchmark/harness/peaks.json with its source")
    return float(table[device_kind]["bf16_flops"])


# -- inputs --------------------------------------------------------------------
def make_pool(seed, batch, image, num_classes, n):
    """``n`` host batches from ``seed``: images uniform in [-1, 1),
    labels uniform over the classes (float32, as MXNet iterators carry
    them).  The same seed gives the same inputs."""
    import numpy as np
    rng = np.random.default_rng(seed)
    xs = rng.random((n, batch) + tuple(image), dtype=np.float32) * 2 - 1
    ys = rng.integers(0, num_classes, (n, batch)).astype(np.float32)
    return xs, ys


# -- the recorder --------------------------------------------------------------
class Recorder:
    """Every sync of the run: when it reached the host, how many
    programs had been built by then, the losses it brought.

    A sync is the read the entry point itself makes (the metric's read
    in ``fit``, the loss read in the loops).  Warm-up lasts until a whole
    interval between two syncs has passed without a program being built
    and at least ``min_warm`` syncs are in; that sync starts the window,
    which lasts ``seconds`` and ends at the first sync boundary after.
    With ``trace_seconds`` the profiler is started that long before the
    window's end, so the window has an un-profiled part (the rates) and a
    profiled part (the trace)."""

    def __init__(self, t0, seconds, steps_per_sync, min_warm, compiles,
                 trace_dir=None, trace_seconds=0.0, on_start=None):
        self.t0 = t0
        self.seconds = float(seconds)
        self.k = int(steps_per_sync)
        self.min_warm = int(min_warm)
        self._compiles = compiles
        self.trace_dir = trace_dir
        self.trace_seconds = float(trace_seconds) if trace_dir else 0.0
        self._on_start = on_start
        self.marks = []        # (host time, programs built so far)
        self.losses = []       # one per step
        self.start = None      # index into marks: the window's first mark
        self.trace_at = None   # index into marks: last un-profiled mark
        self.tracing = False
        self.deadline = None

    def sync(self, losses):
        """One sync reached the host with the losses of its steps."""
        import jax
        if len(losses) != self.k:
            raise BenchFailure(f"a sync brought {len(losses)} losses, the "
                               f"job says {self.k} steps per sync")
        now = time.perf_counter()
        self.losses.extend(float(v) for v in losses)
        self.marks.append((now, self._compiles()))
        if self.tracing:
            # the reduction finds the steps of the trace by these
            with jax.profiler.TraceAnnotation("bench/sync"):
                pass
        if self.start is None:
            n = len(self.marks)
            if n >= max(2, self.min_warm) and \
                    self.marks[-1][1] == self.marks[-2][1]:
                self.start = n - 1
                self.deadline = now + self.seconds
                if self._on_start is not None:
                    self._on_start()
            elif n >= MAX_WARMUP_SYNCS:
                raise BenchFailure(
                    f"still compiling after {n} syncs: the warm-up never "
                    "settled")
        elif self.trace_seconds and not self.tracing and \
                now >= self.deadline - self.trace_seconds:
            self.trace_at = len(self.marks) - 1
            opts = jax.profiler.ProfileOptions()
            # the Python tracer multiplies the host's work per step; the
            # benchmark's own annotations and the runtime's host events
            # are what the gaps are attributed to
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.tracing = True
            with jax.profiler.TraceAnnotation("bench/sync"):
                pass

    @property
    def done(self):
        """True once the window's time is up; asked at sync boundaries."""
        return self.deadline is not None and \
            time.perf_counter() >= self.deadline

    def stop_trace(self):
        if self.tracing:
            import jax
            jax.profiler.stop_trace()
            self.tracing = False

    # -- what the window says ---------------------------------------------
    def setup_s(self):
        return self.marks[self.start][0] - self.t0

    def rate_marks(self):
        """The marks the rates are taken over: the whole window, or its
        un-profiled part in a traced run."""
        end = self.trace_at if self.trace_at is not None \
            else len(self.marks) - 1
        return self.marks[self.start:end + 1]

    def window_steps(self):
        return (len(self.marks) - 1 - self.start) * self.k

    def window_losses(self):
        return self.losses[(self.start + 1) * self.k:]

    def compiles_in_window(self):
        return self.marks[-1][1] - self.marks[self.start][1]


def window_metrics(rec, batch):
    """images_per_s and (for a sync every step) step_ms_p95 with its
    sample count, from the host times of the syncs."""
    import numpy as np
    marks = rec.rate_marks()
    if len(marks) < 2:
        raise BenchFailure("the window holds fewer than two syncs")
    times = np.array([m[0] for m in marks])
    wall = float(times[-1] - times[0])
    steps = (len(marks) - 1) * rec.k
    out = {"images_per_s": steps * batch / wall, "wall_s": wall,
           "steps": steps}
    if rec.k == 1:
        gaps = np.diff(times) * 1e3
        out["step_ms_p95"] = float(np.percentile(gaps, 95))
        out["step_ms_p50"] = float(np.percentile(gaps, 50))
        out["samples"] = int(gaps.size)
    return out


# -- the comparison that decides ``correct`` -------------------------------------
# The system multiplies float32 operands at the TPU's default precision
# (bfloat16 products, float32 sums); the plain reference multiplies at
# "highest".  ~2e-3 per product compounds over some fifty convolutions:
# PR 21 measured 0.27 % of max|logit| on ResNet-50.  A step computed in
# bfloat16 end to end (8-bit mantissa in the activations and the BatchNorm
# statistics too) is several times that.
LOGIT_REL_TOL = 2e-2
# the loss is a mean of log-probabilities over the batch: errors of the
# logits average out, so it is held tighter than a single logit
LOSS_REL_TOL = 1e-2


def compare_logits(got, ref):
    import numpy as np
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if got.shape != ref.shape or not np.isfinite(got).all() \
            or not np.abs(ref).max() > 0:
        return False, float("inf")
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    return rel <= LOGIT_REL_TOL, rel


def compare_loss(got, ref):
    if not (math.isfinite(got) and math.isfinite(ref)):
        return False, float("inf")
    rel = abs(got - ref) / max(abs(ref), 1e-30)
    return rel <= LOSS_REL_TOL, rel


def cross_entropy(prob, label):
    """Mean -log p[label] of softmax outputs, on the host, as
    ``chip_smoke.py``'s ``LossTrace`` reads it from ``SoftmaxOutput``."""
    import numpy as np
    lab = np.asarray(label).astype(np.int64)
    p = np.asarray(prob)[np.arange(lab.size), lab]
    return float(-np.log(np.maximum(p, 1e-30)).mean())


def loss_checks(rec):
    """(3) of ``correct``: the mean loss of the last eight steps is finite
    and below that of the run's first eight, warm-up included (the
    warm-up trains too)."""
    import numpy as np
    n = min(8, len(rec.losses) // 2)
    first, last = np.mean(rec.losses[:n]), np.mean(rec.losses[-n:])
    return {"loss_finite": bool(np.isfinite(rec.losses).all()),
            "loss_fell": bool(last < first)}, float(first), float(last)


# -- the device ------------------------------------------------------------------
def device_report(devices):
    """The device as jax reports it; the peak is that of the fullest
    chip.  On the TPU ``peak_bytes_in_use`` counts the arrays the process
    holds and ``peak_bytes_reserved`` what loaded programs reserve for
    their temporaries (the activations of a train step live there: 2.6 GB
    at batch 32 and 9.0 GB at batch 128 against 0.8 and 2.1 GB "in use",
    my chip runs, PR 22), so the peak is their sum."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                int(s.get("peak_bytes_in_use", 0))
                + int(s.get("peak_bytes_reserved", 0)) for s in stats)}


def compile_counts():
    from mxnet_tpu import compile as mxc
    jaxc = mxc.LEDGER.counts()["jax"]
    return {k: int(jaxc.get(k, 0)) for k in
            ("backend_compiles", "persistent_hits", "persistent_misses")}


def backend_compiles():
    return compile_counts()["backend_compiles"]


# -- the program's own lanes -----------------------------------------------------
LANE_COVERAGE = 0.9


def lane_share_pct(data, names):
    """Share of the window's wall time in the named ``telemetry.steps``
    lanes, or None where the run has no lanes or all lanes together
    account for under 90 % of the wall they were taken over."""
    lanes = data.get("lanes")
    if not lanes or lanes["wall_s"] <= 0:
        return None
    if sum(lanes["lanes"].values()) < LANE_COVERAGE * lanes["wall_s"]:
        return None
    return 100.0 * sum(lanes["lanes"].get(n, 0.0) for n in names) \
        / lanes["wall_s"]
