#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/harness/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, no children, jax touched only here.  It looks the cell up in
``BENCHMARK.json``, finds the cell's configuration, job and driver and
the per-layer readers by their names (``benchcore.Cell``), trains through
the program's own entry point on the chip, checks the outputs against the
configuration's plain reference, and prints one JSON object as the last
line of its standard output: the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics and a breakdown of the profiled
seconds.  No TPU, fewer chips than the cell asks for, or a
``device_kind`` that is not in ``peaks.json``: a non-zero exit and no
result line, never a number from a CPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse   # noqa: E402
import contextlib  # noqa: E402
import glob       # noqa: E402
import json       # noqa: E402
import math       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
if HARNESS_DIR not in sys.path:
    sys.path.insert(0, HARNESS_DIR)

import benchcore as C  # noqa: E402


class Run:
    """What a driver is handed: the cell, the sizes it runs at, the
    seed, the devices, and the recorder to call at every sync."""

    def __init__(self, cell, seed, seconds, trace, devices, ctx, t0,
                 dry=None):
        self.cell = cell
        self.job = dict(cell.job)
        self.cfg = dict(cell.config)
        self.dry = dry is not None
        if dry:   # the CPU tests' thumbnail sizes; main() has no such mode
            self.cfg.update(dry.get("config", {}))
            self.job.update(dry.get("job", {}))
        self.cfgmod = cell.config_module()
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = list(devices)
        self.ctx = ctx
        self.t0 = T0 if t0 is None else t0
        self.batch = int(self.job["batch"])
        self.image = tuple(self.cfg["image"])
        self.k = int(self.job.get("steps_per_sync", 1))
        self.trace_dir = None
        self.lanes0 = None
        self.rec = None
        self.phase("imports done, device found")
        self.pool = C.make_pool(self.seed, self.batch, self.image,
                                self.cfg["num_classes"],
                                int(self.job["pool_batches"]))
        self.phase("pool of host batches made")

    def recorder(self):
        self.rec = C.Recorder(
            self.t0, self.seconds, self.k, self.job["min_warm_syncs"],
            C.backend_compiles, trace_dir=self.trace_dir,
            trace_seconds=min(float(self.job["trace_seconds"]),
                              self.seconds / 2),
            on_start=self._window_starts)
        return self.rec

    def phase(self, what):
        """One line per phase of set-up, with the seconds since the
        process started: where set-up goes is read off these."""
        C.say(f"  set-up {time.perf_counter() - self.t0:7.2f} s  {what}")

    def _window_starts(self):
        self.phase("warm-up done: the window starts")
        from mxnet_tpu import telemetry
        self.lanes0 = telemetry.step_breakdown()
        self.setup_counts = C.compile_counts()

    # -- the plain reference, once, in set-up -----------------------------
    def reference_checks(self, which, params, eval_logits, device):
        """(1) and (2) of ``correct``: eval-mode logits of 8 pool images
        at the initial parameters, and the first training loss, against
        the configuration's plain reference fed the same parameters.
        ``params`` is {canonical name: host array}; returns the checks
        and a closure for the loss (known only after the first step)."""
        import numpy as np

        import benchref
        forward = self.cfgmod.reference(self.cfg, which)
        want = set(self.cfgmod.param_shapes(self.cfg, which))
        if set(params) != want:
            raise C.BenchFailure(
                "the program's parameters do not match the configuration's "
                f"layer shapes: missing {sorted(want - set(params))[:4]}, "
                f"unexpected {sorted(set(params) - want)[:4]}")
        xs, ys = self.pool
        self.phase("parameters initialised, the program's eval forward run")
        ref_logits = np.asarray(benchref.run_reference(
            forward, params, xs[0][:8], device=device))
        ok, rel = C.compare_logits(eval_logits, ref_logits)
        C.say(f"  eval logits of 8 images vs the plain reference: "
              f"max|diff|/max|logit| {rel:.3e} (tolerance "
              f"{C.LOGIT_REL_TOL:g})")
        ref_loss = float(benchref.run_reference(
            forward, params, xs[0], labels=ys[0], device=device))

        self.phase("plain reference run (eval logits, first loss)")

        def first_loss(got):
            ok2, rel2 = C.compare_loss(got, ref_loss)
            C.say(f"  first training loss {got:.6f} vs the plain reference "
                  f"{ref_loss:.6f}: relative {rel2:.3e} (tolerance "
                  f"{C.LOSS_REL_TOL:g})")
            return ok2
        return {"eval_logits": ok}, first_loss


def run_cell(cell, seed, seconds, trace, devices, ctx, t0=None, dry=None,
             keep_trace=None):
    """Drive one cell and return the result object of its last line.
    ``main`` calls it on the chip; the CPU tests call it with ``dry``
    sizes on ``mx.cpu()``."""
    # the ledger taps jax's compile events from the moment it is
    # imported, and the Gluon path alone never imports it
    import mxnet_tpu.compile  # noqa: F401
    from mxnet_tpu import telemetry

    run = Run(cell, seed, seconds, trace, devices, ctx, t0, dry)
    if not run.trace:
        return _result(run, cell.driver_module().run(run), keep_trace)
    telemetry.enable()   # lanes and spans, as MXNET_TELEMETRY=1
    run.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        out = cell.driver_module().run(run)
        return _result(run, out, keep_trace)
    finally:
        if run.rec is not None and run.rec.tracing:   # the driver raised
            with contextlib.suppress(Exception):
                run.rec.stop_trace()
        shutil.rmtree(run.trace_dir, ignore_errors=True)


def _result(run, out, keep_trace):
    cell, rec = run.cell, run.rec
    win = C.window_metrics(rec, run.batch)
    checks = dict(out["checks"])
    loss_ok, first, last = C.loss_checks(rec)
    checks.update(loss_ok)
    in_window = rec.compiles_in_window()
    checks["no_compile_in_window"] = in_window == 0
    dev = C.device_report(run.devices)
    C.say(f"  memory_stats of {run.devices[0]}: "
          f"{run.devices[0].memory_stats()}")
    failed = sum(1 for v in rec.window_losses() if not math.isfinite(v))
    setup = C.compile_counts()
    C.say(f"  window: {win['steps']} steps of batch {run.batch} in "
          f"{win['wall_s']:.3f} s = {win['images_per_s']:.2f} images/s"
          + (f"; step p50 {win['step_ms_p50']:.3f} ms p95 "
             f"{win['step_ms_p95']:.3f} ms over {win['samples']} samples"
             if "samples" in win else "")
          + (" (the un-profiled part of a traced run)" if run.trace else ""))
    gaps = [b[0] - a[0] for a, b in zip(rec.marks, rec.marks[1:])]
    C.say(f"  seconds between syncs, the first {min(len(gaps), 12)} "
          f"(window from sync {rec.start + 1}): "
          + " ".join(f"{g:.3f}" for g in gaps[:12]))
    C.say(f"  loss: first eight steps {first:.4f}, last eight {last:.4f}; "
          f"programs built in the window: {in_window}; in the whole run "
          f"{setup['backend_compiles']} ({setup['persistent_hits']} from "
          f"the persistent cache, {setup['persistent_misses']} compiled)")
    C.say("  checks: " + " ".join(
        f"{k}={'ok' if v else 'FAILED'}" for k, v in sorted(checks.items())))

    values = {"setup_s": rec.setup_s(), "images_per_s": win["images_per_s"]}
    if "step_ms_p95" in win:
        values["step_ms_p95"] = win["step_ms_p95"]
    result = {"correct": all(checks.values()),
              "attempted": rec.window_steps(), "failed": failed}
    if not run.trace:
        wanted = cell.metrics("end_to_end")
    else:
        wanted = cell.metrics("per_layer")
        values, extra = _per_layer(run, out, win, wanted, keep_trace,
                                   dev["memory_peak_bytes"])
        dev.update(extra.pop("device"))
        result["breakdown"] = extra["breakdown"]
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values.get(m["name"]) is not None}
    missing = [m["name"] for m in wanted if m["name"] not in
               result["metrics"]]
    if missing:
        C.say(f"  not available in this run: {' '.join(missing)}")
    result["device"] = dev
    return result


def _per_layer(run, out, win, wanted, keep_trace, memory_peak_bytes):
    """Reduce the trace, gather lanes and counters, and ask each
    per-layer metric's reader for its number."""
    from mxnet_tpu import telemetry
    cell, rec = run.cell, run.rec
    reduce = C.load_py(os.path.join(cell.root, "benchmark", "trace",
                                    "reduce.py"), "benchmark_trace_reduce")
    files = sorted(glob.glob(os.path.join(
        run.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise C.BenchFailure("the profiler wrote no .xplane.pb")
    if keep_trace:
        os.makedirs(keep_trace, exist_ok=True)
        shutil.copy(files[-1], os.path.join(
            keep_trace, cell.name + ".xplane.pb"))
    trace = reduce.reduce(reduce.load_xplane(files[-1]),
                          n_devices=cell.chips,
                          steps_per_sync=rec.k)
    lanes1 = telemetry.step_breakdown()
    lanes = None
    if run.lanes0 is not None and lanes1["steps"] > run.lanes0["steps"]:
        lanes = {"wall_s": lanes1["wall_s"] - run.lanes0["wall_s"],
                 "steps": lanes1["steps"] - run.lanes0["steps"],
                 "lanes": {k: v - run.lanes0["lanes"].get(k, 0.0)
                           for k, v in lanes1["lanes"].items()}}
        C.say(f"  telemetry lanes cover "
              f"{100 * sum(lanes['lanes'].values()) / lanes['wall_s']:.1f} "
              f"% of the {lanes['wall_s']:.2f} s of wall they were taken "
              f"over ({lanes['steps']} steps)")
    kind = run.devices[0].device_kind
    data = {
        "trace": trace, "lanes": lanes, "window": win,
        "counters": dict(out.get("counters", {}),
                         compiles_in_window=rec.compiles_in_window(),
                         **{"setup_" + k: v for k, v in
                            run.setup_counts.items()}),
        "cell": {"name": cell.name, "chips": cell.chips,
                 "batch": run.batch, "steps_per_sync": rec.k,
                 # 2 per multiply-accumulate, training = 3 x forward
                 "flops_per_image": 6 * run.cfgmod.macs_per_image(
                     run.cfg, run.job["build"]),
                 "peak_flops": None if run.dry else C.peak_flops(
                     kind, cell.root)},
        "memory_peak_bytes": memory_peak_bytes,
    }
    values = {m["name"]: cell.reader(m["name"])(data) for m in wanted}
    return values, {
        "device": {"busy_s": trace["busy_s"], "window_s": trace["window_s"]},
        "breakdown": {"device_ops": trace["device_ops"][:10],
                      "idle_gaps": trace["idle_gaps"][:10]}}


def _prepare_environment(cell):
    """Before jax or mxnet_tpu is imported: the compile cache at a fixed
    path inside the checkout unless one is placed from outside, jax's
    persistence floors off (every program of a second run is a cache
    read; see PERF.md for the floor measured both ways), and the job's
    own environment (``MXNET_SCAN_STEPS`` for the scanned cell)."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(cell.root, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for key, value in cell.job.get("env", {}).items():
        os.environ[key] = str(value)
    if cell.root not in sys.path:
        sys.path.insert(0, cell.root)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)

    try:
        cell = C.Cell(args.workload)
        _prepare_environment(cell)
        import jax
        devs = jax.devices()
        dev = devs[0]
        C.say(f"[{cell.name}] jax {jax.__version__} platform={dev.platform} "
              f"device_kind={dev.device_kind} count={len(devs)} "
              f"seed={args.seed} seconds={args.seconds:g} trace={args.trace}")
        if dev.platform != "tpu":
            raise C.BenchFailure(
                f"needs a TPU, jax found platform {dev.platform!r} "
                f"({dev.device_kind}); there is no CPU mode")
        if len(devs) < cell.chips:
            raise C.BenchFailure(f"the cell asks for {cell.chips} chips, "
                                 f"jax found {len(devs)}")
        C.peak_flops(dev.device_kind, cell.root)
        import mxnet_tpu as mx
        devices = devs[:cell.chips]
        result = run_cell(cell, args.seed, args.seconds, args.trace,
                          devices, mx.tpu(0), keep_trace=args.keep_trace)
    except C.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    # a one-chip cell on a four-chip host reports the chips it used
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
