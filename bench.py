#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet-shape training throughput + MFU.

Baseline (BASELINE.md / reference docs/faq/perf.md:231-243):
ResNet-50 train @ bs32 fp32 on 1x V100 = 298.51 img/s.

TPU recipe: the whole train step (fwd+bwd+SGD-momentum update) is ONE
compiled XLA program; bf16 compute with fp32 master weights & BatchNorm
statistics (mxnet_tpu.amp recipe).  Model build / functionalization happens
on the host CPU backend with jit disabled so NOTHING compiles for the
device except the few programs we time.

Timing methodology.  Every timed call ends in a 4-byte device->host
transfer, and device step time is the DIFFERENCE quotient
(T(2K) - T(K)) / K of one jitted ``lax.fori_loop`` with a dynamic trip
count, which cancels the fixed per-dispatch cost.  On the v5e this repo
now runs on, ``block_until_ready`` IS a barrier (chip_smoke.py's clock
leg: a chained 4096^3 bf16 matmul timed around it lands under the table
peak and equals the transfer-synced time), so the transfer and the
differencing are no longer needed for correctness; collapsing the two
timing conventions into one helper is ROADMAP D4.

  * the K-step loop returns ONLY the final scalar loss — params never
    transfer back, so the transfer in the barrier is 4 bytes.
  * loop-carried sequential dependence (params_{i+1} = f(params_i)) makes
    the K iterations non-hoistable; fused-loop correctness was verified
    against K sequential single-step calls (bit-identical losses).
  * MFU uses ANALYTIC model FLOPs (ResNet-50 v1 fwd = 2*MACs =
    7.72 GFLOP/img at 224x224, train = 3x fwd) — the standard
    convention; XLA's compiled.cost_analysis() is reported alongside
    for diagnosis.
  * BOTH MFU ratios are emitted: "mfu_table" (vs the public table number
    for the reported device_kind) and "mfu_calibrated" (vs the measured
    matmul peak); headline "mfu" uses the larger denominator
    (conservative).  MFU > 1.0 is reported as an "anomaly", never as mfu.
  * remat is OFF by default at every batch size.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", "mfu", ...}
Needs a TPU: where jax finds none, on an unknown ``device_kind`` and on
any failed device phase it exits non-zero and prints no line.

Env knobs: BENCH_DTYPE, BENCH_K (steps per timed dispatch, default 8),
BENCH_TIME_BUDGET (s), BENCH_BATCH, BENCH_BATCH2 (second MFU point, 0
disables), BENCH_CALIB_N (comma-separated matmul sizes, default
"4096,8192"), BENCH_CALIB_REPS (base rep count R; timing differences 2R vs
R, default 40), BENCH_REMAT_FROM_BS (rematerialize at batch >= this; 0 =
never, the default).
"""
import json
import os
import sys
import time

BASELINE_IMG_S = 298.51
# ResNet-50 v1, 224x224, fwd pass: gluon resnet50_v1 = 3.86 GMACs
# (torchvision's 4.09 is the v1.5 variant), and model FLOPs = 2*MACs =
# 7.72e9/img.  Training step ~= 3x forward.
#
# ROUND-5 CORRECTION: r2-r4 used 3.86e9 here — the MAC count, not
# 2*MACs — understating every reported MFU by exactly 2x.  The HLO-level
# audit (tools/hlo_flops.py) shows the compiled step executes 1.09x the
# 2*MAC analytic (the 9% being stride-2 backward-data convs XLA charges
# over the zero-dilated input), so cost_analysis ~715 GF @ bs32 vs
# 3*7.72e9*32 = 741 GF analytic was never a 2x waste: r4's honest
# "mfu 0.135" was really ~0.27.
ANALYTIC_FWD_FLOPS_PER_IMG = 7.72e9
T_START = time.perf_counter()


def log(msg):
    print(f"[bench +{time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def emit(payload):
    print(json.dumps(payload), flush=True)


# bf16 peak FLOP/s by TPU generation (public numbers).
_PEAK_FLOPS = [
    ("v2", 45e12), ("v3", 123e12), ("v4", 275e12),
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12), ("v6", 918e12), ("trillium", 918e12),
]


def peak_flops_for(device_kind: str):
    dk = device_kind.lower()
    for key, val in _PEAK_FLOPS:
        if key in dk:
            return val, key
    raise ValueError(f"no table peak for device_kind {device_kind!r}: add "
                     "it to _PEAK_FLOPS with its source")


def calibrate_peak(dev, reps=None):
    """Empirical peak bf16 FLOP/s: chained NxN matmuls on-device.

    One compiled program with a dynamic rep count; timed by transferring a
    scalar element of the result; per-matmul time is (T(2R) - T(R)) / R so
    the fixed per-dispatch cost cancels.  chip_smoke.py's clock leg times
    the same chain around block_until_ready, which is a barrier on the
    v5e.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    reps = reps or int(os.environ.get("BENCH_CALIB_REPS", 40))
    sweep_env = os.environ.get("BENCH_CALIB_N", "4096,8192")
    sizes = [int(s) for s in str(sweep_env).split(",") if s.strip()]
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 1200))
    key = jax.random.PRNGKey(0)
    sweep = {}
    best = 0.0

    for n in sizes:
        if time.perf_counter() - T_START > budget * 0.85:
            sweep[f"skipped_{n}"] = "time budget"
            continue
        @jax.jit
        def init(k, n=n):
            ka, kb = jax.random.split(k)
            a = jax.random.normal(ka, (n, n), jnp.bfloat16)
            b = jax.random.normal(kb, (n, n), jnp.bfloat16)
            return a, b

        @jax.jit
        def chain(r, salt, a, b):
            # b_{i+1} = a @ b_i: sequential dependence, nothing hoistable;
            # returns one scalar so the sync transfer is 4 bytes.
            # salt: a fresh live input per call, so no layer between the
            # caller and the chip can serve a repeated execution from a
            # cache
            def body(_, ab):
                a_, b_ = ab
                return a_, a_ @ b_
            b = b + (salt * 1e-30).astype(b.dtype)
            out = lax.fori_loop(0, r, body, (a, b))[1]
            return out[0, 0].astype(jnp.float32)

        # placement follows the committed key (jit(device=) is deprecated)
        a, b = init(jax.device_put(key, dev))
        float(chain(jnp.int32(2), jnp.float32(1), a, b))  # compile + warm
        calls = [1]

        def timed(r, tries=3):
            ts = []
            for _ in range(tries):
                calls[0] += 1
                t0 = time.perf_counter()
                float(chain(jnp.int32(r), jnp.float32(calls[0]), a, b))
                ts.append(time.perf_counter() - t0)
            return min(ts)

        t1 = timed(reps)
        t2 = timed(2 * reps)
        per_matmul = (t2 - t1) / reps
        if per_matmul <= 0:
            sweep[n] = {"anomaly": f"T(2R)={t2:.4f}s <= T(R)={t1:.4f}s"}
            continue
        fl = 2.0 * n * n * n / per_matmul
        sweep[n] = {"tflops": round(fl / 1e12, 2),
                    "ms_per_matmul": round(per_matmul * 1e3, 4),
                    "fixed_overhead_ms": round(
                        (t1 - per_matmul * reps) * 1e3, 1)}
        best = max(best, fl)
    return best, {"base_reps": reps, "method": "transfer-sync differenced",
                  "sweep": sweep}


def measure_checkpoint():
    """Time-to-safe metrics: how long a checkpoint save blocks the train
    loop (async manager: device->host snapshot only) vs the equivalent
    synchronous save, and restore latency — on BENCH_CKPT_MB of state.

    Emits ckpt_save_blocking_ms (async headline), ckpt_save_sync_ms
    (the serialize+sha256+fsync+commit cost the writer thread hides),
    blocking_fraction, and ckpt_restore_s (checksum-verified load).
    Best-of-3 each, so one fs hiccup doesn't skew the trajectory.
    """
    import shutil
    import tempfile

    import numpy as np
    from mxnet_tpu import config as mxcfg
    from mxnet_tpu.checkpoint import CheckpointManager

    mb = max(1, mxcfg.get("BENCH_CKPT_MB"))
    n = mb * 1024 * 1024 // 4 // 8
    arrays = {f"w{i}": np.random.randn(n).astype(np.float32)
              for i in range(8)}
    nbytes = sum(a.nbytes for a in arrays.values())
    root = tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        sync_ms, blocking_ms, restore_s = [], [], []
        with CheckpointManager(os.path.join(root, "sync"), keep_last=1,
                               async_save=False) as mgr:
            for i in range(3):
                t0 = time.perf_counter()
                mgr.save(i + 1, arrays=arrays, block=True)
                sync_ms.append((time.perf_counter() - t0) * 1e3)
        with CheckpointManager(os.path.join(root, "async"), keep_last=1,
                               async_save=True) as mgr:
            for i in range(3):
                t0 = time.perf_counter()
                mgr.save(i + 1, arrays=arrays)  # returns after the snapshot
                blocking_ms.append((time.perf_counter() - t0) * 1e3)
                mgr.wait()
            for _ in range(3):
                t0 = time.perf_counter()
                mgr.restore()  # checksum-verified
                restore_s.append(time.perf_counter() - t0)
        blk, syn = min(blocking_ms), min(sync_ms)
        return {
            "metric": "ckpt_save_blocking_ms",
            "value": round(blk, 2),
            "ckpt_save_sync_ms": round(syn, 2),
            "blocking_fraction": round(blk / syn, 4) if syn else None,
            "ckpt_restore_s": round(min(restore_s), 4),
            "state_bytes": nbytes,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def measure_serving():
    """Inference serving throughput: ResNet-18 through the DynamicBatcher
    under synthetic Poisson arrivals (open loop).

    Three phases: (1) warm the full bucket so the XLA compile is outside
    the window; (2) a short closed-loop probe to find the saturated
    throughput; (3) a BENCH_SERVE_SECONDS open-loop run with exponential
    inter-arrivals at BENCH_SERVE_RATE (0 = auto: 1.2x the probe, i.e.
    deliberately slightly over capacity so queueing + shedding engage).
    Headline value is completed img/s over the open-loop window; p50/p99
    and batch occupancy come from serving metrics.
    """
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import config as mxcfg
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import vision

    max_batch = mxcfg.get("BENCH_SERVE_BATCH")
    lat_ms = mxcfg.get("BENCH_SERVE_LATENCY_MS")
    seconds = mxcfg.get("BENCH_SERVE_SECONDS")
    rate = mxcfg.get("BENCH_SERVE_RATE")

    net = vision.resnet18_v1()
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((1, 3, 224, 224)))  # materialize deferred-init params
    server = serving.ModelServer(
        max_batch_size=max_batch, max_latency_ms=lat_ms,
        max_queue_depth=max(256, 4 * max_batch), name="bench")
    server.load("resnet18", block=net)
    sample = np.random.randn(3, 224, 224).astype(np.float32)

    def fire(n):
        futs = []
        for _ in range(n):
            futs.append(server.predict_async("resnet18", {"data": sample}))
        for f in futs:
            f.result(600)

    log(f"[serving] warmup: bucket {max_batch} compile + first batch")
    fire(max_batch)
    t0 = time.perf_counter()
    fire(4 * max_batch)
    probe_rps = 4 * max_batch / (time.perf_counter() - t0)
    lam = rate or 1.2 * probe_rps
    log(f"[serving] probe {probe_rps:.1f} img/s closed-loop; "
        f"Poisson arrivals at {lam:.1f} req/s for {seconds:.0f}s")

    rng = np.random.default_rng(0)
    futures, shed = [], 0
    t_begin = time.perf_counter()
    t_next, t_end = t_begin, t_begin + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        t_next += rng.exponential(1.0 / lam)
        if t_next > now:
            time.sleep(t_next - now)
        try:
            futures.append(
                server.predict_async("resnet18", {"data": sample}))
        except serving.ServingOverloadError:
            shed += 1
    completed = 0
    for f in futures:
        try:
            f.result(600)
            completed += 1
        except Exception:
            pass
    elapsed = time.perf_counter() - t_begin
    snap = server.stats()
    server.shutdown()
    return {
        "metric": "resnet18_serve_img_per_sec",
        "value": round(completed / elapsed, 2),
        "unit": "img/s",
        "window_s": round(elapsed, 2),
        "arrival_rate_rps": round(lam, 2),
        "probe_closed_loop_rps": round(probe_rps, 2),
        "offered": len(futures) + shed,
        "completed": completed,
        "shed": shed,
        "p50_ms": snap["latency_ms"]["p50"],
        "p99_ms": snap["latency_ms"]["p99"],
        "batch_occupancy": snap.get("batch_occupancy"),
        "max_batch_size": max_batch,
        "max_latency_ms": lat_ms,
    }


def _module_steps(symbol, data_shape, fused, steps, warmup=2,
                  optimizer_params=None):
    """Train `steps` Module steps on CPU; returns (ms/step,
    dispatches/step).  Runs on mx.cpu(): a count and a host
    wall time, never a device metric."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio, profiler as prof

    os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
    bs = data_shape[0]
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(*data_shape).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 10, bs).astype(np.float32))
    batch = mxio.DataBatch(data=[x], label=[y])
    mod = mx.mod.Module(symbol, context=mx.cpu())
    mod.bind(data_shapes=[("data", x.shape)],
             label_shapes=[("softmax_label", y.shape)])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=optimizer_params or
                       {"learning_rate": 0.01, "momentum": 0.9})
    probe = mod._exec.arg_dict[mod._param_names[0]]
    for _ in range(warmup):
        mod.forward_backward(batch)
        mod.update()
    mod._exec.arg_dict[mod._param_names[0]]._data.block_until_ready()
    prof.reset_dispatch_counts()
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
    mod._exec.arg_dict[mod._param_names[0]]._data.block_until_ready()
    ms = (time.perf_counter() - t0) / steps * 1e3
    disp = prof.dispatch_counts().get("total", 0) / steps
    del probe
    return ms, disp


def measure_telemetry_overhead():
    """Disabled-path cost of one telemetry.span (ISSUE 5): the span
    tracer annotates fit/serving hot loops unconditionally, so the
    disabled path must stay well under 1 us — this phase keeps that
    budget measured alongside the step-time numbers it protects."""
    import time as _t

    from mxnet_tpu import telemetry
    was_enabled = telemetry.enabled()
    telemetry.disable()
    try:
        n = 50000
        best = float("inf")
        for _ in range(3):
            t0 = _t.perf_counter()
            for _ in range(n):
                with telemetry.span("bench/noop"):
                    pass
            best = min(best, (_t.perf_counter() - t0) / n)
    finally:
        if was_enabled:
            telemetry.enable()
    return {"telemetry": {"metric": "telemetry_disabled_span_ns",
                          "value": round(best * 1e9, 1), "unit": "ns",
                          "budget_ns": 1000}}


def measure_trace_overhead():
    """Disabled-path cost of the ISSUE-12 observability hooks: one
    trace start+stage (the per-request/per-window tracing) plus one
    flight-recorder record (the decision-event ring).  Both are wired
    into hot paths unconditionally, so — like a disabled span or chaos
    failpoint — the off path must stay well under 1 us per event."""
    import time as _t

    from mxnet_tpu.telemetry import flight, trace

    was_trace = trace.enabled()
    was_flight = flight.enabled()
    trace.disable()
    flight.disable()
    try:
        n = 50000
        best = float("inf")
        for _ in range(3):
            t0 = _t.perf_counter()
            for _ in range(n):
                tr = trace.start("bench")
                with tr.stage("noop"):
                    pass
                flight.record("bench", "noop", value=1)
            # three hook events per iteration: start+stage, record
            best = min(best, (_t.perf_counter() - t0) / (3 * n))
    finally:
        if was_trace:
            trace.enable()
        if was_flight:
            flight.enable()
    return {"trace": {"metric": "trace_disabled_overhead_ns",
                      "value": round(best * 1e9, 1), "unit": "ns",
                      "budget_ns": 1000}}


def measure_alert_overhead():
    """ISSUE-13 observatory overheads, three numbers:

    * ``alert_tick_overhead_us`` — one evaluation pass of the DEFAULT
      rule pack on an armed engine (< 1 ms: the engine may tick at 1 Hz
      on a serving box without showing up in p99);
    * ``resource_sample_overhead_us`` — one host resource sample
      (RSS + fds + threads; < 1 ms for the same reason — checkpoint-dir
      disk walks excluded here, they are sampled on the slow thread);
    * ``alerts_disabled_tick_ns`` — the module-level tick with the
      engine DISARMED (< 1 µs, the span/trace/failpoint bar: callers
      may pulse it opportunistically from hot paths)."""
    import time as _t

    from mxnet_tpu.telemetry import alerts, resources

    # disabled path first: module state must be pristine
    assert not alerts.enabled()
    n = 50000
    best_off = float("inf")
    for _ in range(3):
        t0 = _t.perf_counter()
        for _ in range(n):
            alerts.tick()
        best_off = min(best_off, (_t.perf_counter() - t0) / n)

    eng = alerts.AlertEngine()  # the default pack, real sampler
    eng.tick()  # warm: metric families + probes resolve once
    best_tick = float("inf")
    for _ in range(5):
        t0 = _t.perf_counter()
        eng.tick()
        best_tick = min(best_tick, _t.perf_counter() - t0)

    best_sample = float("inf")
    for _ in range(5):
        t0 = _t.perf_counter()
        resources.sample_now(disk=False)
        best_sample = min(best_sample, _t.perf_counter() - t0)

    return {
        "alerts": {"metric": "alert_tick_overhead_us",
                   "value": round(best_tick * 1e6, 2), "unit": "us",
                   "budget_us": 1000,
                   "disabled_tick_ns": round(best_off * 1e9, 1),
                   "disabled_budget_ns": 1000},
        "resource_sample": {"metric": "resource_sample_overhead_us",
                            "value": round(best_sample * 1e6, 2),
                            "unit": "us", "budget_us": 1000},
    }


def measure_degraded_p99():
    """CPU-only host phase ``degraded_p99_ms`` (ISSUE 8): serving p99
    with one of two batcher workers WEDGED (chaos failpoint) versus
    healthy, with load shedding live.  Opara's concurrency argument cut
    down to a gate: a wedged worker must degrade p99 by at most 3x —
    the healthy worker + the bounded queue + shedding absorb the loss,
    they don't queue it.  Pure-host numpy runner: no device."""
    import threading as _th
    import time as _t

    import numpy as _np

    import mxnet_tpu.chaos as _chaos
    from mxnet_tpu.serving.batcher import (DynamicBatcher,
                                           RequestTimeoutError,
                                           ServingOverloadError)

    w = _np.random.RandomState(0).randn(64, 64).astype(_np.float32) * 0.1

    def runner(feed, n_real):
        _t.sleep(0.002)  # a ~2 ms model: service time dominates jitter
        return [feed["x"] @ w]

    def drive(batcher, seconds, n_clients=8):
        lat_ms, sheds, timeouts, failures = [], [0], [0], []
        stop = _t.perf_counter() + seconds
        lock = _th.Lock()

        def client():
            x = _np.ones((64,), _np.float32)
            while _t.perf_counter() < stop:
                t0 = _t.perf_counter()
                try:
                    # per-request deadline: requests claimed by a wedged
                    # worker resolve as typed RequestTimeoutError via the
                    # in-flight sweep — degraded mode sheds and times
                    # out, it never silently loses a request
                    batcher.submit({"x": x},
                                   timeout_ms=500.0).result(10.0)
                    with lock:
                        lat_ms.append((_t.perf_counter() - t0) * 1e3)
                except ServingOverloadError:
                    with lock:
                        sheds[0] += 1
                    _t.sleep(0.001)
                except RequestTimeoutError:
                    with lock:
                        timeouts[0] += 1
                except Exception as e:  # non-shed failure: gate-fatal
                    with lock:
                        failures.append(f"{type(e).__name__}: {e}")
            return None

        threads = [_th.Thread(target=client) for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lat_ms.sort()
        p99 = lat_ms[min(len(lat_ms) - 1,
                         int(0.99 * (len(lat_ms) - 1)))] if lat_ms else None
        return {"p99_ms": p99, "served": len(lat_ms), "shed": sheds[0],
                "timeouts": timeouts[0], "failures": failures}

    kw = dict(max_batch_size=8, max_latency_ms=2.0, num_workers=2,
              max_queue_depth=64, shed_watermark=16)
    healthy_b = DynamicBatcher(runner, name="bench-healthy", **kw)
    try:
        drive(healthy_b, 0.5)  # warm the code paths
        healthy = drive(healthy_b, 2.0)
    finally:
        healthy_b.close()

    _chaos.reset()
    _chaos.arm("serving/batcher/worker", "wedge", hits=1, count=1)
    degraded_b = DynamicBatcher(runner, name="bench-degraded", **kw)
    try:
        degraded = drive(degraded_b, 2.0)
    finally:
        _chaos.release("serving/batcher/worker")
        _chaos.reset()
        degraded_b.close()

    bar = 3.0
    ratio = (degraded["p99_ms"] / healthy["p99_ms"]
             if healthy["p99_ms"] and degraded["p99_ms"] else None)
    return {"degraded": {
        "metric": "degraded_p99_ms",
        "value": degraded["p99_ms"], "unit": "ms",
        "healthy_p99_ms": healthy["p99_ms"],
        "ratio_vs_healthy": round(ratio, 3) if ratio else None,
        "bar_ratio": bar,
        "served_degraded": degraded["served"],
        "shed_degraded": degraded["shed"],
        "timeouts_degraded": degraded["timeouts"],
        "non_shed_failures": degraded["failures"] + healthy["failures"],
        "passed": bool(ratio is not None and ratio <= bar
                       and not degraded["failures"]
                       and not healthy["failures"]),
    }}


def measure_serve_pool():
    """CPU-only host phases ``serve_sustained_img_per_sec`` and
    ``serve_spike_p99_ms`` (ISSUE 10): replica-pool serving vs the
    single batcher, and tail latency under a 10x Poisson load spike.

    Runner is pure-host (per-item sleep — models per-sample device
    compute, releases the GIL so replicas genuinely overlap): no
    device.  Gates:

    * sustained: a BENCH_SERVE_SPIKE_REPLICAS-replica pool sustains
      >= 2x the closed-loop throughput of the single batcher;
    * spike: with SLO admission armed (slo self-tuned to 2.5x the
      measured steady p99), the p99 of ADMITTED requests inside a
      BENCH_SERVE_SPIKE_X (10x) arrival spike stays <= 3x the
      steady-state p99, every refusal is a typed ServingOverloadError,
      and zero admitted requests time out or drop.
    """
    import sys as _sys
    import threading as _th
    import time as _t

    import numpy as _np

    from mxnet_tpu import config as mxcfg
    from mxnet_tpu.serving.batcher import (RequestTimeoutError,
                                           ServingOverloadError)
    from mxnet_tpu.serving.metrics import ServingMetrics
    from mxnet_tpu.serving.router import ReplicaPool

    # a 10x-overload submit loop degenerates into a GIL-hogging tight
    # loop at the default 5 ms switch interval, starving the dispatch
    # threads it is supposed to measure — a load-GENERATOR artifact.
    # Real clients live on other hosts; shrink the GIL slice so the
    # in-process generator approximates them.
    prev_switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.0005)

    n_replicas = max(2, mxcfg.get("BENCH_SERVE_SPIKE_REPLICAS"))
    steady_s = float(mxcfg.get("BENCH_SERVE_SPIKE_SECONDS"))
    spike_x = float(mxcfg.get("BENCH_SERVE_SPIKE_X"))

    def factory(rid):
        def run(feed, n_real):
            # a ~2 ms/sample model: service time dominates framework
            # overhead (the regime replica scaling is for), and the
            # per-sample cost is what makes the >= 2x pool gate measure
            # added CAPACITY rather than batching-overhead amortization
            _t.sleep(0.002 * n_real + 0.001)
            return [feed["x"] * 2.0]
        return run

    kw = dict(max_batch_size=8, max_latency_ms=2.0, num_workers=1,
              max_queue_depth=256, shed_watermark=128)

    def closed_loop(pool, seconds, n_clients=16):
        done = [0]
        lock = _th.Lock()
        stop = _t.perf_counter() + seconds

        def client():
            x = _np.ones((16,), _np.float32)
            while _t.perf_counter() < stop:
                try:
                    pool.submit({"x": x}).result(10.0)
                    with lock:
                        done[0] += 1
                except ServingOverloadError:
                    _t.sleep(0.001)

        threads = [_th.Thread(target=client) for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return done[0] / seconds

    # -- sustained: single batcher vs replica pool (closed loop) ---------
    single = ReplicaPool(factory, num_replicas=1, name="bench-single",
                         model="bench-single",
                         metrics=ServingMetrics("bench-single"), **kw)
    try:
        closed_loop(single, 0.4)  # warm the code paths
        single_rps = closed_loop(single, steady_s)
    finally:
        single.close()
    pool_metrics = ServingMetrics("bench-pool")
    pool = ReplicaPool(factory, num_replicas=n_replicas,
                       name="bench-pool", model="bench-pool",
                       metrics=pool_metrics, **kw)
    sustained_rps = closed_loop(pool, steady_s, n_clients=8 * n_replicas)
    sustained_bar = 2.0
    sustained = {
        "metric": "serve_sustained_img_per_sec",
        "value": round(sustained_rps, 1), "unit": "img/s",
        "single_batcher_img_per_sec": round(single_rps, 1),
        "ratio_vs_single": round(sustained_rps / max(single_rps, 1e-9), 2),
        "replicas": n_replicas,
        "bar_ratio": sustained_bar,
        "passed": bool(sustained_rps >= sustained_bar * single_rps),
    }

    # -- spike: Poisson steady window, then a 10x window -----------------
    def open_loop(seconds, lam):
        """Poisson arrivals at ``lam``; returns (submitted futures,
        sheds, other-typed-refusals)."""
        rng = _np.random.default_rng(0)
        x = _np.ones((16,), _np.float32)
        futures, sheds, refused = [], 0, []
        t_next = _t.perf_counter()
        t_end = t_next + seconds
        while True:
            now = _t.perf_counter()
            if now >= t_end:
                return futures, sheds, refused
            t_next += rng.exponential(1.0 / lam)
            # open-loop discipline: arrivals the generator could not
            # keep up with are dropped from the schedule, not burst as
            # a GIL-bound backlog (the rate cap is the generator's)
            t_next = max(t_next, now - 0.002)
            if t_next > now:
                _t.sleep(t_next - now)
            try:
                futures.append(pool.submit({"x": x}, timeout_ms=1000.0))
            except ServingOverloadError:
                sheds += 1
            except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                refused.append(f"{type(e).__name__}: {e}")

    def settle(futures):
        """Resolve every submitted future; returns (ok, timeouts,
        failures) — an unresolved future is a DROP and gate-fatal."""
        ok, timeouts, failures = 0, 0, []
        for f in futures:
            try:
                f.result(10.0)
                ok += 1
            except RequestTimeoutError:
                timeouts += 1
            except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                failures.append(f"{type(e).__name__}: {e}")
        return ok, timeouts, failures

    def p99(vals):
        vals.sort()
        return vals[min(len(vals) - 1,
                        int(0.99 * (len(vals) - 1)))] if vals else None

    try:
        steady_lam = 0.5 * sustained_rps
        pool_metrics.drain_latencies()
        futs, steady_sheds, steady_refused = open_loop(steady_s,
                                                       steady_lam)
        s_ok, s_to, s_fail = settle(futs)
        steady_p99 = p99(pool_metrics.drain_latencies())
        # arm SLO admission, self-tuned from the measured steady p99:
        # the controller sheds on PREDICTED p99 so the spike's tail is
        # bounded by refusals, not by queueing (2.0x leaves the last
        # admitted request's own service time inside the 3x gate)
        slo_ms = max(10.0, 2.0 * (steady_p99 or 10.0))
        pool.admission.slo_p99_ms = slo_ms
        futs, spike_sheds, spike_refused = open_loop(
            max(1.0, steady_s / 2), spike_x * steady_lam)
        k_ok, k_to, k_fail = settle(futs)
        spike_p99 = p99(pool_metrics.drain_latencies())
    finally:
        pool.close()
        _sys.setswitchinterval(prev_switch)

    bar = 3.0
    ratio = (spike_p99 / steady_p99
             if steady_p99 and spike_p99 else None)
    spike = {
        "metric": "serve_spike_p99_ms",
        "value": spike_p99, "unit": "ms",
        "steady_p99_ms": steady_p99,
        "ratio_vs_steady": round(ratio, 3) if ratio else None,
        "bar_ratio": bar,
        "spike_x": spike_x,
        "steady_rate_rps": round(steady_lam, 1),
        "slo_p99_ms": round(slo_ms, 1),
        "served_steady": s_ok, "served_spike": k_ok,
        "shed_steady": steady_sheds, "shed_spike": spike_sheds,
        "timeouts": s_to + k_to,
        "non_shed_failures": (steady_refused + spike_refused
                              + s_fail + k_fail),
        "passed": bool(ratio is not None and ratio <= bar
                       and spike_sheds > 0
                       and s_to + k_to == 0
                       and not (steady_refused + spike_refused
                                + s_fail + k_fail)),
    }
    return {"serve_sustained": sustained, "serve_spike": spike}


def measure_generation():
    """CPU-only host phases ``generate_tokens_per_sec`` and
    ``generate_p99_intertoken_ms`` (ISSUE 16): stateful autoregressive
    sessions over the paged-KV GenerationEngine under Poisson arrivals.

    Runner is pure-host (``tiny_lm(jit=False)`` with a fixed
    per-decode-tick sleep — models a fixed per-step device cost that
    the whole slot cohort SHARES, which is exactly what continuous
    decode batching amortizes): no device.  Gates:

    * batching: the multi-slot engine sustains >= 1.5x the token
      throughput of a closed-loop single-session run (same model, same
      per-tick cost) — continuous decode batching must buy capacity;
    * prefix reuse: with half the arrivals sharing a common prompt
      head, the content-hash prefix cache ends the run with a hit rate
      >= 0.25 (hits / lookups);
    * health: zero non-shed session failures, and every intertoken
      gap sampled on the engine's emit path lands in the reservoir
      (p99 reported as ``generate_p99_intertoken_ms``).
    """
    import threading as _th
    import time as _t

    import numpy as _np

    from mxnet_tpu import config as mxcfg
    from mxnet_tpu.serving.batcher import (RequestTimeoutError,
                                           ServingOverloadError)
    from mxnet_tpu.serving.generation import GenerationEngine, tiny_lm

    seconds = float(mxcfg.get("BENCH_GENERATE_SECONDS"))
    rate = float(mxcfg.get("BENCH_GENERATE_RATE"))
    max_new = max(2, mxcfg.get("BENCH_GENERATE_TOKENS"))
    tick_s = 0.0005   # modeled fixed device cost per decode dispatch
    slots = 8

    def build_engine(name, prefix_entries):
        return GenerationEngine(
            tiny_lm(vocab=64, d_model=16, max_len=256, seed=0, jit=False,
                    per_token_cost_s=tick_s),
            name=name, slots=slots, page_tokens=16, kv_budget_mb=16,
            prefix_cache_entries=prefix_entries, max_len=256,
            session_timeout_s=60.0)

    rng = _np.random.default_rng(0)
    shared = rng.integers(1, 63, size=32).astype(_np.int32)

    def prompt_for(i):
        tail = rng.integers(1, 63, size=int(rng.integers(2, 10)))
        tail = tail.astype(_np.int32)
        return _np.concatenate([shared, tail]) if i % 2 else tail

    # -- closed-loop single session: the unbatched baseline --------------
    single = build_engine("bench-gen-single", prefix_entries=0)
    single.warm()
    try:
        t_end = _t.perf_counter() + max(0.5, seconds / 2)
        single_tokens, i = 0, 0
        t0 = _t.perf_counter()
        while _t.perf_counter() < t_end:
            single_tokens += len(single.generate(
                prompt_for(i), max_new_tokens=max_new))
            i += 1
        single_tps = single_tokens / (_t.perf_counter() - t0)
    finally:
        single.close()

    # -- open loop: Poisson session arrivals against the full engine -----
    eng = build_engine("bench-gen", prefix_entries=32)
    eng.warm()
    # default rate: ~60% of the slot pool's modeled token capacity
    lam = rate or 0.6 * slots * single_tps / max_new
    sessions, sheds, refused = [], 0, []
    try:
        t_next = _t.perf_counter()
        t_end = t_next + seconds
        i = 0
        while True:
            now = _t.perf_counter()
            if now >= t_end:
                break
            t_next += rng.exponential(1.0 / lam)
            t_next = max(t_next, now - 0.002)  # open-loop discipline
            if t_next > now:
                _t.sleep(t_next - now)
            try:
                sessions.append(eng.start_session(
                    prompt_for(i), max_new_tokens=max_new))
            except ServingOverloadError:
                sheds += 1
            except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                refused.append(f"{type(e).__name__}: {e}")
            i += 1
        t0_drain = _t.perf_counter()
        ok, failures = 0, list(refused)
        for s in sessions:
            try:
                toks = s.result(timeout=30.0)
                ok += 1
                if len(toks) != max_new:
                    failures.append(f"short session: {len(toks)} tokens")
            except RequestTimeoutError:
                failures.append("session timed out (drop)")
            except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                failures.append(f"{type(e).__name__}: {e}")
        wall = t0_drain - (t_end - seconds)
        stats = eng.stats()
        gaps = sorted(eng.metrics.drain_observations("intertoken_ms"))
        p99_inter = (gaps[min(len(gaps) - 1, int(0.99 * (len(gaps) - 1)))]
                     if gaps else None)
        tps = stats["tokens_emitted"] / max(wall, 1e-9)
    finally:
        eng.close()

    px = stats["prefix_cache"]
    lookups = px["hits"] + px["misses"]
    hit_rate = px["hits"] / lookups if lookups else 0.0
    ratio = tps / max(single_tps, 1e-9)
    throughput = {
        "metric": "generate_tokens_per_sec",
        "value": round(tps, 1), "unit": "tok/s",
        "single_session_tok_per_sec": round(single_tps, 1),
        "ratio_vs_single": round(ratio, 2),
        "bar_ratio": 1.5,
        "slots": slots, "arrival_rate_sessions_per_s": round(lam, 1),
        "sessions_ok": ok, "sessions_shed": sheds,
        "max_active": stats["max_active"],
        "prefix_hit_rate": round(hit_rate, 3),
        "prefix_hit_bar": 0.25,
        "non_shed_failures": failures,
        "passed": bool(ratio >= 1.5 and hit_rate >= 0.25
                       and ok > 0 and not failures),
    }
    intertoken = {
        "metric": "generate_p99_intertoken_ms",
        "value": round(p99_inter, 3) if p99_inter is not None else None,
        "unit": "ms",
        "samples": len(gaps),
        "modeled_tick_ms": tick_s * 1e3,
        "passed": bool(p99_inter is not None),
    }
    return {"generate_throughput": throughput,
            "generate_intertoken": intertoken}


_COLD_START_CHILD = r'''
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import compile as mxc
from mxnet_tpu import serving

LAYERS, WIDTH, IN_DIM = 24, 128, 64

def build():
    h = mx.sym.Variable("data")
    for i in range(LAYERS):
        h = mx.sym.FullyConnected(h, num_hidden=WIDTH, name=f"fc{i}")
        h = mx.sym.Activation(h, act_type="relu")
    return mx.sym.FullyConnected(h, num_hidden=10, name="out")

rng = np.random.RandomState(0)
params, prev = {}, IN_DIM
for i in range(LAYERS):
    params[f"fc{i}_weight"] = mx.nd.array(
        rng.randn(WIDTH, prev).astype(np.float32) * 0.05)
    params[f"fc{i}_bias"] = mx.nd.zeros((WIDTH,))
    prev = WIDTH
params["out_weight"] = mx.nd.array(
    rng.randn(10, prev).astype(np.float32) * 0.05)
params["out_bias"] = mx.nd.zeros((10,))

server = serving.ModelServer(max_batch_size=8, name="coldstart")
server.load("mlp", symbol=build(), params=params)
x = rng.randn(IN_DIM).astype(np.float32)
t0 = time.perf_counter()
server.predict("mlp", {"data": x}, wait_s=600.0)
first_ms = (time.perf_counter() - t0) * 1e3
counts = mxc.LEDGER.counts()
print(json.dumps({"first_request_ms": round(first_ms, 2),
                  "compiles": mxc.LEDGER.compiles(),
                  "jax": counts["jax"]}))
server.shutdown()
'''


def measure_cold_start():
    """CPU-only phase ``cold_start_first_request_ms`` (ISSUE 7):
    time-to-first-response of a freshly started serving process, with a
    cold persistent-cache dir vs a warm restart reusing it.

    Two identical subprocesses publish a 24-layer MLP and time the first
    ``predict``: the first populates ``MXNET_COMPILE_CACHE_DIR``, the
    second deserializes executables instead of compiling.  Gate: warm
    restart must be >= 2x faster to first response (the bar below), and
    the warm child's ledger must report 0 backend compiles.
    """
    import shutil
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="bench-coldstart-")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE="1",
               MXNET_COMPILE_CACHE_DIR=cache_dir,
               MXNET_COMPILE_CACHE_MIN_COMPILE_S="0")
    env.pop("XLA_FLAGS", None)  # single-device child, fastest startup
    # the child tests the cache mechanism against ITS fresh directory
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run_child(tag):
        t0 = time.perf_counter()
        proc = subprocess.run([sys.executable, "-c", _COLD_START_CHILD],
                              env=env, capture_output=True, text=True,
                              timeout=600)
        wall = time.perf_counter() - t0
        if proc.returncode != 0:
            raise RuntimeError(
                f"cold-start child ({tag}) failed: "
                f"{proc.stderr.strip()[-800:]}")
        payload = json.loads(proc.stdout.strip().splitlines()[-1])
        log(f"[cold_start] {tag}: first request "
            f"{payload['first_request_ms']:.0f} ms, "
            f"{payload['compiles']} compiles "
            f"(child wall {wall:.1f}s)")
        return payload

    try:
        cold = run_child("cold cache")
        warm = run_child("warm restart")
        speedup = cold["first_request_ms"] / max(1e-9,
                                                 warm["first_request_ms"])
        return {"cold_start": {
            "metric": "cold_start_first_request_ms",
            "value": warm["first_request_ms"],
            "unit": "ms",
            "cold_first_request_ms": cold["first_request_ms"],
            "speedup_warm_vs_cold": round(speedup, 2),
            "bar_speedup": 2.0,
            "passed": speedup >= 2.0,
            "warm_backend_compiles": warm["compiles"],
            "cold_backend_compiles": cold["compiles"],
            "model": "mlp24x128 via ModelServer",
        }}
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def measure_multichip():
    """CPU-only phase for the mesh fused distributed step
    (ISSUE 9): a subprocess forced to 8 fake CPU devices runs
    ``python -m mxnet_tpu.parallel.fused --bench-json`` — a dp=2,tp=2
    Module.fit with a dist_device_sync kvstore routed through the
    donated shard_map window.

    * ``multichip_dispatches_per_step`` — gate <= (1+eps)/K at
      K=BENCH_MULTICHIP_K: one donated dispatch per K-step window.
    * ``multichip_comm_blocking_pct`` — gate <= 30: the differential
      between the bucketed-collective window and the same window with
      collectives compiled out isolates communication's share of step
      wall.
    """
    import subprocess

    from mxnet_tpu import config as mxcfg

    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               BENCH_MULTICHIP_K=str(mxcfg.get("BENCH_MULTICHIP_K")))
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.parallel.fused",
         "--bench-json"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(f"multichip child failed: "
                           f"{proc.stderr.strip()[-800:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    disp = payload["multichip_dispatches_per_step"]
    blocking = payload["multichip_comm_blocking_pct"]
    return {
        "multichip_dispatch": {
            "metric": "multichip_dispatches_per_step",
            "value": disp,
            "budget": payload["budget"],
            "gate_pass": bool(disp <= payload["budget"]),
            "k": payload["k"], "mesh": payload["mesh"],
            "note": "Module.fit dispatches/step with a dist_device_sync "
                    "kvstore on a dp=2,tp=2 fake-device mesh (one "
                    "donated shard_map window per K steps; the "
                    "per-param push/pull loop is off the hot path)",
        },
        "multichip_comm": {
            "metric": "multichip_comm_blocking_pct",
            "value": blocking,
            "budget_pct": payload["blocking_budget_pct"],
            "gate_pass": bool(blocking <= payload["blocking_budget_pct"]),
            "step_ms": payload["step_ms"],
            "step_ms_comm_off": payload["step_ms_comm_off"],
            "comm_standalone_ms_per_step":
                payload["comm_standalone_ms_per_step"],
            "note": "share of mesh step wall attributable to the "
                    "bucketed gradient collectives (differential vs "
                    "MXNET_COLLECTIVE_MODE=off)",
        },
    }


def measure_multihost():
    """CPU-only phases for the elastic multi-host runtime
    (ISSUE 11): a subprocess supervisor runs 2 worker processes × 4
    fake CPU devices each through ``python -m
    mxnet_tpu.parallel.elastic --bench-json``.

    * ``multihost_dispatches_per_step`` — gate <= (1+eps)/K per
      process at K=BENCH_MULTIHOST_K: the donated shard_map window
      spans the cross-process mesh, so the budget holds across hosts.
    * ``multihost_recovery_s`` — gate <= 60: SIGTERM one host mid-run;
      wall time from the preemption notice to the respawned survivor
      world advancing training progress past the pre-fault mark.
    * ``collective_compression_ratio_2bit`` — gate >= 3x: 2-bit
      error-feedback codec's wire-byte shrink vs the dense psum on the
      same model (``mxnet_collective_bytes``).
    """
    import subprocess

    from mxnet_tpu import config as mxcfg

    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               BENCH_MULTIHOST_K=str(mxcfg.get("BENCH_MULTIHOST_K")))
    env.pop("XLA_FLAGS", None)  # the launcher sets per-worker devices
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.parallel.elastic",
         "--bench-json"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(f"multihost child failed: "
                           f"{proc.stderr.strip()[-800:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    disp = payload["multihost_dispatches_per_step"]
    recovery = payload["multihost_recovery_s"]
    ratio = payload["collective_compression_ratio_2bit"]
    return {
        "multihost_dispatch": {
            "metric": "multihost_dispatches_per_step",
            "value": disp,
            "budget": payload["budget"],
            "gate_pass": bool(disp <= payload["budget"]),
            "k": payload["k"], "world": payload["world"],
            "note": "per-process Module.fit dispatches/step on a "
                    "2-process x 4-fake-device jax.distributed mesh "
                    "(gloo collectives inside the donated shard_map "
                    "window; elastic launcher supervised)",
        },
        "multihost_recovery": {
            "metric": "multihost_recovery_s",
            "value": recovery,
            "budget_s": payload["recovery_budget_s"],
            "gate_pass": bool(recovery <= payload["recovery_budget_s"]),
            "restarts": payload["restarts"],
            "note": "SIGTERM of host 1/2 mid-run -> survivors boundary-"
                    "checkpoint, launcher respawns the dp/2 world, "
                    "clock stops when training progress advances",
        },
        "multihost_compression": {
            "metric": "collective_compression_ratio_2bit",
            "value": ratio,
            "budget_x": payload["compression_budget_x"],
            "gate_pass": bool(ratio >= payload["compression_budget_x"]),
            "note": "dense psum wire bytes / 2-bit packed all_gather "
                    "wire bytes per rank (ring schedules), same model "
                    "(mxnet_collective_bytes)",
        },
    }


def measure_fleet():
    """CPU-only phase for the fleet observability plane
    (ISSUE 20): one subprocess runs ``python -m
    mxnet_tpu.telemetry.fleet_sim --ranks 1000 --json`` — 1000
    in-process synthetic reporters (delta pushes, scripted anomalies)
    against one real leader on a virtual clock, with an internal
    rank=100 reference run for the sublinearity ratio and the rank<=8
    byte-compat pin.

    * ``fleet_merge_p99_ms``   — gate < 1: per-push leader merge p99.
    * ``fleet_rollup_cpu_ms``  — gate < 50: summary rollup at scrape.
    * ``fleet_scrape_kib``     — gate < 256: summary /fleet.json bytes.
    * ``fleet_sublinearity``   — gate <= 3x: rank=1000 merge p99 over
      the rank=100 reference.
    """
    import subprocess

    from mxnet_tpu import config as mxcfg

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry.fleet_sim",
         "--ranks", str(mxcfg.get("MXNET_FLEET_SIM_RANKS")),
         "--cycles", str(mxcfg.get("MXNET_FLEET_SIM_CYCLES")),
         "--json"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0 and not proc.stdout.strip():
        raise RuntimeError(f"fleet sim child failed: "
                           f"{proc.stderr.strip()[-800:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    res, gates = payload["result"], payload["gates"]
    sub = gates.get("sublinear_vs_ref", {})
    return {
        "fleet_merge": {
            "metric": "fleet_merge_p99_ms",
            "value": round(res["merge"]["p99_ms"], 4),
            "budget_ms": gates["merge_p99_ms"]["limit"],
            "gate_pass": bool(gates["merge_p99_ms"]["ok"]),
            "pushes": res["merge"]["pushes"],
            "delta_pushes": res["merge"]["delta"],
            "resyncs": res["merge"]["resync"],
            "note": "per-push leader merge latency p99 at rank="
                    f"{res['ranks']} (delta upsert into the sharded "
                    "FleetStore; virtual clock, pure host CPU)",
        },
        "fleet_rollup": {
            "metric": "fleet_rollup_cpu_ms",
            "value": round(res["rollup"]["max_ms"], 3),
            "budget_ms": gates["rollup_ms"]["limit"],
            "gate_pass": bool(gates["rollup_ms"]["ok"]),
            "p50_ms": round(res["rollup"]["p50_ms"], 3),
            "note": "summary rollup cost at scrape time, worst cycle "
                    "(bounded-staleness cache + incremental family "
                    "catalog; O(families + anomalous ranks))",
        },
        "fleet_scrape": {
            "metric": "fleet_scrape_kib",
            "value": round(res["scrape"]["summary_kib"], 2),
            "budget_kib": gates["scrape_kib"]["limit"],
            "gate_pass": bool(gates["scrape_kib"]["ok"]),
            "note": "summary-mode /fleet.json bytes at rank="
                    f"{res['ranks']} (per-rank detail stays behind "
                    "?detail=rank)",
        },
        "fleet_sublinear": {
            "metric": "fleet_sublinearity",
            "value": round(sub.get("value", 0.0), 3),
            "budget_x": sub.get("limit"),
            "gate_pass": bool(sub.get("ok", False)),
            "ref_ranks": sub.get("ref_ranks"),
            "backcompat_identical": bool(
                payload["backcompat"]["identical"]),
            "alert_lag_intervals": res["alerts"]["lag_intervals"],
            "note": "rank=1000 merge p99 over the rank=100 reference "
                    "run (plus the rank<=8 byte-compat pin and the "
                    "breach->leader alert propagation lag)",
        },
    }


def measure_train_dispatch():
    """Host-side counts for the fused train step, on mx.cpu():

    * ``resnet50_step_dispatches`` — XLA computation launches per
      Module train step on symbolic ResNet-50, fused vs per-param loop.
      The count is shape-independent, so it runs at a small image size
      (BENCH_DISPATCH_IMAGE) to keep CPU conv time out of the budget.
    * ``train_step_ms_bs32`` — wall time per step at batch 32 on a
      deep-narrow MLP (49 dispatch-bound layers) where launch overhead,
      not FLOPs, dominates — the quantity the fused step eliminates.
      ResNet-50 at bs32 on CPU is conv-bound (~1 min/step), which would
      measure Eigen, not dispatch.
    """
    import mxnet_tpu as mx
    from mxnet_tpu import config as mxcfg

    img = mxcfg.get("BENCH_DISPATCH_IMAGE")
    dbs = mxcfg.get("BENCH_DISPATCH_BATCH")
    steps = mxcfg.get("BENCH_DISPATCH_STEPS")

    log(f"[dispatch] resnet50 dispatch count @ {dbs}x3x{img}x{img}")
    from mxnet_tpu.symbol.resnet import resnet_v1
    rn50 = resnet_v1()
    f_ms, f_disp = _module_steps(rn50, (dbs, 3, img, img), True, 2)
    l_ms, l_disp = _module_steps(rn50, (dbs, 3, img, img), False, 2)

    log(f"[dispatch] deep-MLP train_step_ms @ bs32 x{steps}")

    def deep_mlp(layers=24, width=64):
        h = mx.sym.Variable("data")
        for i in range(layers):
            h = mx.sym.FullyConnected(h, num_hidden=width, name=f"fc{i}")
            h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc_out")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    mf_ms, mf_disp = _module_steps(deep_mlp(), (32, 64), True, steps)
    ml_ms, ml_disp = _module_steps(deep_mlp(), (32, 64), False, steps)

    return {
        "dispatch": {
            "metric": "resnet50_step_dispatches",
            "value": f_disp,
            "unfused_dispatches_per_step": l_disp,
            "fused_step_ms": round(f_ms, 1),
            "unfused_step_ms": round(l_ms, 1),
            "image": img, "batch": dbs,
            "note": "Module-API XLA launches/step; count is "
                    "shape-independent (small image keeps CPU convs "
                    "out of the budget)",
        },
        "train_step": {
            "metric": "train_step_ms_bs32",
            "value": round(mf_ms, 3),
            "unfused_ms": round(ml_ms, 3),
            "improvement_vs_loop": round(1.0 - mf_ms / ml_ms, 3),
            "fused_dispatches_per_step": mf_disp,
            "unfused_dispatches_per_step": ml_disp,
            "model": "mlp24x64 (dispatch-bound)",
            "steps": steps,
        },
    }


def measure_graftlint():
    """ISSUE-15 lint-cost phase: ``graftlint_full_tree_s`` — one
    whole-tree run of the two-phase engine (lexical rules + summary
    collection + call-graph resolution + flow rules) in a fresh
    subprocess, gated under the same 15 s wall budget ci/run.sh
    enforces.  Lint runs before every test phase, so its cost is a hot
    path like any other: the per-rule breakdown rides along from
    ``--timings`` so a regression names its rule."""
    import json as _json
    import subprocess as _sp
    import sys as _sys
    import time as _t

    budget_s = 15.0
    best = float("inf")
    timings = {}
    for _ in range(2):
        t0 = _t.perf_counter()
        r = _sp.run(
            [_sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "graftlint.py"),
             "--fail-on-new", "--timings", "--json"],
            capture_output=True, text=True, timeout=120)
        wall = _t.perf_counter() - t0
        if r.returncode != 0:
            raise RuntimeError(
                f"graftlint --fail-on-new failed during bench: "
                f"{r.stdout[-500:]}")
        best = min(best, wall)
        timings = _json.loads(r.stdout).get("timings", {})
    slowest = sorted(((v, k) for k, v in timings.items()
                      if not k.startswith("(")), reverse=True)[:3]
    return {"graftlint": {
        "metric": "graftlint_full_tree_s",
        "value": round(best, 2), "unit": "s",
        "budget_s": budget_s,
        "gate_pass": bool(best < budget_s),
        "slowest_rules": {k: round(v, 3) for v, k in slowest},
    }}


def measure_kernels():
    """ISSUE-17 kernels-layer phases (BENCH_KERNELS).  They tune
    in-process on the default backend, which main() has established is
    the chip:

    * ``kernel_tuner_overhead_s`` — a cold measured tune of every
      registered kernel on a bench shape (grid capped by
      MXNET_KERNELS_TUNE_BUDGET) into a throwaway namespace, gated
      under a fixed wall budget.  Every search must commit a ``tuned``
      winner, and re-resolving every kernel afterwards must be pure
      ladder work: ZERO new tune traces on the PR 7 ledger;
    * ``kernel_device`` — tuned-vs-reference LayerNorm latency on the
      chip.  The winners' namespace is a throwaway directory; the compile
      cache itself was resolved once at the top of main() and does not
      move.
    """
    import tempfile as _tf
    import time as _t

    import numpy as _np

    import jax as _jax
    from mxnet_tpu import kernels as _k
    from mxnet_tpu.compile.ledger import LEDGER
    from mxnet_tpu.kernels import autotune as _at

    budget_s = 60.0
    shapes = {"layernorm": (256, 128), "softmax_ce": (256, 64),
              "attention": (2, 2, 64, 16)}
    prev = {k: os.environ.get(k)
            for k in ("MXNET_COMPILE_CACHE_DIR", "MXNET_KERNELS")}
    os.environ["MXNET_COMPILE_CACHE_DIR"] = _tf.mkdtemp(
        prefix="bench-kernels-")
    os.environ["MXNET_KERNELS"] = "tuned"
    try:
        _k.reset_for_tests()
        before = LEDGER.trace_count("kernels/tune")
        t0 = _t.perf_counter()
        winners = {}
        for name, shape in shapes.items():
            cfg, src = _k.tune(name, shape, _np.float32, repeats=1)
            winners[name] = {"config": cfg, "source": src}
        tune_s = _t.perf_counter() - t0
        tunes = LEDGER.trace_count("kernels/tune") - before
        for name, shape in shapes.items():
            _k.get(name, shape, _np.float32)
        retunes = LEDGER.trace_count("kernels/tune") - before - tunes
        all_tuned = all(w["source"] == "tuned" for w in winners.values())

        spec = _k.get_spec("layernorm")
        rng = _np.random.RandomState(7)
        args, kwargs = spec.example_inputs(shapes["layernorm"],
                                           _np.float32, rng)
        cfg = winners["layernorm"]["config"]
        tuned_ms = _at._measure(spec.make(dict(cfg)), args, kwargs, 20)
        ref_ms = _at._measure(spec.reference, args, kwargs, 20)
        device = {
            "metric": "kernel_layernorm_speedup_vs_reference",
            "value": round(ref_ms / max(tuned_ms, 1e-9), 3),
            "unit": "x", "backend": _jax.default_backend(),
            "tuned_ms": round(tuned_ms, 4),
            "reference_ms": round(ref_ms, 4),
            "gate_pass": bool(tuned_ms <= ref_ms * 1.1),
        }
        return {
            "kernel_tuner": {
                "metric": "kernel_tuner_overhead_s",
                "value": round(tune_s, 2), "unit": "s",
                "budget_s": budget_s,
                "tunes": tunes, "retunes_on_reresolve": retunes,
                "winners": winners,
                "gate_pass": bool(tune_s < budget_s and tunes ==
                                  len(shapes) and retunes == 0 and
                                  all_tuned),
            },
            "kernel_device": device,
        }
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _k.reset_for_tests()


def measure_numerics_overhead():
    """ISSUE-14 numerics-observatory overheads, two gates:

    * ``numerics_overhead_pct`` — armed (MXNET_NUMERICS=warn) K=8
      scanned-window step wall vs numerics-off on a compute-
      representative MLP (width 256 @ bs 512 — NOT the synthetic
      dispatch-bound width-64/bs-32 model, which exists to magnify
      per-step overheads: there the CPU backend's memory-bound reduce
      throughput, not the design, dominates.  At training-shaped
      batches the stat reductions amortize into real compute).
      Gate < 5%: the in-trace stats are two fused reductions per
      parameter riding the donated window, with the dispatches/step
      REQUIRED identical (the stats add zero dispatches);
    * ``numerics_disabled_ns`` — the disarmed hot-path gate
      (``numerics.armed()`` + the boundary check's early-out; < 1 µs,
      the span/trace/failpoint bar)."""
    import time as _t

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio, profiler as prof
    from mxnet_tpu.telemetry import numerics

    # disabled-path cost first: module state pristine
    assert not numerics.armed()
    n = 100000
    best_off = float("inf")
    for _ in range(3):
        t0 = _t.perf_counter()
        for _ in range(n):
            numerics.armed()
            numerics.observe_window(None, "bench", 0, 0)
        best_off = min(best_off, (_t.perf_counter() - t0) / n)

    K, steps, bs = 8, 8, 512

    def mlp(layers=16, width=256):
        h = mx.sym.Variable("data")
        for i in range(layers):
            h = mx.sym.FullyConnected(h, num_hidden=width, name=f"fc{i}")
            h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc_out")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(steps * bs, 64).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 10, steps * bs).astype(np.float32))

    os.environ["MXNET_FUSED_STEP"] = "1"
    os.environ["MXNET_SCAN_STEPS"] = str(K)
    opt = {"learning_rate": 0.01, "momentum": 0.9}

    def make_runner(mode):
        os.environ["MXNET_NUMERICS"] = mode
        numerics.configure()
        it = mxio.NDArrayIter(x, y, batch_size=bs,
                              label_name="softmax_label")
        mod = mx.mod.Module(mlp(), context=mx.cpu())
        mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt,
                initializer=mx.initializer.Xavier())  # warm: compiles
        return mod, it

    def epoch_ms(mod, it):
        it.reset()
        prof.reset_dispatch_counts()
        t0 = _t.perf_counter()
        mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt)
        return ((_t.perf_counter() - t0) / steps * 1e3,
                prof.dispatch_counts().get("total", 0) / steps)

    # alternate BLOCKS per mode (the mode is baked into the trace, so
    # each toggle retraces — pay one throwaway epoch per block), judge
    # per ROUND (one adjacent off-block + on-block pair), and keep the
    # round with the smallest on/off ratio: a machine-load spike can
    # only INFLATE a round's ratio, so the min round is the cleanest
    # measurement a noisy box yields
    try:
        best = None  # (ratio, off_ms, on_ms, off_disp, on_disp)
        for _round in range(3):
            _mod, _it = make_runner("off")
            epoch_ms(_mod, _it)  # retrace settles
            r_off = sorted((epoch_ms(_mod, _it) for _ in range(3)),
                           key=lambda t: t[0])[1]  # median of 3
            _mod, _it = make_runner("warn")
            epoch_ms(_mod, _it)
            r_on = sorted((epoch_ms(_mod, _it) for _ in range(3)),
                          key=lambda t: t[0])[1]  # median of 3
            ratio = r_on[0] / r_off[0] if r_off[0] else 1.0
            if best is None or ratio < best[0]:
                best = (ratio, r_off[0], r_on[0], r_off[1], r_on[1])
    finally:
        os.environ.pop("MXNET_NUMERICS", None)
        os.environ.pop("MXNET_SCAN_STEPS", None)
        numerics.configure()
    _ratio, off_ms, on_ms, off_disp, on_disp = best
    overhead = max(0.0, _ratio - 1.0) * 100.0
    return {
        "numerics": {
            "metric": "numerics_overhead_pct",
            "value": round(overhead, 2),
            "unit": "%",
            "budget_pct": 5.0,
            "gate_pass": bool(overhead <= 5.0 and on_disp == off_disp),
            "k": K,
            "step_ms_armed": round(on_ms, 3),
            "step_ms_off": round(off_ms, 3),
            "dispatches_per_step_armed": round(on_disp, 4),
            "dispatches_per_step_off": round(off_disp, 4),
            "disabled_ns": round(best_off * 1e9, 1),
            "disabled_budget_ns": 1000,
        }}


def measure_data_pipeline():
    """ISSUE-19 streaming-data-plane gate (``BENCH_DATA``): a K=8
    scanned fit fed by the multi-worker window feed must hide the data
    plane behind compute —

    * ``data_wait_pct`` — total train-thread blocked-on-data time
      (the ``mxnet_data_wait_seconds`` histogram, recorded at the one
      place the train thread can block: ``WindowFeed.get``) as a
      percentage of epoch wall, on the compute-representative MLP
      (width 256 @ bs 512, same model as the numerics phase).  Gate
      < 5%: window N+1 stages on the feed thread while window N
      executes, so the train thread should almost never wait;
    * ``serial_ratio`` — pipelined epoch wall over the serial baseline
      (``workers=0``: same seeded shard order, read + staged inline on
      the train thread).  Reported, not gated (CPU-backend compute
      dominates both sides; the 5% wait gate is the contract);
    * dispatches/step REQUIRED identical on vs off — the pipeline
      feeds the same donated window dispatch, it never adds one."""
    import time as _t

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import io_pipeline as mxpipe, profiler as prof
    from mxnet_tpu import telemetry as _tel

    K, steps, bs = 8, 16, 512

    def mlp(layers=16, width=256):
        h = mx.sym.Variable("data")
        for i in range(layers):
            h = mx.sym.FullyConnected(h, num_hidden=width, name=f"fc{i}")
            h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc_out")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    x = rng.randn(steps * bs, 64).astype(np.float32)
    y = rng.randint(0, 10, steps * bs).astype(np.float32)

    os.environ["MXNET_FUSED_STEP"] = "1"
    os.environ["MXNET_SCAN_STEPS"] = str(K)
    opt = {"learning_rate": 0.01, "momentum": 0.9}

    def make_runner(workers):
        if workers:
            os.environ["MXNET_DATA_WORKERS"] = str(workers)
        else:
            os.environ.pop("MXNET_DATA_WORKERS", None)
        it = mxpipe.DataPipeline(
            mxpipe.NDArraySource(x, y, batch_size=bs,
                                 batches_per_shard=1),
            workers=workers, seed=0)
        mod = mx.mod.Module(mlp(), context=mx.cpu())
        mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt,
                initializer=mx.initializer.Xavier())  # warm: compiles
        return mod, it

    def epoch(mod, it):
        it.reset()
        prof.reset_dispatch_counts()
        wait0 = _tel._DATA_WAIT.stats()["sum"]
        t0 = _t.perf_counter()
        mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt)
        wall = _t.perf_counter() - t0
        return (wall / steps * 1e3,
                prof.dispatch_counts().get("total", 0) / steps,
                _tel._DATA_WAIT.stats()["sum"] - wait0, wall)

    try:
        # serial baseline (workers=0: inline read + stage)
        mod0, it0 = make_runner(0)
        epoch(mod0, it0)  # settle
        off = sorted((epoch(mod0, it0) for _ in range(3)),
                     key=lambda t: t[0])[1]  # median of 3
        it0.close()
        # pipelined (2 readers + the window feed double-buffer)
        mod1, it1 = make_runner(2)
        epoch(mod1, it1)  # settle
        runs = sorted((epoch(mod1, it1) for _ in range(3)),
                      key=lambda t: t[0])
        on = runs[1]  # median of 3
        it1.close()
    finally:
        os.environ.pop("MXNET_DATA_WORKERS", None)
        os.environ.pop("MXNET_SCAN_STEPS", None)
    off_ms, off_disp, _w, _off_wall = off
    on_ms, on_disp, wait_s, on_wall = on
    wait_pct = (wait_s / on_wall * 100.0) if on_wall else 0.0
    return {
        "data_pipeline": {
            "metric": "data_wait_pct",
            "value": round(wait_pct, 2),
            "unit": "%",
            "budget_pct": 5.0,
            "gate_pass": bool(wait_pct < 5.0 and on_disp == off_disp),
            "k": K,
            "workers": 2,
            "step_ms_pipelined": round(on_ms, 3),
            "step_ms_serial": round(off_ms, 3),
            "serial_ratio": round(on_ms / off_ms, 3) if off_ms else 1.0,
            "data_wait_s_per_epoch": round(wait_s, 4),
            "dispatches_per_step_pipelined": round(on_disp, 4),
            "dispatches_per_step_serial": round(off_disp, 4),
        }}


def measure_scan_dispatch(fused_step_ms=None):
    """CPU-measurable perf signal for the K-step scanned train window
    (ISSUE 6): the same dispatch-bound deep MLP as train_step_ms_bs32,
    but driven through Module.fit so MXNET_SCAN_STEPS batches run as ONE
    donated lax.scan dispatch.

    * ``scan_dispatches_per_step`` — framework dispatches per train step
      at K=BENCH_SCAN_K (gate: <= (1+eps)/K; eps=0.25).
    * ``train_step_ms_scan_k<K>`` — amortized wall per step (bar: >=25%
      below the PR-4 fused per-step figure measured in the same run).
    """
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import config as mxcfg, io as mxio, profiler as prof

    K = max(2, mxcfg.get("BENCH_SCAN_K"))
    steps = max(K, (mxcfg.get("BENCH_DISPATCH_STEPS") // K) * K)

    def deep_mlp(layers=24, width=64):
        h = mx.sym.Variable("data")
        for i in range(layers):
            h = mx.sym.FullyConnected(h, num_hidden=width, name=f"fc{i}")
            h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc_out")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    log(f"[scan] deep-MLP fit @ bs32, K={K}, {steps} steps/epoch")
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(steps * 32, 64).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 10, steps * 32).astype(np.float32))

    def fit_epoch_ms(scan_k):
        os.environ["MXNET_FUSED_STEP"] = "1"
        os.environ["MXNET_SCAN_STEPS"] = str(scan_k)
        it = mxio.NDArrayIter(x, y, batch_size=32,
                              label_name="softmax_label")
        mod = mx.mod.Module(deep_mlp(), context=mx.cpu())
        opt = {"learning_rate": 0.01, "momentum": 0.9}
        mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt,
                initializer=mx.initializer.Xavier())  # warm: compiles
        it.reset()
        prof.reset_dispatch_counts()
        t0 = time.perf_counter()
        mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt)
        ms = (time.perf_counter() - t0) / steps * 1e3
        return ms, prof.dispatch_counts().get("total", 0) / steps

    scan_ms, scan_disp = fit_epoch_ms(K)
    seq_ms, seq_disp = fit_epoch_ms(1)
    budget = (1 + 0.25) / K
    fused_ref = fused_step_ms if fused_step_ms else seq_ms
    return {
        "scan_dispatch": {
            "metric": "scan_dispatches_per_step",
            "value": round(scan_disp, 4),
            "budget": round(budget, 4),
            "gate_pass": bool(scan_disp <= budget),
            "k": K,
            "sequential_dispatches_per_step": round(seq_disp, 2),
            "note": "Module.fit dispatches/step with MXNET_SCAN_STEPS "
                    "windows (one donated lax.scan per K steps)",
        },
        "train_step_scan": {
            "metric": f"train_step_ms_scan_k{K}",
            "value": round(scan_ms, 3),
            "sequential_fused_ms": round(seq_ms, 3),
            "fused_per_step_ref_ms": round(fused_ref, 3),
            "improvement_vs_fused": round(1.0 - scan_ms / fused_ref, 3)
            if fused_ref else None,
            "bar": "amortized >= 25% below the per-step fused figure",
            "model": "mlp24x64 (dispatch-bound)",
            "steps": steps,
        },
    }


_MODEL_CACHE = {}


def build_train_step(batch, dtype="bfloat16", use_remat=False,
                     loss_mode="fused"):
    """Build the benchmarked ResNet-50 train step (fwd+bwd+SGD-momentum).

    Shared by main() and tools/hlo_flops.py so the FLOP forensics always
    analyze the exact program being timed.  Returns
    ``(step_fn, (tparams, aparams), n_params)`` with the param tuples as
    host arrays; callers place them on their own device and create the
    momentum buffers (``jnp.zeros_like``) themselves.

    The functionalized model is batch-polymorphic, so it is built ONCE
    per dtype and cached — multi-batch-size runs (bs32/128/256) pay the
    host-side functionalize + init exactly once.

    loss_mode: "fused" routes softmax-CE through the Pallas kernel
    (mxnet_tpu.ops.pallas_softmax_ce, XLA fallback built in);
    "onehot" keeps the r2-r4 one-hot formulation for A/B.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.spmd import (functionalize, merge_params,
                                         host_cpu_scope, remat_wrap)
    from mxnet_tpu.ops import registry as _registry
    from mxnet_tpu.ops.pallas_softmax_ce import fused_softmax_ce
    from mxnet_tpu import autograd as _ag
    from mxnet_tpu import amp

    if dtype == "bfloat16":
        # framework AMP: MXU ops compute in bf16, fp32 master weights
        # and norm statistics — the recipe lives in mxnet_tpu.amp
        amp.init(target_dtype="bfloat16")

    if dtype in _MODEL_CACHE:
        apply_fn, param_arrays, train_idx, aux_list = _MODEL_CACHE[dtype]
    else:
        with host_cpu_scope(), jax.disable_jit():
            net = vision.resnet50_v1()
            net.initialize(mx.initializer.Xavier())
            x_ex = mx.nd.zeros((batch, 3, 224, 224))
            fb = functionalize(net, x_ex)
            apply_fn, param_arrays, _names = fb
            x_sds = jax.ShapeDtypeStruct((batch, 3, 224, 224),
                                         np.dtype(np.float32))
            train_idx, aux_list = fb.split_train_aux((x_sds,))
        _MODEL_CACHE[dtype] = (apply_fn, param_arrays, train_idx, aux_list)

    sgd_attrs = {"lr": 0.01, "wd": 1e-4, "momentum": 0.9,
                 "rescale_grad": 1.0}
    sgd_mom = _registry.get("sgd_mom_update").fcompute

    def step(key, tparams, aparams, moms, x, y):
        def fwd(tps, x_):
            ps = merge_params(train_idx, aux_list, tps, aparams)
            with _ag.train_mode():
                outs, mutated = apply_fn(key, ps, (x_,))
            return outs[0], mutated

        if use_remat:
            fwd = remat_wrap(fwd)

        def loss_fn(tps):
            logits, mutated = fwd(tps, x)
            logits = logits.astype(jnp.float32)
            if loss_mode == "fused":
                loss = fused_softmax_ce(logits, y.astype(jnp.int32)).mean()
            else:
                logp = jax.nn.log_softmax(logits, axis=-1)
                oh = jax.nn.one_hot(y.astype(jnp.int32), 1000)
                loss = -(oh * logp).sum(axis=-1).mean()
            return loss, mutated

        (loss, mutated), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(tparams)
        new_p, new_m = [], []
        for w, g, m in zip(tparams, grads, moms):
            nw, nm = sgd_mom(sgd_attrs, w, g.astype(w.dtype), m)
            new_p.append(nw)
            new_m.append(nm)
        new_aux = tuple(mu.astype(a.dtype) for mu, a in zip(mutated, aparams))
        return tuple(new_p), new_aux, tuple(new_m), loss

    tparams = tuple(param_arrays[i] for i in train_idx)
    aparams = tuple(param_arrays[i] for i in aux_list)
    n_params = sum(int(np.prod(a.shape)) for a in param_arrays)
    return step, (tparams, aparams), n_params


def main():
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 1200))
    batch = int(os.environ.get("BENCH_BATCH", 32))
    batch2 = int(os.environ.get("BENCH_BATCH2", 128))
    batch3 = int(os.environ.get("BENCH_BATCH3", 256))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    k_steps = max(2, int(os.environ.get("BENCH_K", 8)))

    result = {
        "metric": f"resnet50_train_img_per_sec_bs{batch}",
        "value": 0.0,
        "unit": "img/s",
        # baseline is bs32 fp32 on 1x V100; only a like-for-like batch is
        # a meaningful ratio
        "vs_baseline": 0.0,
    }

    # no chip is a failure, before any phase prints a number: this
    # process takes the TPU here and keeps it; every child below is
    # pinned to the CPU backend
    import jax
    devs = jax.devices()
    dev = devs[0]
    kind = str(dev.device_kind)
    if dev.platform != "tpu":
        sys.exit(f"bench.py needs a TPU: jax found platform "
                 f"{dev.platform!r} ({kind})")
    table_peak, table_kind = peak_flops_for(kind)  # unknown kind: error
    log(f"devices: {len(devs)}x {dev.platform}/{kind}")
    result["n_devices"] = len(devs)
    result["device_kind"] = kind
    # one compile-cache directory for the whole process, resolved before
    # any phase can get there first (JAX_COMPILATION_CACHE_DIR untouched
    # when set, else <checkout>/.jax_cache)
    from mxnet_tpu import compile as _mxc
    log(f"compile cache: {_mxc.ensure_persistent_cache()}")

    try:
        # --- dispatch phases (host CPU) ---------------------------------
        # these phases bind their modules to mx.cpu() and count
        # dispatches / host wall time; none of them is a device metric
        from mxnet_tpu import config as _cfg0
        if _cfg0.get("BENCH_DISPATCH"):
            _prev_fused = os.environ.get("MXNET_FUSED_STEP")
            try:
                result.update(measure_train_dispatch())
                d, t = result["dispatch"], result["train_step"]
                log(f"[dispatch] fused {d['value']}/step vs loop "
                    f"{d['unfused_dispatches_per_step']}/step; "
                    f"step {t['value']}ms vs {t['unfused_ms']}ms "
                    f"({t['improvement_vs_loop']:.0%} faster)")
            except Exception as e:
                log(f"dispatch phase failed: {type(e).__name__}: {e}")
                result["dispatch"] = {
                    "metric": "resnet50_step_dispatches",
                    "error": f"{type(e).__name__}: {e}"}
            finally:
                if _prev_fused is None:
                    os.environ.pop("MXNET_FUSED_STEP", None)
                else:
                    os.environ["MXNET_FUSED_STEP"] = _prev_fused

        if _cfg0.get("BENCH_SCAN"):
            _prev = {k: os.environ.get(k)
                     for k in ("MXNET_FUSED_STEP", "MXNET_SCAN_STEPS")}
            try:
                fused_ref = (result.get("train_step") or {}).get("value")
                result.update(measure_scan_dispatch(fused_ref))
                sd, st = result["scan_dispatch"], result["train_step_scan"]
                log(f"[scan] {sd['value']}/step dispatches at K={sd['k']} "
                    f"(budget {sd['budget']}); step {st['value']}ms vs "
                    f"fused {st['fused_per_step_ref_ms']}ms "
                    f"({st['improvement_vs_fused']:.0%} faster)")
            except Exception as e:
                log(f"scan phase failed: {type(e).__name__}: {e}")
                result["scan_dispatch"] = {
                    "metric": "scan_dispatches_per_step",
                    "error": f"{type(e).__name__}: {e}"}
            finally:
                for k, v in _prev.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v

        if _cfg0.get("BENCH_MULTICHIP"):
            try:
                result.update(measure_multichip())
                md, mc = result["multichip_dispatch"], \
                    result["multichip_comm"]
                log(f"[multichip] {md['value']}/step dispatches at "
                    f"K={md['k']} on {md['mesh']} (budget "
                    f"{md['budget']}, "
                    f"{'PASS' if md['gate_pass'] else 'FAIL'}); comm "
                    f"blocking {mc['value']}% (budget "
                    f"{mc['budget_pct']}%, "
                    f"{'PASS' if mc['gate_pass'] else 'FAIL'})")
            except Exception as e:
                log(f"multichip phase failed: {type(e).__name__}: {e}")
                result["multichip_dispatch"] = {
                    "metric": "multichip_dispatches_per_step",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_MULTIHOST"):
            try:
                result.update(measure_multihost())
                mh, mr, mx_ = (result["multihost_dispatch"],
                               result["multihost_recovery"],
                               result["multihost_compression"])
                log(f"[multihost] {mh['value']}/step dispatches/proc "
                    f"at K={mh['k']} world={mh['world']} (budget "
                    f"{mh['budget']}, "
                    f"{'PASS' if mh['gate_pass'] else 'FAIL'}); "
                    f"recovery {mr['value']}s (budget {mr['budget_s']}s, "
                    f"{'PASS' if mr['gate_pass'] else 'FAIL'}); "
                    f"2bit wire shrink {mx_['value']}x (bar "
                    f"{mx_['budget_x']}x, "
                    f"{'PASS' if mx_['gate_pass'] else 'FAIL'})")
            except Exception as e:
                log(f"multihost phase failed: {type(e).__name__}: {e}")
                result["multihost_dispatch"] = {
                    "metric": "multihost_dispatches_per_step",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_FLEET"):
            try:
                result.update(measure_fleet())
                fm, fr, fs, fx = (result["fleet_merge"],
                                  result["fleet_rollup"],
                                  result["fleet_scrape"],
                                  result["fleet_sublinear"])
                log(f"[fleet] merge p99 {fm['value']}ms (budget "
                    f"{fm['budget_ms']}ms, "
                    f"{'PASS' if fm['gate_pass'] else 'FAIL'}); rollup "
                    f"{fr['value']}ms (budget {fr['budget_ms']}ms, "
                    f"{'PASS' if fr['gate_pass'] else 'FAIL'}); scrape "
                    f"{fs['value']}KiB (budget {fs['budget_kib']}KiB, "
                    f"{'PASS' if fs['gate_pass'] else 'FAIL'}); "
                    f"sublinear {fx['value']}x vs rank="
                    f"{fx['ref_ranks']} (bar {fx['budget_x']}x, "
                    f"{'PASS' if fx['gate_pass'] else 'FAIL'})")
            except Exception as e:
                log(f"fleet phase failed: {type(e).__name__}: {e}")
                result["fleet_merge"] = {
                    "metric": "fleet_merge_p99_ms",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_COLD_START"):
            try:
                result.update(measure_cold_start())
                cs = result["cold_start"]
                log(f"[cold_start] warm {cs['value']}ms vs cold "
                    f"{cs['cold_first_request_ms']}ms "
                    f"({cs['speedup_warm_vs_cold']}x, bar "
                    f"{cs['bar_speedup']}x, "
                    f"{'PASS' if cs['passed'] else 'FAIL'})")
            except Exception as e:
                log(f"cold_start phase failed: {type(e).__name__}: {e}")
                result["cold_start"] = {
                    "metric": "cold_start_first_request_ms",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_TELEMETRY"):
            try:
                result.update(measure_telemetry_overhead())
                log(f"[telemetry] disabled span "
                    f"{result['telemetry']['value']} ns "
                    f"(budget {result['telemetry']['budget_ns']})")
            except Exception as e:
                log(f"telemetry phase failed: {type(e).__name__}: {e}")
                result["telemetry"] = {
                    "metric": "telemetry_disabled_span_ns",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_TRACE"):
            try:
                result.update(measure_trace_overhead())
                log(f"[trace] disabled trace/flight hook "
                    f"{result['trace']['value']} ns "
                    f"(budget {result['trace']['budget_ns']})")
            except Exception as e:
                log(f"trace phase failed: {type(e).__name__}: {e}")
                result["trace"] = {
                    "metric": "trace_disabled_overhead_ns",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_ALERTS"):
            try:
                result.update(measure_alert_overhead())
                al, rs = result["alerts"], result["resource_sample"]
                log(f"[alerts] tick {al['value']} us "
                    f"(budget {al['budget_us']}), disabled "
                    f"{al['disabled_tick_ns']} ns (budget "
                    f"{al['disabled_budget_ns']}); host sample "
                    f"{rs['value']} us (budget {rs['budget_us']})")
            except Exception as e:
                log(f"alerts phase failed: {type(e).__name__}: {e}")
                result["alerts"] = {
                    "metric": "alert_tick_overhead_us",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_NUMERICS"):
            try:
                result.update(measure_numerics_overhead())
                nm = result["numerics"]
                log(f"[numerics] armed K={nm['k']} overhead "
                    f"{nm['value']}% (budget {nm['budget_pct']}%), "
                    f"dispatches {nm['dispatches_per_step_armed']} vs "
                    f"{nm['dispatches_per_step_off']} off, disabled "
                    f"path {nm['disabled_ns']} ns (budget "
                    f"{nm['disabled_budget_ns']}), "
                    f"{'PASS' if nm['gate_pass'] else 'FAIL'}")
            except Exception as e:
                log(f"numerics phase failed: {type(e).__name__}: {e}")
                result["numerics"] = {
                    "metric": "numerics_overhead_pct",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_DATA"):
            try:
                result.update(measure_data_pipeline())
                dp = result["data_pipeline"]
                log(f"[data] K={dp['k']} x{dp['workers']} workers: "
                    f"data_wait {dp['value']}% of wall (budget "
                    f"{dp['budget_pct']}%), step "
                    f"{dp['step_ms_pipelined']}ms vs serial "
                    f"{dp['step_ms_serial']}ms "
                    f"({dp['serial_ratio']}x), dispatches "
                    f"{dp['dispatches_per_step_pipelined']} vs "
                    f"{dp['dispatches_per_step_serial']} serial, "
                    f"{'PASS' if dp['gate_pass'] else 'FAIL'}")
            except Exception as e:
                log(f"data phase failed: {type(e).__name__}: {e}")
                result["data_pipeline"] = {
                    "metric": "data_wait_pct",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_LINT"):
            try:
                result.update(measure_graftlint())
                gl = result["graftlint"]
                log(f"[graftlint] full tree {gl['value']}s (budget "
                    f"{gl['budget_s']}s, "
                    f"{'PASS' if gl['gate_pass'] else 'FAIL'}); "
                    f"slowest rules {gl['slowest_rules']}")
            except Exception as e:
                log(f"graftlint phase failed: {type(e).__name__}: {e}")
                result["graftlint"] = {
                    "metric": "graftlint_full_tree_s",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_KERNELS"):
            try:
                result.update(measure_kernels())
                kt, kd = result["kernel_tuner"], result["kernel_device"]
                log(f"[kernels] tuner {kt['value']}s for {kt['tunes']} "
                    f"searches (budget {kt['budget_s']}s, "
                    f"{kt['retunes_on_reresolve']} re-tunes on "
                    f"re-resolve, "
                    f"{'PASS' if kt['gate_pass'] else 'FAIL'}); LayerNorm "
                    f"tuned {kd['tuned_ms']} ms vs reference "
                    f"{kd['reference_ms']} ms")
            except Exception as e:
                log(f"kernels phase failed: {type(e).__name__}: {e}")
                result["kernel_tuner"] = {
                    "metric": "kernel_tuner_overhead_s",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_SERVE_SPIKE"):
            try:
                result.update(measure_serve_pool())
                ss, sp = result["serve_sustained"], result["serve_spike"]
                log(f"[serve_pool] sustained {ss['value']} img/s vs "
                    f"single {ss['single_batcher_img_per_sec']} "
                    f"({ss['ratio_vs_single']}x, bar {ss['bar_ratio']}x, "
                    f"{'PASS' if ss['passed'] else 'FAIL'}); spike p99 "
                    f"{sp['value']}ms vs steady {sp['steady_p99_ms']}ms "
                    f"({sp['ratio_vs_steady']}x, bar {sp['bar_ratio']}x, "
                    f"shed {sp['shed_spike']}, "
                    f"{'PASS' if sp['passed'] else 'FAIL'})")
            except Exception as e:
                log(f"serve_pool phase failed: {type(e).__name__}: {e}")
                result["serve_spike"] = {
                    "metric": "serve_spike_p99_ms",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_GENERATE"):
            try:
                result.update(measure_generation())
                gt = result["generate_throughput"]
                gi = result["generate_intertoken"]
                log(f"[generate] {gt['value']} tok/s vs single "
                    f"{gt['single_session_tok_per_sec']} "
                    f"({gt['ratio_vs_single']}x, bar {gt['bar_ratio']}x), "
                    f"prefix hit rate {gt['prefix_hit_rate']} "
                    f"(bar {gt['prefix_hit_bar']}), p99 intertoken "
                    f"{gi['value']}ms, "
                    f"{'PASS' if gt['passed'] else 'FAIL'}")
            except Exception as e:
                log(f"generate phase failed: {type(e).__name__}: {e}")
                result["generate_throughput"] = {
                    "metric": "generate_tokens_per_sec",
                    "error": f"{type(e).__name__}: {e}"}

        if _cfg0.get("BENCH_CHAOS"):
            try:
                result.update(measure_degraded_p99())
                dg = result["degraded"]
                log(f"[chaos] degraded p99 {dg['value']}ms vs healthy "
                    f"{dg['healthy_p99_ms']}ms "
                    f"({dg['ratio_vs_healthy']}x, bar {dg['bar_ratio']}x, "
                    f"{'PASS' if dg['passed'] else 'FAIL'})")
            except Exception as e:
                log(f"chaos phase failed: {type(e).__name__}: {e}")
                result["degraded"] = {
                    "metric": "degraded_p99_ms",
                    "error": f"{type(e).__name__}: {e}"}

        import numpy as np
        import jax.numpy as jnp
        from jax import lax

        from mxnet_tpu import random as _random

        compute_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

        # remat parity hook (MXNET_BACKWARD_DO_MIRROR). Default OFF: honest
        # timing shows no activation-spill cliff at these sizes and remat
        # costs ~20% real step time at bs128 (measured r4).
        remat_from = int(os.environ.get("BENCH_REMAT_FROM_BS", 0))
        loss_mode = os.environ.get("BENCH_LOSS", "fused")

        def measure(bs):
            """Compile + time the train step at batch size bs.

            One program: a dynamic-trip-count fori_loop over the train
            step, returning only the final scalar loss.  Device step time
            = (T(2K) - T(K)) / K with transfer sync (see module docstring).
            """
            log(f"[bs{bs}] building ResNet-50 on host CPU "
                "(no device compiles)")
            step_fn, (tparams_h, aparams_h), n_params = build_train_step(
                bs, dtype, use_remat=(bs >= remat_from > 0),
                loss_mode=loss_mode)
            log(f"[bs{bs}] functionalized ({n_params / 1e6:.1f}M params)")
            tparams = tuple(jax.device_put(p, dev) for p in tparams_h)
            aparams = tuple(jax.device_put(p, dev) for p in aparams_h)
            moms = tuple(jnp.zeros_like(p) for p in tparams)
            x = jax.device_put(
                np.random.randn(bs, 3, 224, 224).astype(np.float32), dev
            ).astype(compute_dtype)
            y = jax.device_put(
                np.random.randint(0, 1000, (bs,)).astype(np.float32), dev)
            key = _random.next_key()

            def multi(k, salt, key, tp, ap, mm, x, y):
                # salt: per-call-unique live input (anti result-caching,
                # see calibrate_peak); folded into x at 1e-30 scale
                x = x + (salt * 1e-30).astype(x.dtype)
                def body(_, carry):
                    tp_, ap_, mm2, _l = carry
                    return step_fn(key, tp_, ap_, mm2, x, y)
                init = (tp, ap, mm, jnp.zeros((), jnp.float32))
                return lax.fori_loop(0, k, body, init)[3]

            log(f"[bs{bs}] lowering + compiling dynamic-K train loop"
                f"{' (remat)' if bs >= remat_from > 0 else ''}")
            t0 = time.perf_counter()
            compiled = jax.jit(multi).lower(
                jnp.int32(1), jnp.float32(0), key, tparams, aparams, moms,
                x, y).compile()
            compile_s = time.perf_counter() - t0
            log(f"[bs{bs}] compiled in {compile_s:.1f}s")

            ca_flops = None
            try:
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0]
                ca_flops = float(ca.get("flops", 0.0)) or None
            except Exception:
                pass

            loss = float(compiled(jnp.int32(2), jnp.float32(1), key,
                                  tparams, aparams, moms, x, y))
            calls = [1]

            def timed(k, tries=3):
                ts = []
                for _ in range(tries):
                    calls[0] += 1
                    t0 = time.perf_counter()
                    nonlocal loss
                    loss = float(compiled(jnp.int32(k), jnp.float32(calls[0]),
                                          key, tparams, aparams, moms, x, y))
                    ts.append(time.perf_counter() - t0)
                    if time.perf_counter() - T_START > budget * 0.9:
                        break
                return min(ts)

            t1 = timed(k_steps)
            t2 = timed(2 * k_steps)
            per_step = (t2 - t1) / k_steps
            if per_step <= 0:
                raise RuntimeError(
                    f"differenced step time non-positive: T({k_steps})="
                    f"{t1:.4f}s T({2 * k_steps})={t2:.4f}s — timing "
                    "anomaly")
            fixed_ms = (t1 - per_step * k_steps) * 1e3
            return {
                "batch": bs,
                "img_s": bs / per_step,
                "step_ms": per_step * 1e3,
                # the fixed per-dispatch cost, cancelled out of step_ms
                # by differencing; reported for transparency
                "dispatch_overhead_ms": round(fixed_ms, 1),
                "timed_steps": 3 * k_steps,
                "k": k_steps,
                "compile_seconds": round(compile_s, 1),
                "flops_analytic": ANALYTIC_FWD_FLOPS_PER_IMG * 3 * bs,
                "flops_cost_analysis": ca_flops,
                "final_loss": loss,
                "sync": "4-byte transfer, differenced",
            }

        m1 = measure(batch)
        log(f"[bs{batch}] {m1['img_s']:.1f} img/s, "
            f"step {m1['step_ms']:.2f}ms "
            f"(dispatch overhead {m1['dispatch_overhead_ms']}ms, "
            f"cancelled)")

        # --- peak calibration -------------------------------------------
        log("calibrating peak FLOP/s (chained bf16 matmuls)")
        calibrated_peak, calib_info = calibrate_peak(dev)
        log(f"calibrated peak: {calibrated_peak / 1e12:.1f} TFLOP/s "
            f"(table {table_kind}: {table_peak / 1e12:.0f})")

        # Conservative headline denominator: whichever evidence says the
        # chip is FASTER (a mis-reported device_kind is exactly what
        # calibration catches). BOTH ratios are reported —
        # mfu_table may be deflated if the table kind overstates the
        # device; mfu_calibrated may be inflated if calibration is bound
        # by anything but the MXU.
        peak_used = max([p for p in (table_peak, calibrated_peak) if p])

        def attach_mfu(m, res):
            achieved = m["flops_analytic"] / (m["step_ms"] / 1e3)
            mfu = achieved / peak_used
            res["step_ms"] = round(m["step_ms"], 3)
            res["dispatch_overhead_ms"] = m["dispatch_overhead_ms"]
            res["mfu_table"] = round(achieved / table_peak, 4)
            if calibrated_peak:
                res["mfu_calibrated"] = round(achieved / calibrated_peak, 4)
            if 0 < mfu <= 1.0:
                res["mfu"] = round(mfu, 4)
            else:
                res["anomaly"] = {
                    "reason": "computed MFU > 1.0 — physically impossible",
                    "mfu_raw": round(mfu, 4),
                    "achieved_flops_per_sec": achieved,
                    "peak_used": peak_used,
                }
            return mfu

        result.update({
            "value": round(m1["img_s"], 2),
            "vs_baseline": (round(m1["img_s"] / BASELINE_IMG_S, 3)
                            if batch == 32 else None),
            "compile_seconds": m1["compile_seconds"],
            "timed_steps": m1["timed_steps"],
            "batch": batch,
            "dtype": dtype,
            "loss": loss_mode,
            "final_loss": m1["final_loss"],
            "flops_per_step_analytic": m1["flops_analytic"],
            "flops_per_step_cost_analysis": m1["flops_cost_analysis"],
            "peak_flops_table": f"{table_kind}:{table_peak:.3g}",
            "peak_flops_calibrated": (
                round(calibrated_peak, 0) if calibrated_peak else None),
            "calibration": calib_info,
            "sync": m1["sync"],
        })
        attach_mfu(m1, result)

        # --- extra MFU points (bs128 per r3 verdict, bs256 per r4) -------
        for extra_bs in (batch2, batch3):
            if not extra_bs or extra_bs == batch:
                continue
            remaining = budget - (time.perf_counter() - T_START)
            if remaining <= 240:
                log(f"skipping bs{extra_bs}: only {remaining:.0f}s left")
                continue
            m2 = measure(extra_bs)
            log(f"[bs{extra_bs}] {m2['img_s']:.1f} img/s, "
                f"step {m2['step_ms']:.2f}ms")
            sub = {"img_s": round(m2["img_s"], 2),
                   "compile_seconds": m2["compile_seconds"],
                   "final_loss": m2["final_loss"]}
            attach_mfu(m2, sub)
            result[f"bs{extra_bs}"] = sub

        # --- serving throughput (resnet18 via the DynamicBatcher) -------
        from mxnet_tpu import config as _mxcfg
        if _mxcfg.get("BENCH_SERVE"):
            remaining = budget - (time.perf_counter() - T_START)
            if remaining <= 180:
                log(f"skipping serving phase: only {remaining:.0f}s left")
            else:
                try:
                    srv = measure_serving()
                    result["serving"] = srv
                    log(f"[serving] {srv['value']} img/s "
                        f"(p99 {srv['p99_ms']}ms, shed {srv['shed']})")
                except Exception as e:
                    log(f"serving phase failed: {type(e).__name__}: {e}")
                    result["serving"] = {
                        "metric": "resnet18_serve_img_per_sec",
                        "error": f"{type(e).__name__}: {e}"}

        # --- checkpoint time-to-safe (save-blocking / restore) ----------
        if _mxcfg.get("BENCH_CKPT"):
            remaining = budget - (time.perf_counter() - T_START)
            if remaining <= 60:
                log(f"skipping checkpoint phase: only {remaining:.0f}s left")
            else:
                try:
                    ck = measure_checkpoint()
                    result["checkpoint"] = ck
                    log(f"[checkpoint] save blocks {ck['value']}ms async vs "
                        f"{ck['ckpt_save_sync_ms']}ms sync "
                        f"({ck['blocking_fraction']:.0%}), restore "
                        f"{ck['ckpt_restore_s']}s")
                except Exception as e:
                    log(f"checkpoint phase failed: {type(e).__name__}: {e}")
                    result["checkpoint"] = {
                        "metric": "ckpt_save_blocking_ms",
                        "error": f"{type(e).__name__}: {e}"}
    except Exception:
        # a failed device phase is a failed run: no JSON line under a
        # device metric's name, non-zero exit
        import traceback
        traceback.print_exc(file=sys.stderr)
        sys.exit(1)
    emit(result)


if __name__ == "__main__":
    main()
