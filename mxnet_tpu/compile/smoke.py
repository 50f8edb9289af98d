"""CI compile smoke (run via ``python -m mxnet_tpu.compile.smoke``).

The retrace ratchet, live: a budgeted serving workload must compile
exactly its warmed ladder and nothing more.

1. fresh persistent-cache dir (no floor), watchdog armed generous;
2. publish an MLP to a ModelServer, AOT-warm its full bucket ladder;
3. assert the TraceLedger saw exactly ladder-size executor-cache
   traces, and that artifacts were persisted;
4. fire a burst of mixed-size request waves (every formed batch lands
   in a warmed bucket) and assert ZERO post-warmup traces and ZERO
   post-warmup backend compiles — first-request latency is a cache hit;
5. the BucketPlanner must beat the power-of-two ladder on a skewed
   synthetic histogram with non-power-of-two boundaries;
6. the watchdog must have stayed silent.

Exit code 0 iff every gate held (ci/run.sh fails otherwise).
"""
from __future__ import annotations

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXNET_WATCHDOG_S", "120")
os.environ.setdefault("MXNET_COMPILE_CACHE_MIN_COMPILE_S", "0")
# a CPU smoke of the cache MECHANISM: it makes a fresh directory on
# purpose, so a directory placed from outside does not apply to it
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
_CACHE_DIR = tempfile.mkdtemp(prefix="mxnet-compile-smoke-")
os.environ["MXNET_COMPILE_CACHE_DIR"] = _CACHE_DIR

MAX_BATCH = 8
IN_DIM = 50


def _fail(msg):
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import compile as mxc
    from mxnet_tpu import serving, telemetry

    def build():
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=64, name="fc1")
        h = mx.sym.Activation(h, act_type="relu")
        return mx.sym.FullyConnected(h, num_hidden=10, name="fc2")

    rng = np.random.RandomState(0)
    params = {"fc1_weight": mx.nd.array(rng.randn(64, IN_DIM)
                                        .astype(np.float32) * 0.1),
              "fc1_bias": mx.nd.zeros((64,)),
              "fc2_weight": mx.nd.array(rng.randn(10, 64)
                                        .astype(np.float32) * 0.1),
              "fc2_bias": mx.nd.zeros((10,))}

    # -- publish + warm ------------------------------------------------------
    server = serving.ModelServer(max_batch_size=MAX_BATCH,
                                 max_latency_ms=2.0, name="compile-smoke")
    server.load("mlp", symbol=build(), params=params)
    warmed = server.warm(
        "mlp", sample_signature=[("data", (IN_DIM,), "float32")])
    if not warmed or max(warmed) != MAX_BATCH:
        _fail(f"warmup did not cover the ladder: {warmed}")
    print(f"warmed ladder {warmed} into {mxc.active_dir()}")

    traces_warm = mxc.LEDGER.trace_count(callsite="serving.executor_cache")
    if traces_warm != len(warmed):
        _fail(f"warmup traced {traces_warm} serving executors, expected "
              f"exactly the ladder size {len(warmed)}")
    if mxc.active_dir() is None:
        _fail("persistent compilation cache did not activate")
    artifacts = [f for f in os.listdir(mxc.active_dir())
                 if f.endswith("-cache")]
    if not artifacts:
        _fail("no compiled executables were persisted during warmup")
    compiles_warm = mxc.LEDGER.compiles()

    # -- burst: mixed-size waves, every one inside the warmed ladder ---------
    answered = 0
    for wave in (1, 3, MAX_BATCH, 2, 5, 7, MAX_BATCH, 4):
        futs = [server.predict_async(
                    "mlp",
                    {"data": rng.randn(IN_DIM).astype(np.float32)})
                for _ in range(wave)]
        for f in futs:
            f.result(60.0)
            answered += 1

    traces_burst = mxc.LEDGER.trace_count(callsite="serving.executor_cache")
    if traces_burst != traces_warm:
        _fail(f"{traces_burst - traces_warm} post-warmup retrace(s): a "
              "request paid a compile after the ladder was warmed")
    compiles_burst = mxc.LEDGER.compiles()
    if compiles_burst != compiles_warm:
        _fail(f"{compiles_burst - compiles_warm} post-warmup backend "
              "compile(s) on the request path")
    try:
        mxc.LEDGER.assert_trace_budget(len(warmed),
                                       callsite="serving.executor_cache")
    except AssertionError as e:
        _fail(str(e))
    server.shutdown()

    # -- planner beats pow2 on a skewed histogram ----------------------------
    hist = {1: 900, 3: 500, 7: 80, 20: 20, 32: 5}
    planned = mxc.plan_ladder(hist, max_ladder=4, max_batch=32)
    pow2 = mxc.pow2_ladder(32)
    w_planned = mxc.padding_waste(hist, planned)
    w_pow2 = mxc.padding_waste(hist, pow2)
    if not any(b & (b - 1) for b in planned):
        _fail(f"planner returned a pure power-of-two ladder {planned} "
              "on a skewed histogram")
    if w_planned >= w_pow2:
        _fail(f"planned ladder {planned} wastes {w_planned} >= pow2 "
              f"{w_pow2}")
    print(f"planner: {planned} waste {w_planned} vs pow2 {w_pow2} "
          f"(-{1 - w_planned / w_pow2:.0%})")

    # -- watchdog stayed silent ----------------------------------------------
    if telemetry.watchdog.fires() != 0:
        _fail(f"watchdog fired ({telemetry.watchdog.last_dump()})")

    print(f"compile smoke OK: ladder {warmed} warmed with "
          f"{traces_warm} traces, {answered} requests answered with 0 "
          "post-warmup traces/compiles, planner beats pow2, "
          "watchdog silent")


if __name__ == "__main__":
    main()
