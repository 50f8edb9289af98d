"""Numerics observatory (ISSUE 14): in-trace training-health telemetry,
non-finite sentinels, anomaly forensics.

Acceptance surface:

* armed windows change NOTHING — weights bitwise-identical with
  MXNET_NUMERICS on vs off (SGD / momentum / Adam, K=8 scan and a
  dp×tp mesh) and dispatches/step unchanged;
* a ``train/poison_grad`` chaos injection is detected within one
  window, drives the default-pack ``nonfinite_window`` alert
  pending→firing (visible in /alerts.json), lands in the flight ring,
  and writes a forensic dump naming the poisoned window;
* skip mode continues training past one poisoned window bit-identically
  to a manual skip; halt mode raises typed ``NonFiniteError``;
* the serving output-health guard fails non-finite rows typed, never
  serves them, and the pool keeps answering healthy requests;
* installing a legacy Monitor still opts out of fusion, with
  ``monitor.numerics_summary()`` as the fused-compatible alternative.
"""
import glob
import json
import os
import urllib.request

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import io as mxio
from mxnet_tpu import profiler as prof
from mxnet_tpu.base import NonFiniteError
from mxnet_tpu.chaos import failpoints as chaos
from mxnet_tpu.telemetry import flight, numerics

_ENV_KEYS = ("MXNET_FUSED_STEP", "MXNET_SCAN_STEPS", "MXNET_NUMERICS",
             "MXNET_NUMERICS_GRAD_NORM_MAX", "MXNET_MESH_FUSED_STEP")


@pytest.fixture(autouse=True)
def _numerics_env(tmp_path, monkeypatch):
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    monkeypatch.setenv("MXNET_NUMERICS_DUMP_DIR", str(tmp_path))
    chaos.reset()
    numerics._reset_for_tests()
    yield
    chaos.reset()
    numerics._reset_for_tests()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    numerics.configure()


def _mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _init_params(seed=5):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, 20) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}


def _dataset(n, feat=20, seed=3):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, feat).astype(np.float32),
            rng.randint(0, 10, n).astype(np.float32))


def _fit(mode, x, y, scan_steps=8, optimizer="sgd", opt_params=None,
         pre_keys=0, batch_size=16):
    os.environ["MXNET_FUSED_STEP"] = "1"
    os.environ["MXNET_SCAN_STEPS"] = str(scan_steps)
    os.environ["MXNET_NUMERICS"] = mode
    numerics.configure()
    mx.random.seed(0)
    from mxnet_tpu import random as mxrand
    for _ in range(pre_keys):
        mxrand.next_key()
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y),
                          batch_size=batch_size,
                          label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer=optimizer,
            optimizer_params=opt_params or {"learning_rate": 0.05},
            arg_params={k: v.copy() for k, v in _init_params().items()})
    params, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in params.items()}


def _opt_state_leaves(mod):
    import pickle
    states = pickle.loads(mod.get_optimizer_states())
    leaves = {}
    for i in states:
        s = states[i] if isinstance(states[i], tuple) else (states[i],)
        leaves[i] = [x.asnumpy() for x in s if x is not None]
    return leaves


# -- parity: armed observation changes nothing -------------------------------
@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_armed_scan_bitwise_parity(optimizer, opt_params):
    """MXNET_NUMERICS=warn over a K=8 scanned fit: weights AND optimizer
    state bitwise-identical to numerics-off, dispatches/step unchanged
    (the stats ride the existing donated window)."""
    x, y = _dataset(256)  # 16 batches -> 2 windows of K=8
    prof.reset_dispatch_counts()
    m_off, p_off = _fit("off", x, y, optimizer=optimizer,
                        opt_params=dict(opt_params))
    d_off = prof.dispatch_counts().get("total", 0)
    numerics._reset_for_tests()
    prof.reset_dispatch_counts()
    m_on, p_on = _fit("warn", x, y, optimizer=optimizer,
                      opt_params=dict(opt_params))
    d_on = prof.dispatch_counts().get("total", 0)
    assert d_on == d_off, "armed numerics changed the dispatch count"
    for k in p_off:
        assert np.array_equal(p_off[k], p_on[k]), f"param {k} diverged"
    ls, lq = _opt_state_leaves(m_on), _opt_state_leaves(m_off)
    for i in ls:
        for a, b in zip(ls[i], lq[i]):
            assert np.array_equal(a, b), f"optimizer state {i} diverged"
    s = numerics.summary()
    assert s["steps"] == 16 and s["nonfinite_windows"] == 0
    # the in-trace stats landed in the history with sane values
    last = numerics.history()[-1]
    assert last["kind"] == "scan_window"
    assert np.isfinite(last["grad_norm"]) and last["grad_norm"] > 0
    assert np.isfinite(last["param_norm"]) and last["param_norm"] > 0
    assert last["update_ratio"] > 0  # window-cadence slot, last row
    assert last["nonfinite"] == 0


def test_armed_single_fused_step_parity():
    """K=1 (plain fused step): parity + per-step observation."""
    x, y = _dataset(64)
    _m, p_off = _fit("off", x, y, scan_steps=1)
    numerics._reset_for_tests()
    _m, p_on = _fit("warn", x, y, scan_steps=1)
    for k in p_off:
        assert np.array_equal(p_off[k], p_on[k]), f"param {k} diverged"
    s = numerics.summary()
    assert s["steps"] == 4
    assert numerics.history()[-1]["kind"] == "fused_step"


def test_armed_mesh_bitwise_parity():
    """MXNET_NUMERICS=warn under the dp×tp mesh-fused window: weights
    bitwise-identical to off, mesh dispatches unchanged."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from mxnet_tpu.parallel import fused as F
    build, init, rng = F._mesh_models()
    K, NB, BS = 4, 8, 16
    x = rng.randn(NB * BS, 50).astype(np.float32)
    y = rng.randint(0, 10, NB * BS).astype(np.float32)
    opt = {"learning_rate": 0.1, "momentum": 0.9}
    os.environ["MXNET_NUMERICS"] = "off"
    numerics.configure()
    p_off, s_off, c_off, _m = F._run_mesh_fit(
        K, NB, BS, "sgd", opt, build, init, x, y)
    os.environ["MXNET_NUMERICS"] = "warn"
    numerics.configure()
    p_on, s_on, c_on, _m = F._run_mesh_fit(
        K, NB, BS, "sgd", opt, build, init, x, y)
    assert c_on.get("mesh_window") == c_off.get("mesh_window") == NB // K
    assert c_on.get("total") == c_off.get("total")
    for k in p_off:
        np.testing.assert_array_equal(p_off[k], p_on[k], err_msg=k)
    for i in s_off:
        for a, b in zip(F._state_arrays(s_on[i]),
                        F._state_arrays(s_off[i])):
            np.testing.assert_array_equal(a, b, err_msg=f"state {i}")
    assert numerics.summary()["steps"] == NB
    assert numerics.history()[-1]["kind"] == "mesh_window"


# -- detection: poison -> alert + flight + forensics -------------------------
def test_poison_detected_with_alert_flight_and_dump(tmp_path):
    """The acceptance gate: a train/poison_grad injection is detected
    within one window, drives the default-pack nonfinite_window rule
    pending->firing (visible in /alerts.json), lands in the flight
    ring, and writes a forensic dump naming the poisoned window."""
    from mxnet_tpu.telemetry import alerts
    from mxnet_tpu.telemetry.alerts import AlertEngine
    from mxnet_tpu.telemetry.exporter import start_exporter, stop_exporter

    flight.enable()
    flight.clear()
    eng = AlertEngine()  # the DEFAULT pack, real registry sampler
    alerts.set_engine(eng)
    try:
        x, y = _dataset(256)
        os.environ["MXNET_NUMERICS"] = "warn"
        numerics.configure()
        eng.tick(now=1.0)  # rate baseline BEFORE the poison
        chaos.arm("train/poison_grad", "raise", hits=2, count=1)
        _fit("warn", x, y)  # window 2 of 2 poisoned
        chaos.reset()
        s = numerics.summary()
        assert s["nonfinite_windows"] == 1, s

        # alert: pending -> firing on the very next tick (for_s=0)
        eng.tick(now=2.0)
        assert eng.state("nonfinite_window")["state"] == "firing"
        transitions = [t["to"] for t in
                       eng.transitions("nonfinite_window")]
        assert transitions[:2] == ["pending", "firing"]

        # visible in /alerts.json
        import mxnet_tpu.telemetry.alerts as alerts_mod
        orig_armed = alerts_mod._armed
        alerts_mod._armed = True
        port = start_exporter(0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/alerts.json",
                    timeout=10) as r:
                doc = json.loads(r.read().decode())
            assert "nonfinite_window" in doc["firing"]
            assert "nonfinite_window" in doc["pages"]
        finally:
            alerts_mod._armed = orig_armed
            stop_exporter()

        # flight ring carries the detection event
        evs = [e for e in flight.events()
               if e["category"] == "numerics"
               and e["event"] == "nonfinite_window"]
        assert evs and evs[0]["severity"] == "error"
        assert evs[0]["fields"]["kind"] == "scan_window"

        # forensic dump names the poisoned window + evidence
        dumps = sorted(glob.glob(
            os.path.join(str(tmp_path), "mxnet-numerics-*.json")))
        assert dumps, "no forensic dump written"
        doc = json.load(open(dumps[0]))
        assert doc["verdict"] == "nonfinite"
        assert doc["window"] == 2 and doc["kind"] == "scan_window"
        assert doc["bad_step"] == 9  # first step of window 2
        assert doc["rng_key_path"] is not None
        assert doc["window_stats"] and doc["history"]
        assert doc["nonfinite_by_bucket"], "no bucket named"
    finally:
        alerts.set_engine(None)


def test_skip_mode_matches_manual_skip_bitwise():
    """Skip mode drops a poisoned window's updates ON DEVICE and
    continues bit-identically to a manual skip (same key stream, second
    window's batches only)."""
    x, y = _dataset(256)  # 2 windows of K=8
    chaos.arm("train/poison_grad", "raise", hits=1, count=1)
    m_a, p_a = _fit("skip", x, y)
    chaos.reset()
    s = numerics.summary()
    assert s["nonfinite_windows"] == 1 and s["skipped_updates"] == 8
    # manual-skip reference: consume window 1's 8 keys, train only on
    # window 2's batches, numerics off
    numerics._reset_for_tests()
    m_b, p_b = _fit("off", x[128:], y[128:], pre_keys=8)
    for k in p_a:
        assert np.array_equal(p_a[k], p_b[k]), f"param {k} diverged"
    ls, lq = _opt_state_leaves(m_a), _opt_state_leaves(m_b)
    for i in ls:
        for a, b in zip(ls[i], lq[i]):
            assert np.array_equal(a, b), f"optimizer state {i} diverged"


def test_halt_mode_raises_typed_nonfinite_error(tmp_path):
    """halt: the boundary check raises NonFiniteError carrying the
    poisoned step + dump path; the fit does NOT degrade into per-batch
    fallback steps."""
    x, y = _dataset(256)
    chaos.arm("train/poison_grad", "raise", hits=1, count=1)
    with pytest.raises(NonFiniteError) as ei:
        _fit("halt", x, y)
    assert ei.value.retryable is False
    assert ei.value.step == 1
    assert ei.value.dump_path and os.path.exists(ei.value.dump_path)


def test_grad_norm_max_rule_breach(monkeypatch):
    """MXNET_NUMERICS_GRAD_NORM_MAX: a finite window breaching the
    bound is judged rule_breach (flight event + dump, warn mode
    continues)."""
    monkeypatch.setenv("MXNET_NUMERICS_GRAD_NORM_MAX", "1e-6")
    flight.enable()
    flight.clear()
    x, y = _dataset(128)
    _fit("warn", x, y)
    s = numerics.summary()
    assert s["rule_breach_windows"] >= 1
    evs = [e for e in flight.events()
           if e["category"] == "numerics"
           and e["event"] == "grad_norm_breach"]
    assert evs


# -- serving output-health guard ---------------------------------------------
def test_serving_guard_fails_nonfinite_rows_typed():
    """A model producing NaN outputs fails THOSE requests typed
    (NonFiniteError, never served), bumps the serving counter, and the
    pool keeps serving healthy requests."""
    from mxnet_tpu import serving, telemetry

    sym = mx.sym.log(mx.sym.Variable("data"))  # negative input -> nan
    server = serving.ModelServer(max_batch_size=4, max_latency_ms=2.0,
                                 name="nf-unit")
    try:
        server.load("m", symbol=sym, params={})
        ok = server.predict("m", {"data": np.ones(3, np.float32)})
        assert np.allclose(np.asarray(ok[0]), 0.0)
        with pytest.raises(NonFiniteError):
            server.predict("m", {"data": -np.ones(3, np.float32)})
        # survivors keep serving
        again = server.predict("m", {"data": 2 * np.ones(3, np.float32)})
        assert np.allclose(np.asarray(again[0]), np.log(2.0))
        fam = telemetry.REGISTRY.get(
            "mxnet_numerics_serving_nonfinite_total")
        assert fam is not None
        assert sum(s[2] for s in fam._samples()) >= 1
        assert server.stats().get("nonfinite_total", 0) >= 1
    finally:
        server.shutdown()


def test_serving_guard_disabled_serves_raw(monkeypatch):
    """MXNET_NUMERICS_SERVING=0: the screen is off — non-finite rows
    resolve (documented escape hatch)."""
    from mxnet_tpu import serving
    monkeypatch.setenv("MXNET_NUMERICS_SERVING", "0")
    numerics.configure()
    sym = mx.sym.log(mx.sym.Variable("data"))
    server = serving.ModelServer(max_batch_size=4, max_latency_ms=2.0,
                                 name="nf-off")
    try:
        server.load("m", symbol=sym, params={})
        out = server.predict("m", {"data": -np.ones(3, np.float32)})
        assert np.isnan(np.asarray(out[0])).all()
    finally:
        server.shutdown()
        monkeypatch.delenv("MXNET_NUMERICS_SERVING")
        numerics.configure()


# -- legacy Monitor compatibility --------------------------------------------
def test_monitor_opts_out_of_fusion_and_numerics_is_the_alternative():
    """Documented contract: installing a Monitor keeps the per-op loop
    (no fused/scan engagement), and monitor.numerics_summary() serves
    Monitor.toc()-shaped rows from the fused-compatible observatory."""
    from mxnet_tpu import monitor as monitor_mod
    x, y = _dataset(64)
    os.environ["MXNET_FUSED_STEP"] = "1"
    os.environ["MXNET_SCAN_STEPS"] = "8"
    os.environ["MXNET_NUMERICS"] = "warn"
    numerics.configure()
    mx.random.seed(0)
    mon = monitor_mod.Monitor(interval=1, pattern="$^")  # match nothing
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=16,
                          label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    prof.reset_dispatch_counts()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            arg_params={k: v.copy() for k, v in _init_params().items()},
            monitor=mon)
    counts = prof.dispatch_counts()
    assert counts.get("fused_step", 0) == 0, \
        "monitor did not opt the module out of the fused step"
    assert counts.get("scan_window", 0) == 0
    assert mod._fused is None and mod._scan is None
    # the monitored loop produced NO observatory rows (per-op path) —
    # now run fused without the monitor and read the summary
    numerics._reset_for_tests()
    _fit("warn", x, y)
    rows = monitor_mod.numerics_summary()
    assert rows, "numerics_summary is empty after an armed fit"
    step, stat, val = rows[-1]
    assert isinstance(step, int) and isinstance(val, str)
    assert stat in ("grad_norm", "param_norm", "update_ratio", "loss")
    stats_seen = {r[1] for r in rows}
    assert {"grad_norm", "param_norm", "update_ratio",
            "loss"} <= stats_seen


# -- plumbing ----------------------------------------------------------------
def test_stat_groups_contiguous_and_bounded():
    groups, labels = numerics.stat_groups(
        [(1 << 18,), (1 << 18,), (8,)], ["float32"] * 3,
        names=["a", "b", "c"], bucket_mb=1.0)
    # 1 MB each under a 1 MB budget -> one param per bucket + the tail
    assert groups == [[0], [1], [2]]
    assert labels == ["a", "b", "c"]
    groups, labels = numerics.stat_groups(
        [(8,), (8,), (8,)], ["float32", "float16", "float32"],
        names=["a", "b", "c"], bucket_mb=64)
    assert groups == [[0], [1], [2]]  # dtype boundary splits


def test_registry_families_and_collector():
    """Armed windows export the mxnet_numerics_* families (plain
    registry metrics: they ride the fleet push) and the collector
    snapshot."""
    from mxnet_tpu import telemetry
    x, y = _dataset(128)
    _fit("warn", x, y)
    dump = telemetry.prometheus_dump()
    for fam in ("mxnet_numerics_grad_norm", "mxnet_numerics_param_norm",
                "mxnet_numerics_update_ratio", "mxnet_numerics_loss",
                "mxnet_numerics_steps_total"):
        assert fam in dump, f"{fam} missing from the scrape"
    snap = telemetry.snapshot()["numerics"]
    assert snap["mode"] == "warn" and snap["steps"] >= 8


def test_bad_mode_rejected(monkeypatch):
    from mxnet_tpu.base import MXNetError
    monkeypatch.setenv("MXNET_NUMERICS", "loud")
    with pytest.raises(MXNetError):
        numerics.configure()


def test_disabled_boundary_check_is_cheap():
    """mode=off: observe_window is an early-out (< 1 us, the
    span/trace/failpoint bar — bench-gated too)."""
    import time
    assert not numerics.armed()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        numerics.observe_window(None, "t", 0, 0)
    per = (time.perf_counter() - t0) / n
    assert per < 1e-6, f"disabled boundary check costs {per * 1e9:.0f} ns"


def test_loss_scaler_feed_from_window():
    """An attached LossScaler consumes the window's per-step flags:
    a poisoned window backs the scale off exactly like update_scale."""
    from mxnet_tpu.amp import LossScaler
    scaler = LossScaler(init_scale=2. ** 10, scale_window=1000)
    numerics.attach_loss_scaler(scaler)
    try:
        x, y = _dataset(256)
        chaos.arm("train/poison_grad", "raise", hits=1, count=1)
        _fit("skip", x, y)
        chaos.reset()
        # window 1: 8 poisoned steps halve 8 times; window 2 clean
        assert scaler.loss_scale == 2. ** 10 / 2 ** 8
    finally:
        numerics.detach_loss_scaler(scaler)
