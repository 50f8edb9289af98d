"""Multi-step scanned training: one donated XLA dispatch per K steps.

Covers the contracts of ``fused_step.ScanTrainStep`` and
``Module._fit_epoch_scan``:

* bitwise parity — a K-step scanned fit epoch == K sequential fused
  steps for SGD / SGD-momentum / Adam, including optimizer state and an
  lr schedule advancing INSIDE the window;
* partial tail — an epoch whose length is not divisible by K finishes
  through the per-batch path, bit-identical to the sequential loop;
* MXNET_SCAN_ACCUM — M micro-batches per scan step match a single
  M-times-larger batch (up to fp summation order), with Module-computed
  rescale_grad covering the effective batch;
* one trace per configuration across a whole epoch (lr schedules and
  window count never retrace the scan);
* dispatch budget — <= (1+eps)/K framework dispatches per train step;
* checkpoint triggers landing mid-window defer to the window boundary
  with the boundary's step number;
* metric interval x scan — flushes round up to window boundaries and
  stacked buffers drain exactly once (no double-count on epoch end);
* watchdog deadline scaling and the scan_window_steps gauge /
  window-aware step-timer accounting.
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mxio
from mxnet_tpu import profiler as prof


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
    x = rng.randn(n, feat).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.float32)
    return x, y


def _fit(monkeypatch, scan_steps, x, y, batch_size=16, num_epoch=1,
         optimizer="sgd", opt_params=None, accum=1, metric="acc",
         batch_end_callback=None, last_batch_handle="pad"):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_SCAN_STEPS", str(scan_steps))
    monkeypatch.setenv("MXNET_SCAN_ACCUM", str(accum))
    mx.random.seed(0)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y),
                          batch_size=batch_size,
                          label_name="softmax_label",
                          last_batch_handle=last_batch_handle)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer=optimizer,
            optimizer_params=opt_params or {"learning_rate": 0.05},
            arg_params={k: v.copy() for k, v in _init_params().items()},
            eval_metric=metric, batch_end_callback=batch_end_callback)
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


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_scan_parity_bitwise(monkeypatch, optimizer, opt_params):
    """A K=4 scanned epoch == the sequential fused loop bit for bit,
    including optimizer state and an lr schedule advancing inside the
    window."""
    x, y = _dataset(128)  # 8 batches of 16 -> 2 windows of K=4
    opt_params = dict(opt_params)
    opt_params["lr_scheduler"] = mx.lr_scheduler.FactorScheduler(
        step=1, factor=0.9)
    ms, ps = _fit(monkeypatch, 4, x, y, num_epoch=2, optimizer=optimizer,
                  opt_params=dict(opt_params))
    assert ms._scan is not None and ms._scan.windows == 4, \
        "scanned windows did not engage"
    mq, pq = _fit(monkeypatch, 1, x, y, num_epoch=2, optimizer=optimizer,
                  opt_params=dict(opt_params))
    for k in ps:
        assert np.array_equal(ps[k], pq[k]), f"param {k} diverged"
    ls, lq = _opt_state_leaves(ms), _opt_state_leaves(mq)
    for i in ls:
        for a, b in zip(ls[i], lq[i]):
            assert np.array_equal(a, b), f"optimizer state {i} diverged"
    # the schedule advanced the same number of steps on both paths
    assert ms._optimizer.num_update == mq._optimizer.num_update == 16


def test_scan_partial_tail(monkeypatch):
    """n % K != 0: full windows scan, the tail runs per-batch — still
    bit-identical to the sequential loop, and the scan trace count stays
    at one across the whole epoch."""
    x, y = _dataset(160)  # 10 batches: 2 windows of 4 + tail of 2
    ms, ps = _fit(monkeypatch, 4, x, y)
    mq, pq = _fit(monkeypatch, 1, x, y)
    for k in ps:
        assert np.array_equal(ps[k], pq[k]), f"param {k} diverged"
    assert ms._scan is not None
    assert ms._scan.windows == 2
    assert ms._scan._scan_trace_count == 1, "scan retraced mid-epoch"
    # tail went through the single-step fused path
    assert ms._fused is not None and ms._fused.steps == 2


def test_scan_dispatch_budget(monkeypatch):
    """<= (1+eps)/K dispatches per train step at K=8 over a warm
    epoch."""
    K = 8
    x, y = _dataset(256)  # 16 batches
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_SCAN_STEPS", str(K))
    mx.random.seed(0)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=16,
                          label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            arg_params={k: v.copy() for k, v in _init_params().items()})
    it.reset()
    prof.reset_dispatch_counts()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05})
    counts = prof.dispatch_counts()
    assert counts.get("scan_window") == 2
    assert counts.get("total", 0) / 16 <= (1 + 0.25) / K, counts


def test_scan_accum_matches_large_batch(monkeypatch):
    """K x M accumulation == one M-times-larger batch per update (up to
    fp summation order), with Module-computed rescale_grad covering the
    effective batch on both paths."""
    x, y = _dataset(128)
    ma, pa = _fit(monkeypatch, 2, x, y, batch_size=16, accum=4,
                  opt_params={"learning_rate": 0.1, "momentum": 0.9})
    mb, pb = _fit(monkeypatch, 1, x, y, batch_size=64,
                  opt_params={"learning_rate": 0.1, "momentum": 0.9})
    assert ma._optimizer.rescale_grad == mb._optimizer.rescale_grad == \
        1.0 / 64
    # both applied 2 updates over 64-sample effective batches
    assert ma._optimizer.num_update == mb._optimizer.num_update == 2
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=2e-5, atol=1e-7,
                                   err_msg=f"accum param {k} diverged")


def test_scan_accum_without_eligibility_warns_and_disables(monkeypatch,
                                                           caplog):
    """ACCUM > 1 with a non-fusable optimizer cannot silently train with
    per-micro-batch updates: it warns and runs the plain loop."""
    import logging
    x, y = _dataset(64)
    with caplog.at_level(logging.WARNING):
        mod, _ = _fit(monkeypatch, 2, x, y, accum=4, optimizer="adagrad",
                      opt_params={"learning_rate": 0.05})
    assert mod._scan_disabled
    assert any("gradient accumulation" in r.message
               for r in caplog.records)


def test_scan_window_error_reaches_the_caller_of_fit(monkeypatch):
    """An error raised inside a scanned window (a compile error, an HBM
    overflow) is not a reason to train per batch instead: it propagates,
    and nothing is marked disabled behind the caller's back."""
    from mxnet_tpu.fused_step import ScanTrainStep

    def boom(self, sbatch):
        raise RuntimeError("injected window failure")

    monkeypatch.setattr(ScanTrainStep, "run_window", boom)
    x, y = _dataset(64)
    with pytest.raises(RuntimeError, match="injected window failure"):
        _fit(monkeypatch, 2, x, y)


def test_scan_checkpoint_mid_window_defers_to_boundary(monkeypatch,
                                                       tmp_path):
    """A checkpoint trigger aimed at a mid-window batch runs at the
    window boundary: the saved params are the boundary params and the
    step number is the boundary's update count."""
    from mxnet_tpu.checkpoint import CheckpointManager
    x, y = _dataset(128)  # 8 batches, K=4 -> boundaries after 4 and 8
    saved = {}

    def maybe_save(param):
        mod = param.locals["self"]
        if param.nbatch == 1 and "step" not in saved:
            # mid-window trigger: by the time callbacks run, the whole
            # window has been applied — save the boundary state
            saved["step"] = mod._optimizer.num_update
            saved["mgr"].save_module(mod, saved["step"], block=True)

    with CheckpointManager(str(tmp_path), async_save=False) as mgr:
        saved["mgr"] = mgr
        ms, _ = _fit(monkeypatch, 4, x, y,
                     opt_params={"learning_rate": 0.05, "momentum": 0.9},
                     batch_end_callback=maybe_save)
        assert saved["step"] == 4, \
            "mid-window trigger did not defer to the boundary step"
        assert mgr.latest() == 4
        ckpt = mgr.restore(4)
    # sequential reference: params after exactly 4 steps
    seq = {}

    def capture(param):
        if param.nbatch == 3 and not seq:
            mod = param.locals["self"]
            ap, _ = mod.get_params()
            seq.update({k: v.asnumpy() for k, v in ap.items()})

    _fit(monkeypatch, 1, x, y,
         opt_params={"learning_rate": 0.05, "momentum": 0.9},
         batch_end_callback=capture)
    for k, v in seq.items():
        got = np.asarray(ckpt.arrays[f"arg:{k}"])
        assert np.array_equal(got, v), \
            f"checkpointed {k} is not the boundary state"


def test_scan_metric_interval_rounds_to_window(monkeypatch):
    """MXNET_METRIC_SYNC_INTERVAL x scan: metric inputs come back
    stacked per window, flushes round up to window boundaries, and
    epoch-end drains exactly once (no double count)."""
    monkeypatch.setenv("MXNET_METRIC_SYNC_INTERVAL", "6")
    x, y = _dataset(128)  # 8 batches of 16, K=4 -> 2 windows
    mod, _ = _fit(monkeypatch, 4, x, y, metric="acc")
    # interval 6 rounds up to the 2-window boundary (8 batches): every
    # sample counted exactly once
    # (fit's epoch end calls flush_metric_updates already)
    assert not mod._pending_metric
    # per-batch vs windowed metric values agree exactly
    monkeypatch.setenv("MXNET_METRIC_SYNC_INTERVAL", "1")
    mod1, _ = _fit(monkeypatch, 4, x, y, metric="acc")
    mod2, _ = _fit(monkeypatch, 1, x, y, metric="acc")
    assert not mod1._pending_metric and not mod2._pending_metric


def test_scan_metric_counts_every_sample(monkeypatch):
    """The stacked boundary flush feeds the metric every batch exactly
    once — same num_inst and value as the sequential loop."""
    x, y = _dataset(128)
    results = {}
    for scan, interval in ((4, "1"), (4, "5"), (1, "1")):
        monkeypatch.setenv("MXNET_METRIC_SYNC_INTERVAL", interval)
        monkeypatch.setenv("MXNET_FUSED_STEP", "1")
        monkeypatch.setenv("MXNET_SCAN_STEPS", str(scan))
        monkeypatch.setenv("MXNET_SCAN_ACCUM", "1")
        mx.random.seed(0)
        it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y),
                              batch_size=16, label_name="softmax_label")
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        metric = mx.metric.Accuracy()
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                arg_params={k: v.copy()
                            for k, v in _init_params().items()},
                eval_metric=metric)
        results[(scan, interval)] = (metric.num_inst, metric.get()[1])
    assert results[(4, "1")] == results[(4, "5")] == results[(1, "1")]
    assert results[(4, "1")][0] == 128


def test_scan_speedometer_flush(monkeypatch):
    """Speedometer at the window boundary drains the stacked buffers
    (flush_metric_updates path) and logs a sane running metric."""
    monkeypatch.setenv("MXNET_METRIC_SYNC_INTERVAL", "100")
    x, y = _dataset(128)
    mod, _ = _fit(monkeypatch, 4, x, y, metric="acc",
                  batch_end_callback=mx.callback.Speedometer(
                      batch_size=16, frequent=8, auto_reset=False))
    assert not mod._pending_metric, \
        "Speedometer flush left stacked window buffers pending"


def test_watchdog_scale_keeps_windows_silent(monkeypatch, tmp_path):
    """The armed fit deadline scales by the window size: a healthy
    window that beats once per K batch-times stays silent, a real wedge
    past the scaled deadline still fires."""
    from mxnet_tpu.telemetry import watchdog
    monkeypatch.setenv("MXNET_WATCHDOG_S", "0.15")
    monkeypatch.setenv("MXNET_WATCHDOG_DIR", str(tmp_path))
    fires0 = watchdog.fires()
    try:
        with watchdog.arm("train/fit"):
            watchdog.set_scale("train/fit", 8)
            # 3x the UNSCALED deadline with no beat: must stay silent
            time.sleep(0.45)
            watchdog.beat("train/fit")
            assert watchdog.fires() == fires0, \
                "watchdog fired on a healthy scaled window"
            # past the SCALED deadline: must fire
            deadline = time.monotonic() + 8 * 0.15 + 1.0
            while watchdog.fires() == fires0 and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert watchdog.fires() == fires0 + 1, \
                "watchdog stayed silent through a scaled-deadline wedge"
    finally:
        watchdog._stop_for_tests()


def test_scan_telemetry_window_accounting(monkeypatch):
    """Step-timer lanes attribute whole windows but amortize per step:
    the step count advances by K*M per window, `last` reports per-step
    values with the window size, and the scan_window_steps gauge is
    exported."""
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.enable()
    telemetry.reset_step_stats()
    try:
        x, y = _dataset(128)  # 8 batches, K=4
        _fit(monkeypatch, 4, x, y)
        bd = telemetry.step_breakdown()
        assert bd["steps"] == 8, bd
        assert bd["last"]["window_steps"] == 4
        assert bd["lanes"]["step_dispatch"] > 0
        # named lanes still cover the overwhelming share of step wall
        lane_total = sum(bd["lanes"].values())
        assert lane_total >= 0.5 * bd["wall_s"]
        dump = telemetry.prometheus_dump()
        assert "mxnet_scan_window_steps 4" in dump
    finally:
        telemetry.disable()


def test_scan_default_off_keeps_per_batch_path(monkeypatch):
    """MXNET_SCAN_STEPS default (1) is exactly yesterday's behavior: no
    ScanTrainStep is ever constructed."""
    x, y = _dataset(64)
    monkeypatch.delenv("MXNET_SCAN_STEPS", raising=False)
    monkeypatch.delenv("MXNET_SCAN_ACCUM", raising=False)
    mx.random.seed(0)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=16,
                          label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.initializer.Xavier())
    assert mod._scan is None
    assert mod._scan_plan() is None


# -- staging the next window while the current one runs ----------------------
class _ListIter(mxio.DataIter):
    """Prepared DataBatches in order; logs every ``next()``."""

    def __init__(self, batches, batch_size=16, feat=20, log=None):
        super().__init__(batch_size)
        self.batches = batches
        self.log = log if log is not None else []
        self.provide_data = [mxio.DataDesc("data", (batch_size, feat))]
        self.provide_label = [mxio.DataDesc("softmax_label", (batch_size,))]
        self.cur = 0

    def reset(self):
        self.cur = 0

    def next(self):
        if self.cur == len(self.batches):
            raise StopIteration
        self.log.append(("next", self.cur))
        self.cur += 1
        return self.batches[self.cur - 1]


def _batches(x, y, kind="ndarray", ctx=None, batch_size=16):
    """``x``/``y`` cut into DataBatches whose arrays are numpy
    (``numpy``) or NDArrays on ``ctx`` (default: the first CPU device)."""
    def wrap(a):
        return a.copy() if kind == "numpy" else mx.nd.array(a, ctx=ctx)
    return [mxio.DataBatch(data=[wrap(x[i:i + batch_size])],
                           label=[wrap(y[i:i + batch_size])], pad=0)
            for i in range(0, len(x), batch_size)]


def _fit_iter(monkeypatch, scan_steps, it, context=None, eval_metric="acc",
              batch_end_callback=None):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_SCAN_STEPS", str(scan_steps))
    monkeypatch.setenv("MXNET_SCAN_ACCUM", "1")
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp(), context=context or mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            arg_params={k: v.copy() for k, v in _init_params().items()},
            eval_metric=eval_metric, batch_end_callback=batch_end_callback)
    params, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in params.items()}


def _log_staging_and_dispatch(monkeypatch, log):
    """Log ``("stage", when, n batches)`` at every window staging and
    ``("dispatch", rows of the window's first column)`` at every scanned
    dispatch."""
    from mxnet_tpu.fused_step import ScanTrainStep
    stage, run = mxio._stage_window, ScanTrainStep.run_window

    def logged_stage(batches, ctx, host, when):
        log.append(("stage", when, len(batches)))
        return stage(batches, ctx, host, when)

    def logged_run(self, sbatch):
        log.append(("dispatch",
                    [float(v) for v in np.asarray(sbatch.data[0])[:, 0, 0]]))
        return run(self, sbatch)

    monkeypatch.setattr(mxio, "_stage_window", logged_stage)
    monkeypatch.setattr(ScanTrainStep, "run_window", logged_run)


def test_scan_stages_next_window_before_the_boundary(monkeypatch):
    """Window N+1 is collected and staged while window N's scan runs,
    before N's first metric update; nothing of N+1 is dispatched before
    N's boundary callbacks ran."""
    K = 4
    x, y = _dataset(16 * 3 * K)
    log = []

    class LoggedAcc(mx.metric.Accuracy):
        def update(self, labels, preds):
            log.append(("update",))
            super().update(labels, preds)

    _log_staging_and_dispatch(monkeypatch, log)
    it = _ListIter(_batches(x, y), log=log)
    mod, _ = _fit_iter(
        monkeypatch, K, it, eval_metric=LoggedAcc(),
        batch_end_callback=lambda p: log.append(("callback", p.nbatch)))
    assert mod._scan.windows == 3
    kinds = [(e[0], e[1]) if e[0] == "stage" else e[0] for e in log]
    nexts, boundary = ["next"] * K, ["update"] * K + ["callback"] * K
    assert kinds == (
        nexts + [("stage", "at_need"), "dispatch"]
        + nexts + [("stage", "ahead")] + boundary + ["dispatch"]
        + nexts + [("stage", "ahead")] + boundary + ["dispatch"]
        + boundary), kinds
    assert [e[1] for e in log if e[0] == "callback"] == list(range(3 * K))


@pytest.mark.parametrize("kind,batch_dev,module_dev", [
    ("numpy", None, 0),          # numpy arrays handed to device_put
    ("ndarray", 0, 1),           # CPU-backed NDArrays elsewhere: views
    ("ndarray", 1, 1),           # already on the module's device
])
def test_scan_staging_parity_bitwise(monkeypatch, kind, batch_dev,
                                     module_dev):
    """A scanned epoch fed numpy-backed, CPU-NDArray or already-on-device
    batches == the sequential fused loop, bit for bit."""
    K = 4
    x, y = _dataset(16 * 2 * K)
    batches = _batches(x, y, kind,
                       mx.cpu(batch_dev) if batch_dev is not None else None)
    ms, ps = _fit_iter(monkeypatch, K, _ListIter(batches),
                       context=mx.cpu(module_dev))
    assert ms._scan is not None and ms._scan.windows == 2
    _, pq = _fit(monkeypatch, 1, x, y,
                 opt_params={"learning_rate": 0.05, "momentum": 0.9})
    for k in ps:
        assert np.array_equal(ps[k], pq[k]), f"param {k} diverged"


def test_scan_short_groups_are_never_staged(monkeypatch):
    """n % K != 0 and a shape-mismatched batch mid-epoch: every batch
    trains exactly once, in arrival order, only full windows are staged,
    and the parameters are the sequential loop's."""
    K = 4
    x, y = _dataset(16 * 13)
    x[:, 0] = np.repeat(np.arange(13), 16)     # a batch's number, in it
    batches = _batches(x, y)
    short = batches[6]                         # half a batch, mid-epoch
    batches[6] = mxio.DataBatch(data=[short.data[0][:8]],
                                label=[short.label[0][:8]], pad=0)
    log = []
    _log_staging_and_dispatch(monkeypatch, log)
    per_batch = mx.mod.Module.forward_backward

    def logged_forward_backward(self, data_batch):
        log.append(("step", float(data_batch.data[0].asnumpy()[0, 0])))
        return per_batch(self, data_batch)

    monkeypatch.setattr(mx.mod.Module, "forward_backward",
                        logged_forward_backward)
    _, ps = _fit_iter(monkeypatch, K, _ListIter(batches))
    trained = []
    for e in log:
        if e[0] == "dispatch":
            trained += e[1]
        elif e[0] == "step":
            trained.append(e[1])
    assert trained == [float(i) for i in range(13)], trained
    assert {e[2] for e in log if e[0] == "stage"} == {K}
    # (the mismatched batch rebinds the executor, so the second window
    # runs in a new ScanTrainStep: count the dispatches, not .windows)
    assert sum(e[0] == "dispatch" for e in log) == \
        sum(e[0] == "stage" for e in log) == 2
    log.clear()
    _, pq = _fit_iter(monkeypatch, 1, _ListIter(batches))
    assert [e[1] for e in log if e[0] == "step"] == trained
    for k in ps:
        assert np.array_equal(ps[k], pq[k]), f"param {k} diverged"


@pytest.mark.parametrize("hits,when", [(1, "at_need"), (2, "ahead")])
def test_scan_staging_error_reaches_the_caller_of_fit(monkeypatch, hits,
                                                      when):
    """An error raised while a window is staged, at need or ahead (the
    failpoint ``io/stage``), propagates out of fit."""
    from mxnet_tpu.chaos import failpoints as fp
    x, y = _dataset(16 * 8)
    log = []
    _log_staging_and_dispatch(monkeypatch, log)
    fp.arm("io/stage", "raise", hits=hits, count=1)
    try:
        with pytest.raises(fp.ChaosInjectedError):
            _fit_iter(monkeypatch, 4, _ListIter(_batches(x, y)))
    finally:
        fp.reset()
    assert [e[1] for e in log if e[0] == "stage"][-1] == when
    assert sum(e[0] == "dispatch" for e in log) == hits - 1


def test_scan_stage_counters(monkeypatch):
    """An epoch of full windows counts one window staged at need and the
    rest ahead, and every batch's bytes once — in the registry and in
    the records of ``io/stage_super``."""
    from mxnet_tpu import telemetry
    K, windows = 4, 3
    x, y = _dataset(16 * K * windows)
    staged = telemetry.REGISTRY.get("mxnet_io_stage_windows_total")
    nbytes = telemetry.REGISTRY.get("mxnet_io_stage_bytes_total")
    before = (staged.value({"when": "at_need"}),
              staged.value({"when": "ahead"}), nbytes.value())
    telemetry.enable()
    telemetry.reset_span_records()
    try:
        _fit_iter(monkeypatch, K, _ListIter(_batches(x, y)))
        records = [r for r in telemetry.span_records()
                   if r["name"] == "io/stage_super"]
    finally:
        telemetry.disable()
    assert staged.value({"when": "at_need"}) - before[0] == 1
    assert staged.value({"when": "ahead"}) - before[1] == windows - 1
    assert nbytes.value() - before[2] == x.nbytes + y.nbytes
    assert [r["counts"] for r in records] == [
        {"mxnet_io_stage_bytes_total": (x.nbytes + y.nbytes) // windows,
         "mxnet_io_stage_windows_total": 1}] * windows
    # one staging per step id from the second window on: the first
    # holds its own, at need, and the second's, ahead
    assert [r["step"] - records[0]["step"] for r in records] == [0, 0, 1]
