"""Fused train step: one donated XLA computation per step.

Covers the contracts in ``mxnet_tpu/fused_step.py``'s docstring:

* numerical parity with the per-param dispatch loop for SGD,
  SGD-momentum and Adam (fp32): bit for bit after one step in
  everything but the first layer, within 4 spacings there, within 5e-6
  after ten steps (``test_fused_parity_with_the_loop`` has the cause);
  bit for bit for multi-precision SGD at the optimizer level (fp16
  weights + fp32 master copies);
* donation safety — old weight buffers are actually donated (deleted)
  after a step, while externally-held arrays are defensively copied and
  survive;
* fallback — custom optimizers without ``fused_update``, kvstore setups,
  and the MXNET_FUSED_STEP=0 opt-out silently use the per-param loop;
* no recompiles across lr-schedule changes (trace counter stays at 1);
* checkpoint save/restore round-trips through a fused-step Module;
* MXNET_METRIC_SYNC_INTERVAL batching + Speedometer flush;
* the batched grad zeroing (no per-param dispatch, grads read as zeros);
* lr/wd reach the jitted step as two host float32 arrays (ISSUE 26): the
  call's host leaves do not grow with the model, the step's outputs are
  bit for bit what a Python float per tensor gave, per-parameter
  multipliers and schedules reach the right tensor, the scanned window
  and the fused step share the helpers, and a weight narrower than
  float32 keeps its dtype.
"""
import os

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


def _data(bs=16, feat=20, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(bs, feat).astype(np.float32)
    y = rng.randint(0, 10, bs).astype(np.float32)
    return mxio.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])


def _init_params(seed=5):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, 20) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}


def _make_module(optimizer="sgd", opt_params=None, fixed=None):
    mod = mx.mod.Module(_mlp(), context=mx.cpu(),
                        fixed_param_names=fixed)
    mod.bind(data_shapes=[("data", (16, 20))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(arg_params={k: v.copy()
                                for k, v in _init_params().items()})
    mod.init_optimizer(kvstore=None, optimizer=optimizer,
                       optimizer_params=opt_params or
                       {"learning_rate": 0.05})
    return mod


def _bufs(tree):
    """NDArray leaves of an optimizer-state tree as their jax buffers."""
    import jax
    return jax.tree_util.tree_map(
        lambda x: x._data if isinstance(x, mx.nd.NDArray) else x, tree)


def _opt_state_leaves(mod):
    """Every optimizer-state array of ``mod`` as numpy, by index."""
    import pickle
    states = pickle.loads(mod.get_optimizer_states())
    return {i: [x.asnumpy()
                for x in (s if isinstance(s, tuple) else (s,))
                if x is not None]
            for i, s in states.items()}


def _run_steps(mod, batch, steps):
    mx.random.seed(0)
    outs = []
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
        outs.append(mod.get_outputs()[0].asnumpy())
    params, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in params.items()}, outs


def _fused_and_loop(monkeypatch, optimizer, opt_params, steps):
    """The same ``steps`` train steps through the fused step and through
    the per-param loop: (params, outputs per step, optimizer state) of
    each."""
    batch = _data()
    runs = []
    for fused in ("1", "0"):
        monkeypatch.setenv("MXNET_FUSED_STEP", fused)
        prof.reset_dispatch_counts()
        mod = _make_module(optimizer, dict(opt_params))
        params, outs = _run_steps(mod, batch, steps)
        assert bool(prof.dispatch_counts().get("fused_step")) == \
            (fused == "1"), "the wrong path engaged"
        runs.append((params, outs, _opt_state_leaves(mod)))
    return runs


def _ulps(a, b):
    """max|a - b| in units of the float32 spacing at ``b``'s largest
    magnitude."""
    return float(np.abs(a - b).max() / np.spacing(np.abs(b).max()))


def _rel(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


_LAST_LAYER = {"fc2_weight": 2, "fc2_bias": 3}     # name -> state index
_FIRST_LAYER = {"fc1_weight": 0, "fc1_bias": 1}


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_fused_parity_with_the_loop(monkeypatch, optimizer, opt_params):
    """The per-param loop is the reference of every fused path; this is
    how closely the fused step agrees with it, and why not bit for bit.

    The loop runs forward and backward as TWO XLA programs (the vjp's
    residuals cross between them), the fused step as ONE.  Compiled as
    one, the CPU backend sums the first layer's backward products in
    another order: the cotangent that crosses the activation through
    ``fc2_weight`` (sums of 10) and, from it, ``fc1``'s gradients.  A
    jit of forward + vjp alone, with no optimizer in it, differs from
    the loop's gradients in exactly the same two tensors, so the update
    math is not the cause.  Everything that does not depend on that
    cotangent is bit-equal after one step: the outputs, the last
    layer's weights and its optimizer state.  The first layer's differ
    by at most 2 float32 spacings of the tensor's largest element
    (measured 0.25-2.0; bound 4).  Ten steps feed the difference back
    through the weights: measured 9e-8 to 4.7e-7 of the largest element
    (Adam the most, it divides by a root), bound 5e-6.
    """
    (pf, of, sf), (pl, ol, sl) = _fused_and_loop(
        monkeypatch, optimizer, opt_params, 1)
    assert np.array_equal(of[0], ol[0]), "step-1 outputs diverged"
    for name, idx in _LAST_LAYER.items():
        assert np.array_equal(pf[name], pl[name]), f"{name} diverged"
        for a, b in zip(sf[idx], sl[idx]):
            assert np.array_equal(a, b), f"state of {name} diverged"
    for name, idx in _FIRST_LAYER.items():
        assert _ulps(pf[name], pl[name]) <= 4, name
        for a, b in zip(sf[idx], sl[idx]):
            assert _ulps(a, b) <= 4, f"state of {name}"

    (pf, of, sf), (pl, ol, sl) = _fused_and_loop(
        monkeypatch, optimizer, opt_params, 10)
    for name in pf:
        assert _rel(pf[name], pl[name]) <= 5e-6, name
    for a, b in zip(of, ol):
        assert _rel(a, b) <= 5e-6, "outputs diverged"
    assert sf.keys() == sl.keys()
    for i in sf:
        for a, b in zip(sf[i], sl[i]):
            assert _rel(a, b) <= 5e-6, f"optimizer state {i}"


def test_fused_parity_multi_precision():
    """fp16 weights + multi_precision: fused_update mirrors the
    mp_sgd_mom_update per-param loop bit for bit (optimizer level — the
    Module binds fp32, so mp is exercised directly)."""
    import jax
    from mxnet_tpu import optimizer as opt_mod

    rng = np.random.RandomState(0)
    shapes = [(8, 4), (8,), (3, 8)]
    weights_l = [mx.nd.array(rng.randn(*s) * 0.5).astype(np.float16)
                 for s in shapes]
    weights_f = [w.copy() for w in weights_l]
    grads = [[mx.nd.array(rng.randn(*s)).astype(np.float16)
              for s in shapes] for _ in range(6)]

    def mk():
        return opt_mod.SGD(learning_rate=0.1, momentum=0.9, wd=1e-3,
                           multi_precision=True, rescale_grad=0.5)

    opt_l, opt_f = mk(), mk()
    upd = opt_mod.get_updater(opt_l)
    states_f = [opt_f.create_state_multi_precision(i, w)
                for i, w in enumerate(weights_f)]

    fused = jax.jit(lambda p, g, s, lrs, wds:
                    opt_f.fused_update(p, g, s, lrs, wds))
    bufs = [w._data for w in weights_f]
    sbufs = _bufs(states_f)
    for gs in grads:
        for i, (w, g) in enumerate(zip(weights_l, gs)):
            upd(i, g, w)
        idx = list(range(len(shapes)))
        for i in idx:
            opt_f._update_count(i)
        lrs, wds = opt_f.fused_hyperparams(idx)
        bufs, sbufs = fused(bufs, [g._data for g in gs], sbufs,
                            tuple(lrs), tuple(wds))
    for a, b in zip(weights_l, bufs):
        assert np.array_equal(a.asnumpy(), np.asarray(b)), \
            "mp weights diverged"


def test_donation_and_external_buffer_safety(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    batch = _data()
    mod = _make_module("sgd", {"learning_rate": 0.05, "momentum": 0.9})
    mod.forward_backward(batch)
    mod.update()  # first step unshares init-time aliases
    old = mod._exec.arg_dict["fc1_weight"]._data
    mod.forward_backward(batch)
    mod.update()
    # in-place buffer reuse: the pre-step weight buffer was donated
    assert old.is_deleted(), "weight buffer was not donated"
    assert mod._exec.arg_dict["fc1_weight"]._data is not old
    # externally-held params must NEVER be invalidated: set_params shares
    # buffers, the fused step copies them before donating
    ext = {k: v.copy() for k, v in _init_params().items()}
    mod.set_params(ext, {})
    mod.forward_backward(batch)
    mod.update()
    for k, v in ext.items():
        assert np.isfinite(v.asnumpy()).all(), f"external {k} invalidated"


def test_fallback_paths(monkeypatch):
    batch = _data()
    # custom optimizer without fused_update: silent per-param loop
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    mod = _make_module("adagrad", {"learning_rate": 0.05})
    prof.reset_dispatch_counts()
    mod.forward_backward(batch)
    mod.update()
    counts = prof.dispatch_counts()
    assert "fused_step" not in counts
    assert counts.get("graph", 0) == 2  # fwd + bwd dispatched separately
    assert mod._fused is None
    # explicit opt-out
    monkeypatch.setenv("MXNET_FUSED_STEP", "0")
    mod2 = _make_module("sgd", {"learning_rate": 0.05})
    prof.reset_dispatch_counts()
    mod2.forward_backward(batch)
    mod2.update()
    assert "fused_step" not in prof.dispatch_counts()
    # fixed params stay frozen on the fused path
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    mod3 = _make_module("sgd", {"learning_rate": 0.5},
                        fixed=["fc1_weight"])
    before = mod3._exec.arg_dict["fc1_weight"].asnumpy()
    mod3.forward_backward(batch)
    mod3.update()
    assert np.array_equal(before, mod3._exec.arg_dict["fc1_weight"]
                          .asnumpy())


def test_fused_step_error_reaches_the_caller(monkeypatch):
    """An error raised by tracing, compiling or running the fused step
    propagates — the per-param loop is chosen up front by eligibility,
    never as a reaction to a failure."""
    from mxnet_tpu.fused_step import FusedTrainStep

    def boom(self, data_batch):
        raise RuntimeError("injected step failure")

    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setattr(FusedTrainStep, "step", boom)
    mod = _make_module("sgd", {"learning_rate": 0.05})
    with pytest.raises(RuntimeError, match="injected step failure"):
        mod.forward_backward(_data())


def test_lr_schedule_no_recompile(monkeypatch):
    """lr/wd are step arguments, not trace constants: a changing lr
    schedule must not retrace, and the fused path stays <= 3
    dispatches/step."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    batch = _data()
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.8)
    mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9,
                               "lr_scheduler": sched})
    mod.forward_backward(batch)
    mod.update()
    prof.reset_dispatch_counts()
    for _ in range(6):
        mod.forward_backward(batch)
        mod.update()
    assert mod._fused is not None
    assert mod._fused._trace_count == 1, \
        "lr schedule caused a retrace"
    counts = prof.dispatch_counts()
    assert counts.get("fused_step") == 6
    assert counts.get("total", 0) / 6 <= 3
    # the schedule really advanced (lr decayed => smaller later steps)
    assert mod._optimizer.learning_rate < 0.1


def test_checkpoint_roundtrip_fused(monkeypatch, tmp_path):
    """save/restore through a fused-step Module is unchanged: a restored
    module continues bit-identically to the original."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    batch = _data()
    opt = {"learning_rate": 0.05, "momentum": 0.9}
    mod = _make_module("sgd", dict(opt))
    _run_steps(mod, batch, 3)
    prefix = str(tmp_path / "fused")
    mod.save_checkpoint(prefix, 0, save_optimizer_states=True)
    m2 = mx.mod.Module.load(prefix, 0, load_optimizer_states=True,
                            context=mx.cpu())
    m2.bind(data_shapes=[("data", (16, 20))],
            label_shapes=[("softmax_label", (16,))])
    m2.init_optimizer(kvstore=None, optimizer="sgd",
                      optimizer_params=dict(opt))
    pa, _ = _run_steps(mod, batch, 2)
    pb, _ = _run_steps(m2, batch, 2)
    for k in pa:
        assert np.array_equal(pa[k], pb[k]), f"{k} diverged after restore"


def test_metric_sync_interval(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_METRIC_SYNC_INTERVAL", "3")
    batch = _data()
    mod = _make_module("sgd", {"learning_rate": 0.01})
    metric = mx.metric.Accuracy()
    for i in range(4):
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)
        if i < 2:
            # buffered: no update reached the metric yet
            assert metric.num_inst == 0
        elif i == 2:
            # third call flushed all three batches at once
            assert metric.num_inst == 3 * 16
    assert metric.num_inst == 3 * 16  # 4th buffered again
    mod.flush_metric_updates()
    assert metric.num_inst == 4 * 16
    # Speedometer drains the buffer before reading the metric
    mod.forward_backward(batch)
    mod.update()
    mod.update_metric(metric, batch.label)
    from mxnet_tpu.model import BatchEndParam
    from mxnet_tpu.callback import Speedometer
    speedo = Speedometer(batch_size=16, frequent=1, auto_reset=False)
    param = BatchEndParam(epoch=0, nbatch=1, eval_metric=metric,
                          locals={"self": mod})
    speedo(param)  # first call arms the timer
    speedo(BatchEndParam(epoch=0, nbatch=2, eval_metric=metric,
                         locals={"self": mod}))
    assert metric.num_inst == 5 * 16, "Speedometer did not flush"


def test_metric_interval_matches_per_batch_sync(monkeypatch):
    """Interval-N metrics aggregate to exactly the per-batch values."""
    batches = [_data(seed=s) for s in range(5)]

    def score(interval):
        monkeypatch.setenv("MXNET_METRIC_SYNC_INTERVAL", str(interval))
        mod = _make_module("sgd", {"learning_rate": 0.05})
        metric = mx.metric.Accuracy()
        for b in batches:
            mod.forward_backward(b)
            mod.update()
            mod.update_metric(metric, b.label)
        mod.flush_metric_updates()
        return metric.get()[1]

    assert score(1) == score(2) == score(5)


def test_batched_grad_zeroing(monkeypatch):
    """After update() grads read as zeros with NO per-param zeroing
    dispatch: a loop-path step costs fwd+bwd (2 graph launches) plus one
    optimizer op per trainable param, nothing else."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "0")
    batch = _data()
    mod = _make_module("sgd", {"learning_rate": 0.05, "momentum": 0.9})
    mod.forward_backward(batch)
    mod.update()
    prof.reset_dispatch_counts()
    mod.forward_backward(batch)
    mod.update()
    counts = prof.dispatch_counts()
    n_params = len(mod._param_names)
    assert counts.get("graph") == 2
    assert counts.get("op", 0) == n_params, counts
    for name in mod._param_names:
        g = mod._exec.grad_dict.get(name)
        assert g is not None and not g.asnumpy().any(), \
            f"grad {name} not zeroed"


def test_stage_batch_and_partial_batch_fit(monkeypatch):
    """The fit loop's input double-buffer stages batches onto the device
    unchanged, and a partial final batch (shape mismatch) falls back to
    the loop path without breaking the epoch."""
    staged = mxio.stage_batch(_data(), mx.cpu())
    assert np.array_equal(staged.data[0].asnumpy(),
                          _data().data[0].asnumpy())
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    rng = np.random.RandomState(0)
    x = rng.randn(22, 20).astype(np.float32)  # 22 = 16 + partial 6
    y = rng.randint(0, 10, 22).astype(np.float32)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=16,
                          label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.initializer.Xavier())
    params, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in params.values())


# -- lr/wd as two host arrays (ISSUE 26) ----------------------------------

def _deep_mlp(layers):
    h = mx.sym.Variable("data")
    for i in range(layers - 1):
        h = mx.sym.FullyConnected(h, num_hidden=8, name=f"fc{i}")
        h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name=f"fc{layers - 1}")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def test_host_leaves_do_not_grow_with_the_model(monkeypatch):
    """What the fused step's call is handed that is not on the device:
    an lr vector, a wd vector and the poison scalar, for 4 parameter
    tensors as for 40 (read where the benchmark's ``step_host_args``
    reads it)."""
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    telemetry.enable()
    try:
        leaves = {}
        for layers in (2, 20):
            telemetry.reset_span_records()
            mod = mx.mod.Module(_deep_mlp(layers), context=mx.cpu())
            mod.bind(data_shapes=[("data", (16, 20))],
                     label_shapes=[("softmax_label", (16,))])
            mod.init_params(mx.initializer.Xavier())
            mod.init_optimizer(kvstore=None, optimizer="sgd",
                               optimizer_params={"learning_rate": 0.05,
                                                 "momentum": 0.9})
            for _ in range(2):
                mod.forward_backward(_data())
                mod.update()
            assert len(mod._fused._train_names) == 2 * layers
            counts = {r["counts"]["mxnet_step_host_arg_leaves"]
                      for r in telemetry.span_records()
                      if r["name"] == "fit/step/fused_dispatch"}
            assert len(counts) == 1, counts
            leaves[layers] = counts.pop()
    finally:
        telemetry.disable()
        telemetry.reset_span_records()
    assert leaves[2] == leaves[20] <= 4, leaves


def _float_tuples(opt, indices, steps=None):
    """What the fused step was handed before ISSUE 26: a Python float per
    tensor, each a weak ``float32`` scalar argument of the jitted step."""
    assert steps is None
    for i in indices:
        opt._update_count(i)
    lrs, wds = opt.fused_hyperparams(indices)
    assert all(type(v) is float for v in lrs + wds)
    return tuple(lrs), tuple(wds)


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_arrays_match_python_floats_bitwise(monkeypatch, optimizer,
                                            opt_params):
    """The traced step fed the two arrays returns bit for bit what it
    returns fed tuples of Python floats (float32: no cast is traced for
    either, so the tuples run the program the parent ran)."""
    from mxnet_tpu import fused_step
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    batch = _data()

    def run():
        sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.7)
        mod = _make_module(optimizer, dict(opt_params, lr_scheduler=sched))
        return (mod,) + _run_steps(mod, batch, 6)

    ma, pa, oa = run()
    monkeypatch.setattr(fused_step, "host_hyperparams", _float_tuples)
    mt, pt, ot = run()
    assert ma._fused._trace_count == mt._fused._trace_count == 1
    for k in pa:
        assert np.array_equal(pa[k], pt[k]), f"param {k} diverged"
    for a, b in zip(oa, ot):
        assert np.array_equal(a, b), "outputs diverged"
    sa, st = _opt_state_leaves(ma), _opt_state_leaves(mt)
    for i in sa:
        for a, b in zip(sa[i], st[i]):
            assert np.array_equal(a, b), f"optimizer state {i} diverged"


def test_multipliers_and_schedule_reach_their_tensor(monkeypatch):
    """Element i of the arrays is parameter i's: a zero ``lr_mult``
    freezes exactly its tensor, a large ``wd_mult`` shrinks exactly its
    tensor, an ``lr_scheduler`` advances, and none of it retraces.  The
    per-param loop, which never sees the arrays, is the reference."""
    batch = _data()

    def run(fused):
        monkeypatch.setenv("MXNET_FUSED_STEP", "1" if fused else "0")
        sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.8)
        mod = _make_module("sgd", {"learning_rate": 0.1, "wd": 1e-2,
                                   "lr_scheduler": sched})
        mod._optimizer.set_lr_mult({"fc2_bias": 0.0, "fc1_bias": 3.0})
        mod._optimizer.set_wd_mult({"fc2_weight": 80.0})
        params, _ = _run_steps(mod, batch, 6)
        return mod, params

    mf, pf = run(True)
    assert mf._fused is not None and mf._fused._trace_count == 1
    assert mf._optimizer.learning_rate < 0.1
    ml, pl = run(False)
    init = {k: v.asnumpy() for k, v in _init_params().items()}
    assert np.array_equal(pf["fc2_bias"], init["fc2_bias"])
    for k in ("fc1_weight", "fc1_bias", "fc2_weight"):
        assert not np.array_equal(pf[k], init[k]), f"{k} did not move"
    # 6 steps of lr * 80 * wd shrink fc2_weight and nothing else
    assert np.abs(pf["fc2_weight"]).sum() < \
        0.8 * np.abs(init["fc2_weight"]).sum()
    assert np.abs(pf["fc1_weight"]).sum() > \
        0.9 * np.abs(init["fc1_weight"]).sum()
    for k in pf:
        np.testing.assert_allclose(pf[k], pl[k], rtol=2e-5, atol=1e-6,
                                   err_msg=k)


def test_k1_window_matches_fused_step(monkeypatch):
    """A K=1 scanned window and a fused step from the same state give the
    same parameters and momenta: ``host_hyperparams`` and
    ``hyper_scalars`` serve both, one as a row, one as a 1-row window."""
    from mxnet_tpu.fused_step import ScanTrainStep
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    batch = _data()

    def make():
        sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.8)
        mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9,
                                   "wd": 1e-3, "lr_scheduler": sched})
        mod._optimizer.set_lr_mult({"fc1_bias": 3.0, "fc2_weight": 0.5})
        mod._optimizer.set_wd_mult({"fc2_weight": 7.0})
        return mod

    mf = make()
    pf, _ = _run_steps(mf, batch, 3)
    ms = make()
    scan = ScanTrainStep(ms, 1)
    mx.random.seed(0)
    for _ in range(3):
        outs = scan.run_window(mxio.stage_super_batch([batch], mx.cpu()))
        assert outs is not False
    assert scan._scan_trace_count == 1
    assert ms._optimizer.num_update == mf._optimizer.num_update == 3
    ps, _ = ms.get_params()
    for k in pf:
        assert np.array_equal(pf[k], ps[k].asnumpy()), f"param {k}"
    sf, ss = _opt_state_leaves(mf), _opt_state_leaves(ms)
    for i in sf:
        for a, b in zip(sf[i], ss[i]):
            assert np.array_equal(a, b), f"momentum {i} diverged"


@pytest.mark.parametrize("dtype,multi_precision", [
    ("float16", False), ("bfloat16", False), ("float16", True)])
def test_narrow_weights_keep_their_dtype(dtype, multi_precision):
    """A strong float32 scalar would promote a float16/bfloat16 update to
    float32; ``hyper_scalars`` casts it to what the weak Python float was
    converted to, so the new weight has the weight's dtype (donation still
    aliases it, a scan carries it) and the same bits, on the fused step's
    path and the scanned window's alike.  Under multi-precision the
    scalar stays float32, like the master copy."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.fused_step import host_hyperparams, hyper_scalars

    rng = np.random.RandomState(1)
    shapes = [(8, 4), (8,), (3, 8)]
    opt = opt_mod.SGD(learning_rate=0.1, momentum=0.9, wd=1e-2,
                      multi_precision=multi_precision,
                      param_idx2name={0: "a_weight", 1: "a_bias",
                                      2: "b_weight"})
    opt.set_lr_mult({"a_bias": 2.0})
    weights = [mx.nd.array(rng.randn(*s) * 0.5).astype(dtype)
               for s in shapes]
    states = [opt.create_state_multi_precision(i, w)
              for i, w in enumerate(weights)]
    params, states = _bufs(weights), _bufs(states)
    grads = [jnp.asarray(rng.randn(*s), dtype) for s in shapes]

    def update(p, s, lrs, wds):
        return opt.fused_update(p, grads, s,
                                *hyper_scalars(lrs, wds, p, s))

    idx = list(range(len(shapes)))
    lrs, wds = host_hyperparams(opt, idx)
    assert lrs.dtype == wds.dtype == np.float32 and lrs.shape == (3,)
    assert opt.num_update == 1
    new_p, new_s = jax.jit(update)(params, states, lrs, wds)
    ref_p, ref_s = jax.jit(update)(
        params, states, tuple(float(v) for v in lrs),
        tuple(float(v) for v in wds))

    def window(p, s, lrs, wds):
        return jax.lax.scan(
            lambda c, xs: (tuple(map(tuple, update(
                list(c[0]), list(c[1]), *xs))), ()),
            (tuple(p), tuple(s)), (lrs, wds))[0]

    win_p, win_s = jax.jit(window)(params, states, lrs[None], wds[None])
    for w, a, b, c in zip(params, new_p, ref_p, win_p):
        assert a.dtype == b.dtype == c.dtype == w.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), np.asarray(c))
    for a, b, c in zip(*map(jax.tree_util.tree_leaves,
                            (new_s, ref_s, win_s))):
        assert a.dtype == b.dtype == c.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), np.asarray(c))
