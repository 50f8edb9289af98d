"""``gluon.Trainer._update`` updates every dense parameter of a step in
ONE jitted program per context (``Optimizer.fused_update`` over the lot,
the rates and decays as two host arrays) and leaves the rest to the
per-tensor updater call.  Tiny nets, CPU."""
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, profiler, telemetry
from mxnet_tpu import compile as compile_mod
from mxnet_tpu import optimizer as opt_mod

BATCH = 8


def _net(dtype="float32", seed=0):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
                gluon.nn.BatchNorm(in_channels=16),
                gluon.nn.Dense(3, in_units=16))
    net.initialize(mx.initializer.Xavier())
    if dtype != "float32":
        net.cast(dtype)
    net.hybridize()
    return net


def _data(dtype="float32"):
    rs = np.random.RandomState(3)
    return (nd.array(rs.randn(BATCH, 8).astype(dtype), dtype=dtype),
            nd.array(rs.randint(0, 3, (BATCH,)).astype(np.float32)))


def _backward(net, x, y):
    with autograd.record():
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(net(x), y)
    loss.backward()
    return loss


def _weights(net):
    params = net.collect_params()
    return {n: params[n].data().asnumpy().astype(np.float32)
            for n in sorted(params.keys())}


def _update_calls():
    return telemetry.REGISTRY.get("mxnet_trainer_update_calls_total").value()


@pytest.fixture
def counted():
    telemetry.enable()
    telemetry.reset_span_records()
    try:
        yield
    finally:
        telemetry.disable()
        telemetry.reset_span_records()


@pytest.mark.parametrize("name,kwargs,dtype", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3}, "float32"),
    ("sgd", {"learning_rate": 0.05, "clip_gradient": 0.5}, "float32"),
    ("adam", {"learning_rate": 0.01, "wd": 1e-3}, "float32"),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3,
             "multi_precision": True}, "float16"),
], ids=["sgd-momentum-wd", "sgd-plain-clip", "adam", "sgd-mp-float16"])
def test_one_program_agrees_with_the_per_tensor_loop(name, kwargs, dtype):
    x, y = _data(dtype)
    net, twin = _net(dtype), _net(dtype)
    trainer = gluon.Trainer(net.collect_params(), name, dict(kwargs))
    assert trainer._one_program
    # the loop, driven by hand: one updater call a tensor
    params = twin.collect_params()
    ordered = [params[n] for n in sorted(params.keys())]
    loop_opt = opt_mod.create(name, param_dict=dict(enumerate(ordered)),
                              **kwargs)
    upd = opt_mod.get_updater(loop_opt)
    for _ in range(5):
        _backward(net, x, y)
        trainer.step(BATCH)
        _backward(twin, x, y)
        loop_opt.rescale_grad = 1.0 / BATCH
        for i, p in enumerate(ordered):
            if p.grad_req != "null":
                upd(i, p.grad(), p.data())
    got, want = _weights(net), _weights(twin)
    moved = _weights(_net(dtype))
    for n in want:
        np.testing.assert_allclose(got[n], want[n], rtol=1e-6, atol=1e-6,
                                   err_msg=n)
    assert any(np.abs(got[n] - moved[n]).max() > 1e-3 for n in got)
    assert len(trainer._update_programs) == 1
    # the states are the updater's own, index by index
    states = trainer._updaters[0].states
    assert sorted(states) == sorted(upd.states)
    if kwargs.get("multi_precision"):
        # (momentum, float32 master copy) beside each float16 weight;
        # BatchNorm's float32 pair keeps a plain momentum
        masters = [s[1].dtype for s in states.values() if isinstance(s, tuple)]
        assert masters == [np.float32] * 4 and len(states) == 6


def test_a_step_launches_one_optimizer_program(counted):
    x, y = _data()
    net = _net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    _backward(net, x, y)
    trainer.step(BATCH)             # builds the program, creates the states
    _backward(net, x, y)
    telemetry.reset_span_records()
    calls, launches = _update_calls(), profiler.dispatch_counts()["total"]
    trainer.step(BATCH)
    assert profiler.dispatch_counts()["total"] - launches == 1
    assert _update_calls() - calls == 1
    (record,) = [r for r in telemetry.span_records()
                 if r["name"] == "gluon/trainer/update"]
    assert record["counts"] == {"mxnet_trainer_update_calls_total": 1}


@pytest.mark.parametrize("how", ["scheduler", "set_learning_rate"])
def test_a_changing_rate_builds_no_program(how):
    x, y = _data()
    net = _net()
    kwargs = {"learning_rate": 0.05, "momentum": 0.9}
    if how == "scheduler":
        kwargs["lr_scheduler"] = mx.lr_scheduler.FactorScheduler(
            step=1, factor=0.5)
    trainer = gluon.Trainer(net.collect_params(), "sgd", kwargs)
    built = compile_mod.LEDGER.trace_count("gluon_trainer_update")
    rates = []
    for step in range(6):
        if how == "set_learning_rate" and step == 3:
            trainer.set_learning_rate(0.005)
        _backward(net, x, y)
        trainer.step(BATCH)
        rates.append(trainer.learning_rate)
    assert len(set(rates)) > 1
    assert compile_mod.LEDGER.trace_count("gluon_trainer_update") - built == 1
    (program,) = trainer._update_programs.values()
    assert program._cache_size() == 1       # jit traced it once


def test_a_changed_rate_is_the_rate_applied():
    """The rate is an argument of the program: the step after
    ``set_learning_rate(0)`` moves nothing."""
    x, y = _data()
    net = _net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    _backward(net, x, y)
    trainer.step(BATCH)
    before = _weights(net)
    trainer.set_learning_rate(0.0)
    _backward(net, x, y)
    trainer.step(BATCH)
    after = _weights(net)
    trainable = [n for n, p in net.collect_params().items()
                 if p.grad_req != "null"]
    for n in trainable:
        np.testing.assert_array_equal(before[n], after[n])


def test_grad_req_null_parameters_are_left_out():
    x, y = _data()
    net = _net()
    params = net.collect_params()
    frozen = sorted(params.keys())[0]
    params[frozen].grad_req = "null"
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.05})
    before = _weights(net)
    _backward(net, x, y)
    trainer.step(BATCH)
    ((_sig, indices, _structure),) = trainer._update_programs
    null = {i for i, p in enumerate(trainer._params) if p.grad_req == "null"}
    assert trainer._param2idx[frozen] in null
    assert set(indices) == set(range(len(trainer._params))) - null
    np.testing.assert_array_equal(_weights(net)[frozen], before[frozen])


def _two_heads():
    mx.random.seed(0)
    a, b = gluon.nn.Dense(3, in_units=8), gluon.nn.Dense(3, in_units=8)
    for block in (a, b):
        block.initialize(mx.initializer.Xavier())
    params = gluon.ParameterDict()
    params.update(a.collect_params())
    params.update(b.collect_params())
    return a, b, params


@pytest.mark.parametrize("ignore", [False, True])
def test_a_stale_gradient(ignore):
    x, y = _data()
    a, b, params = _two_heads()
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.05})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(a(x) + b(x), y)
    loss.backward()
    trainer.step(BATCH)
    a_before, b_before = _weights(a), _weights(b)
    with autograd.record():
        loss = loss_fn(a(x), y)      # b takes no part: its gradient is old
    loss.backward()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trainer.step(BATCH, ignore_stale_grad=ignore)
    stale = [w for w in caught if "has not been updated" in str(w.message)]
    for n, w in _weights(a).items():
        assert np.abs(w - a_before[n]).max() > 0
    if ignore:
        assert not stale
        assert len(trainer._update_programs) == 1
    else:
        assert len(stale) == 2          # b's weight and bias
        for n, w in _weights(b).items():
            np.testing.assert_array_equal(w, b_before[n])
        # the fresh set alone is another key, not an error
        assert len(trainer._update_programs) == 2


class _SGDWithItsOwnUpdate(opt_mod.SGD):
    def update(self, index, weight, grad, state):
        weight[:] = weight - 2 * self._get_lr(index) * grad


@pytest.mark.parametrize("make", [
    lambda: opt_mod.create("nag", learning_rate=0.05, momentum=0.9),
    lambda: opt_mod.create("adam", learning_rate=0.01, multi_precision=True),
    lambda: _SGDWithItsOwnUpdate(learning_rate=0.05),
], ids=["nag", "adam-multi-precision", "sgd-subclass-own-update"])
def test_an_optimizer_without_a_current_fused_update_takes_the_loop(
        make, counted):
    x, y = _data()
    net = _net()
    trainer = gluon.Trainer(net.collect_params(), make())
    assert not trainer._one_program
    before = _weights(net)
    _backward(net, x, y)
    calls = _update_calls()
    trainer.step(BATCH)
    trainable = [p for p in trainer._params if p.grad_req != "null"]
    assert _update_calls() - calls == len(trainable)
    assert not trainer._update_programs
    after = _weights(net)
    assert all(np.abs(after[p.name] - before[p.name]).max() > 0
               for p in trainable)


def test_a_row_sparse_gradient_takes_the_loop(counted):
    mx.random.seed(0)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Embedding(20, 4, sparse_grad=True),
            gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    tokens = nd.array(np.array([[1, 2], [3, 1], [7, 2], [1, 9]], np.float32))
    y = nd.array(np.array([0, 1, 2, 0], np.float32))
    before = _weights(net)
    with autograd.record():
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(net(tokens), y)
    loss.backward()
    calls = _update_calls()
    trainer.step(4)
    # the embedding's table through the updater, the Dense pair in one program
    assert _update_calls() - calls == 2
    ((_sig, indices, _structure),) = trainer._update_programs
    assert len(indices) == 2
    after = _weights(net)
    assert all(np.abs(after[n] - before[n]).max() > 0 for n in after)


def test_states_saved_and_loaded_continue_the_run(tmp_path):
    x, y = _data()
    kwargs = {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3}
    whole = _net()
    trainer = gluon.Trainer(whole.collect_params(), "sgd", dict(kwargs))
    for _ in range(6):
        _backward(whole, x, y)
        trainer.step(BATCH)

    first = _net()
    trainer = gluon.Trainer(first.collect_params(), "sgd", dict(kwargs))
    for _ in range(3):
        _backward(first, x, y)
        trainer.step(BATCH)
    trainer.save_states(str(tmp_path / "trainer.states"))
    first.save_parameters(str(tmp_path / "net.params"))

    second = _net(seed=1)
    second.load_parameters(str(tmp_path / "net.params"))
    resumed = gluon.Trainer(second.collect_params(), "sgd", dict(kwargs))
    resumed.load_states(str(tmp_path / "trainer.states"))
    for _ in range(3):
        _backward(second, x, y)
        resumed.step(BATCH)
    got, want = _weights(second), _weights(whole)
    for a, b in zip(got.values(), want.values()):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_two_contexts_give_one_program_each(counted):
    ctxs = [mx.cpu(0), mx.cpu(1)]
    mx.random.seed(0)
    net = gluon.nn.Dense(3, in_units=8)
    net.initialize(mx.initializer.Xavier(), ctx=ctxs)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    x, y = _data()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(2):
        losses = []
        with autograd.record():
            for ctx in ctxs:
                losses.append(loss_fn(net(x.as_in_context(ctx)),
                                      y.as_in_context(ctx)))
        autograd.backward(losses)
        calls = _update_calls()
        trainer.step(2 * BATCH)
        assert _update_calls() - calls == 2
    assert len(trainer._update_programs) == 1       # one key, two devices
    for p in net.collect_params().values():
        a, b = (d.asnumpy() for d in p.list_data())
        np.testing.assert_array_equal(a, b)
    for updater in trainer._updaters:
        assert len(updater.states) == 2


def test_the_weights_are_not_donated():
    """The recorded graph still holds the weights the update read: a
    second backward through a retained graph finds them."""
    x, y = _data()
    net = _net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    with autograd.record():
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(net(x), y)
    loss.backward(retain_graph=True)
    weight = trainer._params[0]
    old = weight.data()._data
    grad = weight.grad().asnumpy()
    trainer.step(BATCH)
    loss.backward()
    np.testing.assert_allclose(weight.grad().asnumpy(), grad, rtol=1e-6)
    assert not old.is_deleted()
