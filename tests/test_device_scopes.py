"""The program names its device work (docs/observability.md, "Device time
by scope"): every registered operator runs under ``op/<name>`` whichever
path reaches it, the step programs put their loss, gradient
synchronisation and update under ``step/...``, and
``profiler.parse_op_name`` takes a device op's name stack apart again.

The lowered text stands in for the chip's trace here: the ``tf_op``
statistic of an ``XLA Ops`` event is the HLO's ``op_name``, which is the
location the lowering writes.  The fixture recorded on the chip is read in
``tests/bench_harness/test_bench_scopes.py``.
"""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, profiler
from mxnet_tpu import io as mxio
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import registry


# -- the four step programs ----------------------------------------------------
def _convnet():
    d = mx.sym.Variable("data")
    h = mx.sym.Convolution(d, kernel=(3, 3), num_filter=4, pad=(1, 1),
                           no_bias=True, name="conv")
    h = mx.sym.BatchNorm(h, name="bn")
    h = mx.sym.Pooling(mx.sym.Activation(h, act_type="relu"),
                       kernel=(2, 2), stride=(2, 2), pool_type="max")
    h = mx.sym.FullyConnected(mx.sym.Flatten(h), num_hidden=10, name="fc")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _spy_on(monkeypatch, cls, build, attr, texts):
    """Have ``cls`` hand the lowered text of its jitted program to
    ``texts`` at every call."""
    real = getattr(cls, build)

    def wrapped(self):
        real(self)
        jitted = getattr(self, attr)

        def call(*args):
            texts.append(jitted.lower(*args).as_text(debug_info=True))
            return jitted(*args)
        setattr(self, attr, call)
    monkeypatch.setattr(cls, build, wrapped)


def _fit_text(monkeypatch, scan_steps):
    from mxnet_tpu import fused_step
    texts = []
    if scan_steps:
        _spy_on(monkeypatch, fused_step.ScanTrainStep, "_build_scan_jit",
                "_scan_jit", texts)
    else:
        _spy_on(monkeypatch, fused_step.FusedTrainStep, "_build_jit",
                "_jit", texts)
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_SCAN_STEPS", str(scan_steps))
    rng = np.random.RandomState(0)
    it = mxio.NDArrayIter(
        mx.nd.array(rng.randn(16, 3, 8, 8).astype(np.float32)),
        mx.nd.array(rng.randint(0, 10, 16).astype(np.float32)),
        batch_size=8, label_name="softmax_label")
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    assert texts, "the step class did not engage"
    return texts[0]


class _Toy(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.conv = nn.Conv2D(4, 3, padding=1, use_bias=False)
            self.bn = nn.BatchNorm()
            self.pool = nn.MaxPool2D(2)
            self.head = nn.Dense(10)

    def hybrid_forward(self, F, x):
        h = self.pool(F.Activation(self.bn(self.conv(x)), act_type="relu"))
        with jax.named_scope("toy/head"):
            return self.head(h)


def _toy_batch():
    rng = np.random.RandomState(0)
    return (rng.randn(8, 3, 8, 8).astype(np.float32),
            rng.randint(0, 10, 8).astype(np.float32))


def _spmd_text(monkeypatch):
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import TrainStep
    x, y = _toy_batch()
    net = _Toy()
    net.initialize(mx.initializer.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.05, "momentum": 0.9},
                     make_mesh(devices=jax.devices()[:1], dp=1),
                     example_batch=(mx.nd.array(x), mx.nd.array(y)),
                     remat=True)
    with step.mesh.jax_mesh:
        return step._step.lower(
            jax.random.PRNGKey(0), step._train_params, step._aux_params,
            step.opt_state, x, y).as_text(debug_info=True)


def _hybrid_text(monkeypatch):
    x, _y = _toy_batch()
    net = _Toy()
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    with mx.autograd.record():
        net(mx.nd.array(x))
    (entry,) = net._jit_cache.values()
    params = [p.data(mx.cpu())._data for p in entry[5]]
    return entry[1].lower(jax.random.PRNGKey(0), *params,
                          jax.numpy.asarray(x)).as_text(debug_info=True)


PROGRAMS = {
    # program: (its lowered text, the scopes it holds, those it has none of)
    "fused": (lambda mp: _fit_text(mp, 0),
              ("op/Convolution", "op/BatchNorm", "op/Pooling",
               "op/SoftmaxOutput", "step/optimizer"), ("step/loss",)),
    "scan": (lambda mp: _fit_text(mp, 2),
             ("op/Convolution", "op/BatchNorm", "op/Pooling",
              "op/SoftmaxOutput", "step/optimizer"), ("step/loss",)),
    "spmd": (_spmd_text,
             ("op/Convolution", "op/BatchNorm", "op/Pooling", "step/loss",
              "step/optimizer", "toy/head", "op/FullyConnected",
              "rematted_computation"), ("step/grad_sync",)),
    "hybrid": (_hybrid_text,
               ("op/Convolution", "op/BatchNorm", "op/Pooling",
                "toy/head", "op/FullyConnected"), ("step/",)),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_step_programs_name_their_device_work(monkeypatch, program):
    lower, holds, lacks = PROGRAMS[program]
    text = lower(monkeypatch)
    for scope in holds:
        assert scope in text, scope
    for scope in lacks:
        assert scope not in text, scope


def test_bucketed_spmd_step_names_its_gradient_sync():
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import TrainStep
    rng = np.random.RandomState(0)
    x = rng.randn(8, 12).astype(np.float32)
    y = rng.randint(0, 4, 8).astype(np.float32)
    net = nn.Dense(4)
    net.initialize(mx.initializer.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.05}, make_mesh(dp=8),
                     example_batch=(mx.nd.array(x), mx.nd.array(y)),
                     bucket_mb=1)
    with step.mesh.jax_mesh:
        text = step._step.lower(
            jax.random.PRNGKey(0), step._train_params, step._aux_params,
            step.opt_state, x, y).as_text(debug_info=True)
    for scope in ("step/grad_sync", "step/optimizer", "step/loss",
                  "op/FullyConnected"):
        assert scope in text, scope


# -- one builder behind every path to an operator -----------------------------
@pytest.mark.parametrize("path", ["jitted", "raw", "grad_aware"])
def test_every_path_to_an_operator_runs_under_its_name(path):
    op = registry.get("sgd_mom_update" if path != "grad_aware"
                      else "SoftmaxOutput")
    if path == "jitted":
        fn, _key = op.bind(lr=0.1, momentum=0.9)
        args = [np.ones((4,), np.float32)] * 3
    elif path == "raw":
        fn = jax.jit(op.raw({"lr": 0.1, "momentum": 0.9}))
        args = [np.ones((4,), np.float32)] * 3
    else:   # the custom gradient's ops carry the name too
        f = op.grad_aware({})
        fn = jax.jit(jax.grad(lambda x, y: f(x, y).sum()))
        args = [np.ones((4, 3), np.float32), np.zeros((4,), np.float32)]
    text = fn.lower(*args).as_text(debug_info=True)
    assert "op/" + op.name in text


def test_amp_cast_stays_outside_nothing_changes_in_the_result():
    op = registry.get("FullyConnected")
    x = np.ones((2, 3), np.float32)
    w = np.full((4, 3), 0.5, np.float32)
    plain = op.raw({"num_hidden": 4, "no_bias": True})(x, w)
    low = op.raw({"num_hidden": 4, "no_bias": True,
                  "_amp": "low:bfloat16"})(x, w)
    assert plain.dtype == np.float32 and low.dtype == jax.numpy.bfloat16
    np.testing.assert_allclose(np.asarray(plain),
                               np.asarray(low, dtype=np.float32))


# -- the name stack taken apart ------------------------------------------------
# Name stacks as the chip's traces and the compiled step programs hold
# them (the ``tf_op`` statistic ends in ``:``, the op's empty type).
NAME_STACKS = [
    ("", (), ()),
    ("jit(step)/jvp()/dot_general:", ("",), ("forward",)),
    ("jit(step)/transpose(jvp())/dot_general;"
     "jit(step)/transpose(jvp())/broadcast_in_dim:",
     ("", ""), ("backward", "backward")),
    ("jit(step)/sub", ("",), ("other",)),
    ("train_params[3]", ("",), ("other",)),
    ("jit(step)/jvp(op/Convolution)/conv_general_dilated:",
     ("op/Convolution",), ("forward",)),
    ("jit(step)/transpose(jvp(op/BatchNorm))/jit(_var)/reduce_sum",
     ("op/BatchNorm",), ("backward",)),
    ("jit(step)/step/optimizer/mul", ("step/optimizer",), ("other",)),
    ("jit(call)/op/multi_sgd_mom_update/sub",
     ("op/multi_sgd_mom_update",), ("other",)),
    ("jit(step)/jvp(step/loss)/op/pick/jit(take_along_axis)/select_n",
     ("step/loss/op/pick",), ("forward",)),
    # a call is its own last component: ``jit(f)`` is the primitive
    ("jit(step)/transpose(jvp(step/loss))/op/pick/jit(take_along_axis)",
     ("step/loss/op/pick",), ("backward",)),
    ("jit(step)/jvp(toy/head)/op/FullyConnected/dot_general",
     ("toy/head/op/FullyConnected",), ("forward",)),
    # jax.checkpoint: its backward under ``checkpoint``, what it runs
    # again under ``checkpoint/rematted_computation``
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/op/Pooling/"
     "select_and_scatter", ("op/Pooling",), ("backward",)),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "nemotron/attention/granite/attention/op/_contrib_flash_attention/"
     "pallas_call",
     ("nemotron/attention/granite/attention/op/_contrib_flash_attention",),
     ("recompute",)),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/model/attention/op/FullyConnected/tanh",
     ("model/attention/op/FullyConnected",), ("recompute",)),
    # as the chip's traces of the token cells hold them (PR 36): the
    # kernel's name and an einsum's labels are components too, and what
    # jax.checkpoint moves about is a ``remat2`` under no scope
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "granite/mamba/ssd/op/_contrib_ssd_scan/bctsgr,bcsgrp->bctgrp/"
     "dot_general:",
     ("granite/mamba/ssd/op/_contrib_ssd_scan/bctsgr,bcsgrp->bctgrp",),
     ("recompute",)),
    ("jit(step)/jvp(granite/attention)/op/_contrib_flash_attention/"
     "mx_flash_attention_fwd/pallas_call:",
     ("granite/attention/op/_contrib_flash_attention/mx_flash_attention_fwd",),
     ("forward",)),
    ("jit(step)/jvp(granite/mamba/ssd)/op/_contrib_ssd_scan/closed_call/"
     "broadcast_in_dim:", ("granite/mamba/ssd/op/_contrib_ssd_scan",),
     ("forward",)),
    ("jit(step)/transpose(jvp(jvp()))/remat2:", ("",), ("backward",)),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/granite/mamba/ssd/op/"
     "reshape/reshape;jit(step)/transpose(jvp(jvp()))/checkpoint/granite/"
     "mamba/ssd/op/_contrib_ssd_scan/reshape:",
     ("granite/mamba/ssd/op/reshape",
      "granite/mamba/ssd/op/_contrib_ssd_scan"), ("backward", "backward")),
    ("states[3]:", ("",), ("other",)),
    # control flow: a scanned window, a routed expert's tile walk
    ("jit(window)/while/body/jvp(op/Convolution)/conv_general_dilated",
     ("op/Convolution",), ("forward",)),
    ("jit(window)/while/body/step/optimizer/add",
     ("step/optimizer",), ("other",)),
    ("jit(window)/while/cond/lt", ("",), ("other",)),
    ("jit(step)/jvp()/while", ("",), ("forward",)),
    ("jit(step)/jvp(solar/moe)/op/_contrib_routed_experts/routed_experts/"
     "experts/while/body/dot_general",
     ("solar/moe/op/_contrib_routed_experts/routed_experts/experts",),
     ("forward",)),
    ("jit(step)/jvp()/cond/branch_1_fun/mul", ("",), ("forward",)),
    # a fusion of two operators, and of a forward with its repeat
    ("jit(step)/jvp(op/Convolution)/conv_general_dilated;"
     "jit(step)/jvp(op/BatchNorm)/mul:",
     ("op/Convolution", "op/BatchNorm"), ("forward", "forward")),
    ("jit(step)/transpose(jvp(granite/mlp))/op/FullyConnected/dot_general;"
     "jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
     "granite/mlp/op/Activation/mul",
     ("granite/mlp/op/FullyConnected", "granite/mlp/op/Activation"),
     ("backward", "recompute")),
]


@pytest.mark.parametrize("op_name,scopes,phases", NAME_STACKS,
                         ids=[str(i) for i in range(len(NAME_STACKS))])
def test_name_stack_taken_apart(op_name, scopes, phases):
    assert profiler.parse_op_name(op_name) == (scopes, phases)
    assert set(phases) <= set(profiler.PHASES)
