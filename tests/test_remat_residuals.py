"""A rematerialisation boundary keeps the flash kernel's ``out`` and ``lse``
by name (``ops.pallas_attention.FLASH_RESIDUALS``): the backward of a layer
recomputes q, k and v but not the forward kernel.  On the CPU, the kernel
in the Pallas interpreter: the gradient's program holds as many forward
``pallas_call``s under remat as without it (twice as many under a boundary
that keeps nothing), the gradients are the un-rematted ones, the gauge
``mxnet_step_remat_saved_residuals`` counts two a flash call inside a
boundary, and a layer with no flash call traces the program it did before
the policy."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu.gluon import block as block_mod
from mxnet_tpu.gluon.model_zoo.language.granite import (GraniteHybrid,
                                                        GroupedQueryAttention)
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep, functionalize

# hidden 64, 4 query heads over 2 key/value heads of 16, 40 positions: a
# 512-row tile of the kernel's is padded, as in the timed shapes
HIDDEN, T = 64, 40


def _attention_net():
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    for _ in range(2):
        net.add(GroupedQueryAttention(HIDDEN, 4, 2, 16, 0.25))
    net.initialize(mx.initializer.Normal(0.1))
    return net


def _count_fwd(jaxpr):
    """``mx_flash_attention_fwd`` calls anywhere under ``jaxpr``
    (``platform_dependent`` puts each in twice: compare, never a constant)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call" and \
            eqn.params["name"] == "mx_flash_attention_fwd"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_fwd(sub)
    return n


def _layer_grads(remat):
    """The jaxpr and value of the gradient of a weighted sum of two
    attention layers' output, with or without a boundary a layer."""
    net = _attention_net()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, T, HIDDEN)).astype(np.float32)
    w = rng.standard_normal((2, T, HIDDEN)).astype(np.float32)
    apply_fn, params, _ = functionalize(net, nd.array(x))

    def loss(ps):
        with block_mod.remat_scope(list(net) if remat else ()) as sc:
            (y,), _ = apply_fn(jax.random.PRNGKey(0), ps, (x,))
        assert sc.boundaries == (2 if remat else 0)
        return (y * w).sum()

    grad = jax.grad(loss)
    return jax.make_jaxpr(grad)(params).jaxpr, grad(params)


def test_the_forward_kernel_runs_once_a_layer_under_remat(monkeypatch):
    plain, want = _layer_grads(remat=False)
    kept, got = _layer_grads(remat=True)
    assert _count_fwd(plain) > 0
    assert _count_fwd(kept) == _count_fwd(plain)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6,
                                   atol=1e-6 * float(np.abs(b).max()))
    # a boundary that keeps nothing runs the kernel again in the backward
    monkeypatch.setattr(block_mod, "_KEEP_FLASH", None)
    again, _ = _layer_grads(remat=True)
    assert _count_fwd(again) == 2 * _count_fwd(plain)


def _granite(layer_types):
    mx.random.seed(0)
    net = GraniteHybrid(64, HIDDEN, 128, layer_types, 4, 2, 0.25,
                        mamba_heads=4, mamba_head_dim=16, mamba_state=16,
                        mamba_chunk=8)
    net.initialize(mx.initializer.Normal(0.02))
    return net


def _step(net, remat, trace=lambda f, *a: f.lower(*a).as_text()):
    """A ``TrainStep`` over ``net`` and ``trace`` of its program."""
    ids = np.random.default_rng(0).integers(0, 64, (1, T + 1)).astype(
        np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, make_mesh(
                         devices=jax.devices()[:1], dp=1),
                     example_batch=(nd.array(x), nd.array(y)), remat=remat)
    args = (jax.random.PRNGKey(0), step._train_params, step._aux_params,
            step.opt_state, x, y)
    with step.mesh.jax_mesh:
        return step, trace(step._step, *args)


def _saved():
    return telemetry.REGISTRY.get("mxnet_step_remat_saved_residuals").value()


@pytest.mark.parametrize("remat", [False, True])
def test_the_gauge_counts_two_a_flash_call_inside_a_boundary(remat):
    layer_types = ["mamba", "attention", "mamba", "attention"]
    step, _ = _step(_granite(layer_types), remat)
    assert step.remat_boundaries == (4 if remat else 0)
    assert _saved() == (2 * layer_types.count("attention") if remat else 0)


def test_the_whole_forward_boundary_keeps_them_too():
    """A block that declares no layers is one boundary under
    ``spmd.remat_wrap``, whose policy keeps the same two names."""
    def net():
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Embedding(64, HIDDEN))
        for _ in range(3):
            net.add(GroupedQueryAttention(HIDDEN, 4, 2, 16, 0.25))
        net.add(gluon.nn.Dense(64, flatten=False))
        net.initialize(mx.initializer.Normal(0.1))
        return net

    def count(f, *args):
        return _count_fwd(jax.make_jaxpr(f)(*args).jaxpr)

    _, plain = _step(net(), remat=False, trace=count)
    step, kept = _step(net(), remat=True, trace=count)
    assert step.remat_boundaries == 1
    assert _saved() == 6
    assert kept == plain > 0


def test_a_layer_with_no_flash_call_traces_the_program_it_did(monkeypatch):
    _, kept = _step(_granite(["mamba", "mamba"]), remat=True)
    assert _saved() == 0
    monkeypatch.setattr(block_mod, "_KEEP_FLASH", None)
    _, before = _step(_granite(["mamba", "mamba"]), remat=True)
    assert kept == before
