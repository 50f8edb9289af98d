"""A rematerialisation boundary keeps the flash kernel's ``out`` and ``lse``
and the KDA kernel's ``o`` and entering states by name
(``gluon.block.KERNEL_RESIDUALS``): the backward of a layer recomputes the
kernels' inputs but not their forward kernels.  On the CPU, the kernels in
the Pallas interpreter: the gradient's program holds as many forward
``pallas_call``s under remat as without it (twice as many under a boundary
that keeps nothing), the gradients are the un-rematted ones, the gauge
``mxnet_step_remat_saved_residuals`` counts two a kernel call inside a
boundary, and a layer with neither kernel traces the program it did before
the policy."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu.gluon import block as block_mod
from mxnet_tpu.gluon.model_zoo.language import KimiDeltaAttention
from mxnet_tpu.gluon.model_zoo.language.granite import (GraniteHybrid,
                                                        GroupedQueryAttention)
from mxnet_tpu.gluon.model_zoo.language.kimi_linear import KimiLinear
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep, functionalize

# hidden 64, 4 query heads over 2 key/value heads of 16, 40 positions: a
# 512-row tile of the kernel's is padded, as in the timed shapes
HIDDEN, T = 64, 40
# one KDA head of 128 (the kernels' shape rule) in chunks of 16: 40
# positions are two whole chunks and a tail
KDA_HEAD, KDA_CHUNK = 128, 16


def _attention_net():
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    for _ in range(2):
        net.add(GroupedQueryAttention(HIDDEN, 4, 2, 16, 0.25))
    net.initialize(mx.initializer.Normal(0.1))
    return net


def _kda_layers():
    return [KimiDeltaAttention(HIDDEN, 1, KDA_HEAD, chunk_size=KDA_CHUNK)
            for _ in range(2)]


def _kda_net():
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(*_kda_layers())
    net.initialize(mx.initializer.Normal(0.1))
    return net


def _count_calls(jaxpr, name="mx_flash_attention_fwd"):
    """Calls of the kernel ``name`` anywhere under ``jaxpr``
    (``platform_dependent`` puts each in twice: compare, never a constant)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call" and eqn.params["name"] == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_calls(sub, name)
    return n


def _layer_grads(remat, make=_attention_net):
    """The jaxpr and value of the gradient of a weighted sum of two
    layers' output (``make``'s net), with or without a boundary a layer."""
    net = make()
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
    assert _count_calls(plain) > 0
    assert _count_calls(kept) == _count_calls(plain)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6,
                                   atol=1e-6 * float(np.abs(b).max()))
    # a boundary that keeps nothing runs the kernel again in the backward
    monkeypatch.setattr(block_mod, "_KEEP_KERNEL_OUTPUTS", None)
    again, _ = _layer_grads(remat=True)
    assert _count_calls(again) == 2 * _count_calls(plain)


def test_the_kda_forward_kernel_runs_once_a_layer_under_remat(monkeypatch):
    """The boundary keeps ``o`` and the entering states of ``mx_kda_fwd``
    (``ops.pallas_kda.KDA_RESIDUALS``): the backward recomputes q, k, v, g
    and β through the layer's projections, convolutions and gates, and
    ``mx_kda_bwd`` reads the kept arrays."""
    plain, want = _layer_grads(remat=False, make=_kda_net)
    kept, got = _layer_grads(remat=True, make=_kda_net)
    assert _count_calls(plain, "mx_kda_fwd") > 0
    assert _count_calls(kept, "mx_kda_fwd") == _count_calls(plain, "mx_kda_fwd")
    assert (_count_calls(kept, "mx_kda_bwd")
            == _count_calls(plain, "mx_kda_bwd") > 0)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6,
                                   atol=1e-6 * float(np.abs(b).max()))
    # a bare jax.checkpoint runs the forward kernel again in the backward
    monkeypatch.setattr(block_mod, "_KEEP_KERNEL_OUTPUTS", None)
    again, _ = _layer_grads(remat=True, make=_kda_net)
    assert (_count_calls(again, "mx_kda_fwd")
            == 2 * _count_calls(plain, "mx_kda_fwd"))


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


def _kimi(layer_types, kda_head=KDA_HEAD):
    """A dense Kimi Linear of ``layer_types``: KDA heads of ``kda_head``,
    two latent attention heads."""
    mx.random.seed(0)
    net = KimiLinear(
        64, HIDDEN, layer_types, dense_layers=len(layer_types),
        dense_width=96, kda_heads=1, kda_head_dim=kda_head, mla_heads=2,
        nope_dim=8, rope_dim=8, v_dim=16, kv_rank=16, expert_width=32,
        experts_total=8, experts_held=4, top_k=2, kda_chunk=KDA_CHUNK)
    net.initialize(mx.initializer.Normal(0.02))
    return net


@pytest.mark.parametrize("remat", [False, True])
def test_the_gauge_counts_two_a_kda_call_beside_the_flash_calls(remat):
    layer_types = ["kda", "mla", "kda"]
    step, _ = _step(_kimi(layer_types), remat)
    assert step.remat_boundaries == (3 if remat else 0)
    assert _saved() == (2 * len(layer_types) if remat else 0)


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
        return _count_calls(jax.make_jaxpr(f)(*args).jaxpr)

    _, plain = _step(net(), remat=False, trace=count)
    step, kept = _step(net(), remat=True, trace=count)
    assert step.remat_boundaries == 1
    assert _saved() == 6
    assert kept == plain > 0


def test_the_whole_forward_boundary_keeps_the_kda_kernels_too():
    def net():
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Embedding(64, HIDDEN), *_kda_layers(),
                gluon.nn.Dense(64, flatten=False))
        net.initialize(mx.initializer.Normal(0.1))
        return net

    def count(f, *args):
        return _count_calls(jax.make_jaxpr(f)(*args).jaxpr, "mx_kda_fwd")

    _, plain = _step(net(), remat=False, trace=count)
    step, kept = _step(net(), remat=True, trace=count)
    assert step.remat_boundaries == 1
    assert _saved() == 4
    assert kept == plain > 0


def test_a_layer_with_no_flash_call_traces_the_program_it_did(monkeypatch):
    _, kept = _step(_granite(["mamba", "mamba"]), remat=True)
    assert _saved() == 0
    monkeypatch.setattr(block_mod, "_KEEP_KERNEL_OUTPUTS", None)
    _, before = _step(_granite(["mamba", "mamba"]), remat=True)
    assert kept == before


def test_kda_layers_in_the_plain_form_trace_the_program_they_did(
        monkeypatch):
    """KDA heads the kernels do not take run the plain XLA form, which
    emits no names: the policy keeps nothing of it."""
    _, kept = _step(_kimi(["kda", "kda"], kda_head=8), remat=True)
    assert _saved() == 0
    monkeypatch.setattr(block_mod, "_KEEP_KERNEL_OUTPUTS", None)
    _, before = _step(_kimi(["kda", "kda"], kda_head=8), remat=True)
    assert kept == before
