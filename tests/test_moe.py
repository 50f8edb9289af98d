"""Mixture-of-Experts + expert parallelism (parallel/moe.py).

The ep strategy completes the dp/fsdp/tp/sp/pp/ep set (SURVEY §2.4:
greenfield — the reference has none). Equivalence oracle: with capacity
admitting every token, MoE output per token is gate * expert_ffn(x), so
the dense single-device version, a hand looped-per-expert evaluation,
and the sharded all_to_all version must all agree.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import DeviceMesh
from mxnet_tpu.parallel.moe import init_moe_params, moe_ffn, moe_ffn_ep

N, D, H, E = 32, 8, 16, 4


@pytest.fixture(scope="module")
def setup():
    params = init_moe_params(jax.random.PRNGKey(0), D, H, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    return params, x


def _reference_loop(params, x):
    """Slow per-token oracle: y_n = gate_n * FFN_{expert(n)}(x_n)."""
    logits = np.asarray(x) @ np.asarray(params["wg"])
    e_x = np.exp(logits - logits.max(axis=1, keepdims=True))
    gates = e_x / e_x.sum(axis=1, keepdims=True)
    expert = gates.argmax(axis=1)
    y = np.zeros_like(np.asarray(x))
    for n in range(x.shape[0]):
        e = int(expert[n])
        h = np.maximum(
            np.asarray(x)[n] @ np.asarray(params["w1"])[e]
            + np.asarray(params["b1"])[e], 0.0)
        y[n] = (h @ np.asarray(params["w2"])[e]
                + np.asarray(params["b2"])[e]) * gates[n, e]
    return y


def test_dense_moe_matches_per_token_oracle(setup):
    params, x = setup
    y, aux = moe_ffn(params, x, capacity_factor=float(E))  # no drops
    np.testing.assert_allclose(np.asarray(y), _reference_loop(params, x),
                               rtol=1e-5, atol=1e-6)
    assert float(aux) > 0.0  # load-balance loss is positive


def test_capacity_drops_tokens(setup):
    params, x = setup
    # capacity 1 slot per expert: most tokens dropped -> zero rows
    y, _ = moe_ffn(params, x, capacity_factor=E / N)
    zero_rows = (np.abs(np.asarray(y)).sum(axis=1) < 1e-9).sum()
    assert zero_rows >= N - 2 * E, zero_rows
    # generous capacity: no zero rows (every token routed)
    y2, _ = moe_ffn(params, x, capacity_factor=float(E))
    assert (np.abs(np.asarray(y2)).sum(axis=1) < 1e-9).sum() == 0


@pytest.mark.parametrize("ep", [2, 4])
def test_expert_parallel_matches_dense(setup, ep):
    params, x = setup
    mesh = DeviceMesh({"ep": ep})
    y_ep, aux_ep = jax.jit(
        lambda p, xx: moe_ffn_ep(p, xx, mesh, capacity_factor=float(E))
    )(params, x)
    # per-token equivalence (capacity admits everything on every shard)
    np.testing.assert_allclose(np.asarray(y_ep),
                               _reference_loop(params, x),
                               rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(aux_ep))


def test_expert_parallel_gradients_flow(setup):
    params, x = setup
    mesh = DeviceMesh({"ep": 4})

    # compare the MAIN loss path only: the aux load-balance term is
    # deliberately per-device in EP (frac*mean_gate is nonlinear in the
    # token set, so per-shard aux != global aux — the standard choice)
    def loss_ep(p):
        y, _aux = moe_ffn_ep(p, x, mesh, capacity_factor=float(E))
        return (y ** 2).mean()

    def loss_dense(p):
        y, _aux = moe_ffn(p, x, capacity_factor=float(E))
        return (y ** 2).mean()

    g_ep = jax.jit(jax.grad(loss_ep))(params)
    g_dense = jax.grad(loss_dense)(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_ep[k]), np.asarray(g_dense[k]),
            rtol=2e-4, atol=1e-6, err_msg=f"grad mismatch for {k}")
    # experts actually receive gradient
    assert float(jnp.abs(g_ep["w1"]).sum()) > 0


def test_moe_trains(setup):
    """A few SGD steps on the dense MoE reduce a regression loss."""
    params, x = setup
    target = jax.random.normal(jax.random.PRNGKey(2), (N, D))

    def loss_fn(p):
        y, aux = moe_ffn(p, x, capacity_factor=float(E))
        return ((y - target) ** 2).mean() + 0.01 * aux

    vg = jax.jit(jax.value_and_grad(loss_fn))
    p = {k: v for k, v in params.items()}
    first = None
    for _ in range(80):
        l, g = vg(p)
        first = first if first is not None else float(l)
        p = jax.tree_util.tree_map(lambda a, b: a - 0.3 * b, p, g)
    assert float(l) < first * 0.8, (first, float(l))


@pytest.mark.slow  # heavy grad/jit compile; excluded from the tier-1 budget
def test_gluon_moe_dense_block():
    """The gluon-facing MoEDense block (op _contrib_MoEFFN) trains with
    autograd + Trainer and matches the functional dense MoE."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.contrib.nn import MoEDense

    layer = MoEDense(num_experts=4, hidden_units=16, capacity_factor=4.0)
    layer.initialize(mx.initializer.Xavier())
    x = nd.array(np.random.RandomState(0).randn(16, 8).astype(np.float32))
    y, aux = layer(x)
    assert y.shape == (16, 8)
    assert np.isfinite(float(aux.asscalar()))
    # equivalence with the functional path on the same params
    p = {"wg": layer.gate_weight.data()._data,
         "w1": layer.w1.data()._data, "b1": layer.b1.data()._data,
         "w2": layer.w2.data()._data, "b2": layer.b2.data()._data}
    y_ref, _ = moe_ffn(p, x._data, capacity_factor=4.0)
    np.testing.assert_allclose(y.asnumpy(), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)
    # a few training steps reduce a regression loss through the router
    target = nd.array(np.random.RandomState(1).randn(16, 8)
                      .astype(np.float32))
    trainer = gluon.Trainer(layer.collect_params(), "adam",
                            {"learning_rate": 0.01})
    losses = []
    for _ in range(25):
        with autograd.record():
            out, aux = layer(x)
            loss = ((out - target) ** 2).mean() + 0.01 * aux
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asscalar()))
    assert losses[-1] < losses[0] * 0.9, (losses[0], losses[-1])
    # 3-D (batch, seq, d) input keeps its shape
    x3 = nd.array(np.random.RandomState(2).randn(2, 8, 8)
                  .astype(np.float32))
    y3, _ = layer(x3)
    assert y3.shape == (2, 8, 8)


def test_gluon_moe_dense_with_in_units_initializes_fully():
    """With in_units given, every parameter (incl. w2/b2) materializes
    at initialize() — no deferred-init asymmetry (regression)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.nn import MoEDense
    layer = MoEDense(num_experts=2, hidden_units=4, in_units=6)
    layer.initialize(mx.initializer.Xavier())
    assert layer.w2.data().shape == (2, 4, 6)
    assert layer.b2.data().shape == (2, 6)
    assert layer.gate_weight.data().shape == (6, 2)


# -- the routed-expert op's score function (ops/_op_moe.py) -----------------------
def _routed_loop(h, router, w1, w3, w2, top_k, first, score):
    """Every token through every held expert, then a mask: the plain way."""
    scores = score(np.asarray(h, np.float64) @ np.asarray(router,
                                                          np.float64).T)
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :top_k]
    y = np.zeros(h.shape)
    for n in range(h.shape[0]):
        chosen = scores[n, order[n]]
        for e, weight in zip(order[n], chosen / chosen.sum()):
            if first <= e < first + w1.shape[0]:
                a, b = w1[e - first] @ h[n], w3[e - first] @ h[n]
                y[n] += weight * (w2[e - first] @ (a / (1 + np.exp(-a)) * b))
    return y, order


@pytest.mark.parametrize("score_function", ["sigmoid", "softmax"])
def test_routed_experts_score_function(score_function):
    """``sigmoid`` scores each expert alone, ``softmax`` all of them
    together: the same walk over the same tiles, other weights (and, away
    from ties, the same choice: both are monotone in the router's
    outputs)."""
    from mxnet_tpu.ops._op_moe import routed_experts
    rng = np.random.default_rng(7)
    h = rng.standard_normal((21, 16)).astype(np.float32)
    router = rng.standard_normal((16, 16)).astype(np.float32)
    w1, w3 = (rng.standard_normal((4, 12, 16)).astype(np.float32) * 0.3
              for _ in range(2))
    w2 = rng.standard_normal((4, 16, 12)).astype(np.float32) * 0.3
    score = {"sigmoid": lambda z: 1 / (1 + np.exp(-z)),
             "softmax": lambda z: np.exp(z - z.max(-1, keepdims=True))
             / np.exp(z - z.max(-1, keepdims=True)).sum(-1, keepdims=True)
             }[score_function]
    want, order = _routed_loop(h, router, w1, w3, w2, 3, 4, score)
    with jax.default_matmul_precision("highest"):
        y, load, _rows = routed_experts(
            jnp.asarray(h), jnp.asarray(router), jnp.asarray(w1),
            jnp.asarray(w3), jnp.asarray(w2), 3, 4, tile=4,
            score_function=score_function)
        other, other_load, _ = routed_experts(
            jnp.asarray(h), jnp.asarray(router), jnp.asarray(w1),
            jnp.asarray(w3), jnp.asarray(w2), 3, 4, tile=4,
            score_function="softmax" if score_function == "sigmoid"
            else "sigmoid")
    np.testing.assert_allclose(y, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))
    np.testing.assert_array_equal(
        load, [(order == e).sum() for e in range(4, 8)])
    # the choice is the same, the weights are not
    np.testing.assert_array_equal(load, other_load)
    assert np.abs(np.asarray(y) - np.asarray(other)).max() > 1e-3


def test_routed_experts_refuse_an_unknown_score_function():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    h, router = nd.ones((5, 8)), nd.ones((4, 8))
    w1 = w3 = nd.ones((2, 6, 8))
    with pytest.raises(mx.MXNetError, match="scores 'tanh'"):
        nd.contrib.routed_experts(h, router, w1, w3, nd.ones((2, 8, 6)),
                                  top_k=2, score_function="tanh")
