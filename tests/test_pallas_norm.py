"""Fused Pallas LayerNorm kernel tests (ops/pallas_norm.py) — runs under
the Pallas interpreter off-TPU, same code path as the device kernel."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops.pallas_norm import fused_layer_norm


def _ref_ln(x, g, b, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mean) / np.sqrt(var + eps) * g + b


def test_forward_matches_reference():
    rng = np.random.RandomState(0)
    for shape in [(8, 64), (4, 7, 128), (3, 33)]:
        x = rng.randn(*shape).astype(np.float32)
        g = (rng.rand(shape[-1]) + 0.5).astype(np.float32)
        b = rng.randn(shape[-1]).astype(np.float32)
        got = np.asarray(fused_layer_norm(jnp.asarray(x), jnp.asarray(g),
                                          jnp.asarray(b)))
        np.testing.assert_allclose(got, _ref_ln(x, g, b),
                                   rtol=1e-4, atol=1e-5)


def test_rows_without_a_tile_divisor_are_padded():
    """n=100 has no power-of-two divisor >= 8: the tile stays at the
    dtype's sublane packing (Mosaic's block rule) and the rows are
    padded, not tiled 4 at a time."""
    from mxnet_tpu.ops._pallas_rows import pick_block_rows
    assert pick_block_rows(100, 256, jnp.float32) == 8
    assert pick_block_rows(100, 256, jnp.bfloat16) == 16
    assert pick_block_rows(4096, 1024, jnp.bfloat16) == 256
    rng = np.random.RandomState(5)
    x = rng.randn(100, 256).astype(np.float32)
    g = (rng.rand(256) + 0.5).astype(np.float32)
    b = rng.randn(256).astype(np.float32)
    got = fused_layer_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    assert got.shape == (100, 256)
    np.testing.assert_allclose(np.asarray(got), _ref_ln(x, g, b),
                               rtol=1e-4, atol=1e-5)
    grads = jax.grad(lambda x_: (fused_layer_norm(
        x_, jnp.asarray(g), jnp.asarray(b)) ** 2).sum())(jnp.asarray(x))
    assert grads.shape == (100, 256) and np.isfinite(np.asarray(grads)).all()


def test_bf16_input_f32_stats():
    rng = np.random.RandomState(1)
    x = (rng.randn(16, 256) * 3 + 100).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    g = jnp.ones(256)
    b = jnp.zeros(256)
    got = np.asarray(fused_layer_norm(xb, g, b)).astype(np.float32)
    # compare against the bf16-ROUNDED input in f64 stats: isolates the
    # kernel's statistics precision from input quantization
    x_rounded = np.asarray(xb).astype(np.float64)
    ref = _ref_ln(x_rounded, np.ones(256), np.zeros(256))
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.02)


def test_gradient_matches_plain_xla():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(6, 48).astype(np.float32))
    g = jnp.asarray((rng.rand(48) + 0.5).astype(np.float32))
    b = jnp.asarray(rng.randn(48).astype(np.float32))

    def loss_fused(x_, g_, b_):
        return (fused_layer_norm(x_, g_, b_) ** 2).mean()

    def loss_plain(x_, g_, b_):
        mean = x_.mean(-1, keepdims=True)
        var = jnp.var(x_, axis=-1, keepdims=True)
        y = (x_ - mean) * jax.lax.rsqrt(var + 1e-5) * g_ + b_
        return (y ** 2).mean()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, g, b)
    gp = jax.grad(loss_plain, argnums=(0, 1, 2))(x, g, b)
    for a, r in zip(gf, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)


def test_layernorm_op_uses_fused_path():
    """The registered LayerNorm op routes trailing-axis cases through the
    kernel and stays numerically identical."""
    rng = np.random.RandomState(3)
    x = rng.randn(4, 10, 32).astype(np.float32)
    g = (rng.rand(32) + 0.5).astype(np.float32)
    b = rng.randn(32).astype(np.float32)
    out = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b),
                       axis=-1, eps=1e-5).asnumpy()
    np.testing.assert_allclose(out, _ref_ln(x, g, b), rtol=1e-4, atol=1e-5)
    # non-trailing axis falls back to the plain path, still correct
    out2 = nd.LayerNorm(nd.array(x), nd.array(rng.rand(10).astype(np.float32)),
                        nd.array(np.zeros(10, np.float32)),
                        axis=1, eps=1e-5)
    assert out2.shape == (4, 10, 32)


def test_gluon_layernorm_trains():
    from mxnet_tpu import gluon
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16), gluon.nn.LayerNorm(), gluon.nn.Dense(2))
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    L = gluon.loss.L2Loss()
    rng = np.random.RandomState(4)
    xs = nd.array(rng.randn(16, 8).astype(np.float32))
    ys = nd.array(rng.randn(16, 2).astype(np.float32))
    first = last = None
    for _ in range(8):
        with mx.autograd.record():
            l = L(net(xs), ys)
        l.backward()
        tr.step(16)
        cur = float(l.mean().asscalar())
        first = first if first is not None else cur
        last = cur
    assert last < first
