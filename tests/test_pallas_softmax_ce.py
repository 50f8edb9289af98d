"""Fused softmax-CE Pallas kernel (ops/pallas_softmax_ce.py) — same
test discipline as the LayerNorm kernel: interpret-mode execution of
the REAL kernel on CPU, values + gradients pinned against plain XLA,
gate behavior, and the registered op routing through it."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.ops.pallas_softmax_ce import (fused_softmax_ce,
                                             fused_softmax_ce_available)

rng = np.random.RandomState(31)


def _ref(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[:, None], axis=-1)[:, 0]


@pytest.mark.parametrize("n,d", [(8, 10), (13, 7), (64, 1000)])
def test_forward_matches_xla(n, d):
    x = jnp.asarray(rng.randn(n, d).astype(np.float32) * 3)
    lab = jnp.asarray(rng.randint(0, d, n))
    got = fused_softmax_ce(x, lab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(x, lab)),
                               rtol=1e-5, atol=1e-6)


def test_bf16_stability_and_large_logits():
    import ml_dtypes
    x = jnp.asarray((rng.randn(16, 32) * 30).astype(ml_dtypes.bfloat16))
    lab = jnp.asarray(rng.randint(0, 32, 16))
    got = fused_softmax_ce(x, lab)
    assert np.isfinite(np.asarray(got)).all()  # f32 max-subtraction inside
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(x, lab)),
                               rtol=5e-2, atol=1e-2)


def test_gradient_matches_analytic():
    n, d = 12, 9
    x = jnp.asarray(rng.randn(n, d).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, d, n))

    g_fused = jax.grad(lambda z: fused_softmax_ce(z, lab).sum())(x)
    g_ref = jax.grad(lambda z: _ref(z, lab).sum())(x)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


def test_gate_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_SOFTMAX_CE", "0")
    assert fused_softmax_ce_available(8, 16, jnp.float32) is False
    x = jnp.asarray(rng.randn(4, 6).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 6, 4))
    got = fused_softmax_ce(x, lab)  # fallback path
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(x, lab)),
                               rtol=1e-5)
    monkeypatch.setenv("MXNET_FUSED_SOFTMAX_CE", "1")
    assert fused_softmax_ce_available(8, 16, jnp.float32) is True


def test_registered_op_routes_through_kernel():
    """nd.softmax_cross_entropy (reference loss_binary_op.cc) totals the
    per-row kernel losses and stays differentiable under the tape."""
    x_np = rng.randn(6, 5).astype(np.float32)
    lab_np = rng.randint(0, 5, 6).astype(np.float32)
    x = nd.array(x_np)
    x.attach_grad()
    with autograd.record():
        loss = nd.softmax_cross_entropy(x, nd.array(lab_np))
    loss.backward()
    want = float(np.asarray(_ref(jnp.asarray(x_np),
                                 jnp.asarray(lab_np))).sum())
    assert float(loss.asscalar()) == pytest.approx(want, rel=1e-5)
    p = np.exp(x_np - x_np.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    p[np.arange(6), lab_np.astype(int)] -= 1
    np.testing.assert_allclose(x.grad.asnumpy(), p, rtol=1e-4, atol=1e-6)
    # doc example from the reference op (loss_binary_op.cc:57)
    data = nd.array(np.array([[1, 2, 3], [11, 7, 5]], np.float32))
    label = nd.array(np.array([2, 0], np.float32))
    got = float(nd.softmax_cross_entropy(data, label).asscalar())
    assert got == pytest.approx(0.4281871, rel=1e-4)


def test_ignore_label_and_zero_batch():
    """-1 padding labels give zero loss AND zero gradient (one_hot
    semantics); n=0 returns empty (regressions from review)."""
    x = jnp.asarray(rng.randn(5, 4).astype(np.float32))
    lab = jnp.asarray(np.array([1, -1, 2, -1, 0], np.int32))

    loss = fused_softmax_ce(x, lab)
    assert np.asarray(loss)[1] == 0.0 and np.asarray(loss)[3] == 0.0
    g = jax.grad(lambda z: fused_softmax_ce(z, lab).sum())(x)
    np.testing.assert_allclose(np.asarray(g)[[1, 3]], 0.0, atol=1e-7)
    # valid rows unaffected by the masking
    ref = np.asarray(_ref(x, jnp.clip(lab, 0, 3)))
    np.testing.assert_allclose(np.asarray(loss)[[0, 2, 4]],
                               ref[[0, 2, 4]], rtol=1e-5)
    # empty batch
    empty = fused_softmax_ce(jnp.zeros((0, 4), jnp.float32),
                             jnp.zeros((0,), jnp.int32))
    assert empty.shape == (0,)


def test_padded_rows_and_ignored_labels_n100():
    """n=100 has no tile divisor >= 8: rows are padded (with label -1)
    up to the tile; -1 labels inside the batch still give zero loss and
    zero gradient, picked without a gather (compare against an iota)."""
    n, d = 100, 40
    x = jnp.asarray(rng.randn(n, d).astype(np.float32) * 2)
    lab_np = rng.randint(0, d, n).astype(np.int32)
    lab_np[[0, 17, 99]] = -1
    lab = jnp.asarray(lab_np)
    loss = np.asarray(fused_softmax_ce(x, lab))
    assert loss.shape == (n,)
    keep = lab_np >= 0
    assert (loss[~keep] == 0.0).all()
    ref = np.asarray(_ref(x, jnp.clip(lab, 0, d - 1)))
    np.testing.assert_allclose(loss[keep], ref[keep], rtol=1e-5, atol=1e-6)
    g = np.asarray(jax.grad(lambda z: fused_softmax_ce(z, lab).sum())(x))
    assert g.shape == (n, d) and (g[~keep] == 0.0).all()
    p = np.exp(np.asarray(x) - np.asarray(x).max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    p[np.arange(n)[keep], lab_np[keep]] -= 1
    np.testing.assert_allclose(g[keep], p[keep], rtol=1e-4, atol=1e-6)


def test_auto_gate_is_a_rule_on_the_shape(monkeypatch):
    """auto decides by shape — the class count must fit the VMEM tile
    budget — never by probing the compiler and swallowing its answer."""
    monkeypatch.delenv("MXNET_FUSED_SOFTMAX_CE", raising=False)
    assert fused_softmax_ce_available(128, 1000, jnp.float32) is True
    assert fused_softmax_ce_available(128, 50304, jnp.float32) is False
    assert fused_softmax_ce_available(128, 16384, jnp.bfloat16) is True


def test_gate_accepts_ln_style_spellings(monkeypatch):
    for off in ("0", "false", "OFF"):
        monkeypatch.setenv("MXNET_FUSED_SOFTMAX_CE", off)
        assert fused_softmax_ce_available(8, 16, jnp.float32) is False
    for on in ("1", "true", "ON"):
        monkeypatch.setenv("MXNET_FUSED_SOFTMAX_CE", on)
        assert fused_softmax_ce_available(8, 16, jnp.float32) is True
