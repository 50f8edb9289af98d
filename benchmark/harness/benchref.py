"""The few operations the plain references are written in: straight
``jax.numpy``/``lax`` in float32, no kernels, nothing from ``mxnet_tpu``.
Each reference runs under ``jax.default_matmul_precision("highest")``
(``run_reference``), because a TPU multiplies float32 at a lower
precision unless told otherwise.

``conv`` and ``dense`` also tally their multiply-accumulates when given a
list, so a configuration's FLOPs come from the very shapes its reference
runs (``count_macs``): convolutions and dense layers only, as MFU counts
them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv(x, w, stride=1, pad=0, groups=1, bias=None, tally=None):
    """NCHW convolution, weights OIHW (``I`` = in/groups)."""
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups)
    if tally is not None:
        # per image: every output element sums I*kh*kw products
        tally.append(y.shape[1] * y.shape[2] * y.shape[3]
                     * w.shape[1] * w.shape[2] * w.shape[3])
    if bias is not None:
        y = y + bias.reshape(1, -1, 1, 1)
    return y


def batch_norm(x, p, train, eps):
    """BatchNorm over N, H, W with ``p`` = (gamma, beta, moving_mean,
    moving_var): batch statistics (biased variance) when training, the
    moving ones otherwise."""
    gamma, beta, mean, var = p
    if train:
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.var(x, axis=(0, 2, 3))
    shape = (1, -1, 1, 1)
    return (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + eps) \
        * gamma.reshape(shape) + beta.reshape(shape)


def max_pool(x, k, stride, pad):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, stride, stride),
        [(0, 0), (0, 0), (pad, pad), (pad, pad)])


def dense(x, w, b=None, tally=None):
    """``x @ w.T + b`` with ``w`` (out, in), as MXNet stores it."""
    if tally is not None:
        tally.append(w.shape[0] * w.shape[1])
    y = x @ w.T
    return y if b is None else y + b


def softmax_ce(logits, labels):
    """Mean over the batch of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    idx = labels.astype(jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, idx[:, None], axis=1))


def run_reference(forward, params, x, labels=None, device=None):
    """Eval-mode logits (``labels`` None) or the train-mode loss of one
    batch, float32 at the highest precision, on ``device``."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = jnp.asarray(x, jnp.float32)
    if device is not None:
        params = jax.device_put(params, device)
        x = jax.device_put(x, device)
    with jax.default_matmul_precision("highest"):
        if labels is None:
            return jax.jit(lambda p, a: forward(p, a, False))(params, x)
        y = jnp.asarray(labels)
        if device is not None:
            y = jax.device_put(y, device)
        return jax.jit(
            lambda p, a, l: softmax_ce(forward(p, a, True), l))(params, x, y)


def count_macs(forward, shapes, image):
    """Multiply-accumulates of one image's forward pass, from the shapes
    alone (nothing is computed)."""
    tally = []
    params = {k: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
              for k, s in shapes.items()}
    x = jax.ShapeDtypeStruct((1,) + tuple(image), jnp.float32)
    jax.eval_shape(lambda p, a: forward(p, a, False, tally=tally), params, x)
    return int(sum(tally))
