"""Fused LayerNorm — Pallas TPU kernel #2.

Reference capability anchor: nn/layer_norm.cc computes mean/variance and
the affine transform as separate kernels over HBM; XLA fuses most of the
chain already, but the canonical fused-row kernel keeps each row resident
in VMEM for exactly one read and one write of HBM per element — the
bandwidth floor. Rows are processed in (BLOCK_ROWS, D) tiles; statistics
are computed in f32 regardless of input dtype (bf16-safe).

Forward runs as a Pallas kernel — Mosaic where the program is lowered
for a tpu, the Pallas interpreter elsewhere, so CPU tests exercise the
same kernel body (_pallas_rows.per_platform); backward is a custom_vjp
in plain XLA using the saved per-row mean/rstd — the standard analytic
LayerNorm gradient, fused by XLA into two row reductions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _pallas_rows as _rows


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    # per-row statistics stay 2-D (rows, 1): Mosaic has no cheap
    # relayout of a sublane vector into a 1-D lane vector
    mean_ref[:] = mean
    rstd_ref[:] = rstd


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def _ln_fwd(x2, gamma, beta, *, eps, block_rows):
    """(out (n, d), mean (n, 1), rstd (n, 1)); rows padded to the tile."""
    n, d = x2.shape
    xp = _rows.pad_rows(x2, block_rows)
    n_pad = xp.shape[0]

    def call(interpret, xp, g2, b2):
        return pl.pallas_call(
            functools.partial(_ln_kernel, eps=eps),
            grid=(n_pad // block_rows,),
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_pad, d), x2.dtype),
                jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
                jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            ],
            interpret=interpret,
            name="mx_layernorm_fwd",
        )(xp, g2, b2)

    out, mean, rstd = _rows.per_platform(
        call, xp, gamma.reshape(1, d), beta.reshape(1, d))
    return out[:n], mean[:n], rstd[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm(x2, gamma, beta, eps, block_rows):
    out, _m, _r = _ln_core(x2, gamma, beta, eps, block_rows)
    return out


def _ln_core(x2, gamma, beta, eps, block_rows=None):
    n, d = x2.shape
    return _ln_fwd(x2, gamma, beta, eps=eps,
                   block_rows=_rows.resolve_block_rows(n, d, x2.dtype,
                                                       block_rows))


def _ln_vjp_fwd(x2, gamma, beta, eps, block_rows):
    out, mean, rstd = _ln_core(x2, gamma, beta, eps, block_rows)
    return out, (x2, gamma, beta, mean, rstd)


def _ln_vjp_bwd(eps, block_rows, res, ct):
    x2, gamma, beta, mean, rstd = res
    xf = x2.astype(jnp.float32)
    ctf = ct.astype(jnp.float32)
    xhat = (xf - mean) * rstd
    gctf = ctf * gamma.astype(jnp.float32)[None, :]
    d = x2.shape[-1]
    # analytic LN gradient: dx = rstd * (g·ct - mean(g·ct) - xhat*mean(g·ct*xhat))
    m1 = jnp.mean(gctf, axis=-1, keepdims=True)
    m2 = jnp.mean(gctf * xhat, axis=-1, keepdims=True)
    dx = (gctf - m1 - xhat * m2) * rstd
    dgamma = jnp.sum(ctf * xhat, axis=0)
    dbeta = jnp.sum(ctf, axis=0)
    return (dx.astype(x2.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype))


_layer_norm.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def fused_layer_norm(x, gamma, beta, eps=1e-5, axis=-1, block_rows=None):
    """Fused LayerNorm over the trailing axis (differentiable).

    x: any shape; normalization along ``axis`` (must be the last axis or
    movable there). gamma/beta: (d,).  ``block_rows`` is the tunable row
    tile (kernels autotuner config); None picks the built-in heuristic.
    """
    if axis not in (-1, x.ndim - 1):
        x = jnp.moveaxis(x, axis, -1)
    shape = x.shape
    out = _layer_norm(x.reshape(-1, shape[-1]), gamma, beta, float(eps),
                      block_rows)
    out = out.reshape(shape)
    if axis not in (-1, len(shape) - 1):
        out = jnp.moveaxis(out, -1, axis)
    return out


def plain_layer_norm(x, gamma, beta, eps=1e-5, axis=-1):
    """The pure-XLA LayerNorm the op path uses when the kernel is off —
    and, verbatim, the kernel registry's reference implementation.  One
    definition on purpose: ``MXNET_KERNELS=reference`` must be bitwise
    identical to kernels-off, which only holds if both modes lower the
    exact same jaxpr."""
    from jax import lax
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    bshape = tuple(x.shape[i] if i == (axis % x.ndim) else 1
                   for i in range(x.ndim))
    return out * gamma.reshape(bshape) + beta.reshape(bshape)
