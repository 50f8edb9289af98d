"""Fused softmax + cross-entropy — Pallas TPU kernel #3.

Reference capability anchor: softmax_output-inl.h computes softmax and
the CE loss/gradient as separate passes over HBM. The fused row kernel
keeps each logit row resident in VMEM and emits BOTH the per-row loss
and the softmax probabilities in one pass (one HBM read of the logits),
with the max-subtraction done in f32 regardless of input dtype
(bf16-safe) — the classifier-head bandwidth floor.

Forward runs as a Pallas kernel — Mosaic where the program is lowered
for a tpu, the Pallas interpreter elsewhere, so CPU tests exercise the
same kernel body (_pallas_rows.per_platform); backward is the analytic
``(softmax - onehot) * ct`` in plain XLA from the saved probs (no 1/N —
the registered op SUMS per-row losses, reference loss_binary_op.cc).
Out-of-range labels (the -1 ignore/padding convention) contribute zero
loss and zero gradient, matching the one_hot semantics of the plain
path.  MXNET_FUSED_SOFTMAX_CE=1/true/on forces the kernel, 0/false/off
forces plain XLA, auto (default) takes the kernel wherever the shape
rule (_pallas_rows.row_tile_fits) holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _pallas_rows as _rows


def _smce_kernel(x_ref, lab_ref, loss_ref, prob_ref):
    x = x_ref[:].astype(jnp.float32)              # (B, D)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    lab = lab_ref[:]                              # (B, 1) int32
    # pick the label's logit by comparing against a class iota and a
    # masked sum — Mosaic has no gather.  A label outside [0, D) (e.g.
    # -1 padding) matches no class and contributes zero, like one_hot
    cls = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    hit = cls == lab
    picked = jnp.sum(jnp.where(hit, x - m, 0.0), axis=-1, keepdims=True)
    valid = jnp.sum(hit.astype(jnp.float32), axis=-1, keepdims=True)
    loss_ref[:] = (jnp.log(s) - picked) * valid   # (B, 1)
    prob_ref[:] = (e / s).astype(prob_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def _smce_fwd(x2, labels, *, block_rows):
    """(loss (n,) f32, prob (n, d)); rows padded to the tile with label
    -1, which the kernel scores as zero."""
    n, d = x2.shape
    xp = _rows.pad_rows(x2, block_rows)
    labp = _rows.pad_rows(labels.astype(jnp.int32).reshape(n, 1),
                          block_rows, value=-1)
    n_pad = xp.shape[0]

    def call(interpret, xp, labp):
        return pl.pallas_call(
            _smce_kernel,
            grid=(n_pad // block_rows,),
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
                jax.ShapeDtypeStruct((n_pad, d), x2.dtype),
            ],
            interpret=interpret,
            name="mx_softmax_ce_fwd",
        )(xp, labp)

    loss, prob = _rows.per_platform(call, xp, labp)
    return loss[:n, 0], prob[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_ce(logits, labels, block_rows):
    loss, _prob = _smce_core(logits, labels, block_rows)
    return loss


def _smce_core(logits, labels, block_rows=None):
    n, d = logits.shape
    return _smce_fwd(logits, labels,
                     block_rows=_rows.resolve_block_rows(
                         n, d, logits.dtype, block_rows))


def _smce_vjp_fwd(logits, labels, block_rows):
    loss, prob = _smce_core(logits, labels, block_rows)
    return loss, (prob, labels)


def _smce_vjp_bwd(block_rows, res, ct):
    prob, labels = res
    lab = labels.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, prob.shape[-1], dtype=jnp.float32)
    valid = ((lab >= 0) & (lab < prob.shape[-1])).astype(jnp.float32)
    # invalid (padding) rows get ZERO gradient, matching their zero loss
    d_logits = (prob.astype(jnp.float32) - onehot) \
        * (ct * valid)[:, None]
    return d_logits.astype(prob.dtype), None


_softmax_ce.defvjp(_smce_vjp_fwd, _smce_vjp_bwd)


def fused_softmax_ce_available(n, d, dtype):
    """MXNET_FUSED_SOFTMAX_CE, else the shape rule (see kernel_wanted)."""
    return _rows.kernel_wanted("MXNET_FUSED_SOFTMAX_CE", d, dtype)


def softmax_ce_kernel(logits, labels, block_rows=None):
    """The Pallas row kernel with an explicit (tunable) row tile — the
    kernels-registry entry point.  No availability gate: the caller
    (kernels.get / fused_softmax_ce) owns that decision."""
    return _softmax_ce(logits, labels.astype(jnp.int32), block_rows)


def plain_softmax_ce(logits, labels):
    """Pure-XLA per-row softmax CE — the gated-off path and, verbatim,
    the kernel registry's reference implementation (one definition so
    ``MXNET_KERNELS=reference`` lowers the same jaxpr as kernels-off)."""
    labels = labels.astype(jnp.int32)
    d = logits.shape[-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = (labels >= 0) & (labels < d)
    picked = jnp.take_along_axis(
        logp, jnp.clip(labels, 0, d - 1)[:, None], axis=-1)[:, 0]
    return jnp.where(valid, -picked, 0.0)


def fused_softmax_ce(logits, labels):
    """Per-row softmax cross-entropy loss, differentiable.

    logits: (n, d); labels: (n,) integer class ids. Returns (n,) f32
    losses.  Plain XLA when the kernel is gated off."""
    labels = labels.astype(jnp.int32)
    n, d = logits.shape
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    if fused_softmax_ce_available(n, d, logits.dtype):
        return _softmax_ce(logits, labels, None)
    return plain_softmax_ce(logits, labels)
