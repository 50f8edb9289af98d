"""Fused optimizer update ops.

Reference: src/operator/optimizer_op.cc (+contrib/adamw.cc) — fused
sgd/sgd_mom/adam/... updates, including multi-precision (fp32 master weights
for fp16 params) variants. Here each update is one jitted XLA computation;
"fused" comes free from XLA fusion. Multi-precision maps to bf16 params with
f32 master copies (the TPU-idiomatic mixed-precision recipe).

All ops return the updated weight (plus updated state tensors) functionally;
the NDArray layer writes results back into the originals so the MXNet
"in-place update" API is preserved (SURVEY.md §7 hard part 1: aliasing via
donation happens inside jit through input-output aliasing when shapes match).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .registry import register


def _common(attrs):
    lr = float(attrs["lr"])
    wd = float(attrs.get("wd", 0.0))
    rescale = float(attrs.get("rescale_grad", 1.0))
    clip = attrs.get("clip_gradient", None)
    clip = None if clip in (None, -1, -1.0) else float(clip)
    return lr, wd, rescale, clip


def _prep_grad(grad, rescale, clip, dtype=None):
    g = grad.astype(dtype or grad.dtype) * rescale
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    return g


@register("sgd_update")
def _sgd_update(attrs, weight, grad):
    lr, wd, rescale, clip = _common(attrs)
    g = _prep_grad(grad, rescale, clip)
    return weight - lr * (g + wd * weight)


@register("sgd_mom_update", num_outputs=2, mutate_aux=(2,))
def _sgd_mom_update(attrs, weight, grad, mom):
    lr, wd, rescale, clip = _common(attrs)
    momentum = float(attrs.get("momentum", 0.0))
    g = _prep_grad(grad, rescale, clip)
    new_mom = momentum * mom - lr * (g + wd * weight)
    return weight + new_mom, new_mom


@register("mp_sgd_update", num_outputs=2, mutate_aux=(2,))
def _mp_sgd_update(attrs, weight, grad, weight32):
    lr, wd, rescale, clip = _common(attrs)
    g = _prep_grad(grad, rescale, clip, jnp.float32)
    w32 = weight32 - lr * (g + wd * weight32)
    return w32.astype(weight.dtype), w32


@register("mp_sgd_mom_update", num_outputs=3, mutate_aux=(2, 3))
def _mp_sgd_mom_update(attrs, weight, grad, mom, weight32):
    lr, wd, rescale, clip = _common(attrs)
    momentum = float(attrs.get("momentum", 0.0))
    g = _prep_grad(grad, rescale, clip, jnp.float32)
    new_mom = momentum * mom - lr * (g + wd * weight32)
    w32 = weight32 + new_mom
    return w32.astype(weight.dtype), new_mom, w32


@register("nag_mom_update", num_outputs=2, mutate_aux=(2,))
def _nag_mom_update(attrs, weight, grad, mom):
    lr, wd, rescale, clip = _common(attrs)
    momentum = float(attrs.get("momentum", 0.0))
    g = _prep_grad(grad, rescale, clip) + wd * weight
    new_mom = momentum * mom + g
    return weight - lr * (g + momentum * new_mom), new_mom


@register("adam_update", num_outputs=3, mutate_aux=(2, 3))
def _adam_update(attrs, weight, grad, mean, var):
    lr, wd, rescale, clip = _common(attrs)
    beta1 = float(attrs.get("beta1", 0.9))
    beta2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(grad, rescale, clip) + wd * weight
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    if bool(attrs.get("lazy_update", False)):
        pass  # dense path identical under XLA
    w = weight - lr * m / (jnp.sqrt(v) + eps)
    return w, m, v


@register("adamw_update", num_outputs=3, mutate_aux=(2, 3))
def _adamw_update(attrs, weight, grad, mean, var):
    """Decoupled weight decay (reference: src/operator/contrib/adamw.cc)."""
    lr, wd, rescale, clip = _common(attrs)
    eta = float(attrs.get("eta", 1.0))
    beta1 = float(attrs.get("beta1", 0.9))
    beta2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(grad, rescale, clip)
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    w = weight - eta * (lr * m / (jnp.sqrt(v) + eps) + wd * weight)
    return w, m, v


@register("rmsprop_update", num_outputs=2, mutate_aux=(2,))
def _rmsprop_update(attrs, weight, grad, n):
    lr, wd, rescale, clip = _common(attrs)
    gamma1 = float(attrs.get("gamma1", 0.95))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(grad, rescale, clip) + wd * weight
    n2 = gamma1 * n + (1 - gamma1) * jnp.square(g)
    return weight - lr * g / (jnp.sqrt(n2) + eps), n2


@register("rmspropalex_update", num_outputs=4, mutate_aux=(2, 3, 4))
def _rmspropalex_update(attrs, weight, grad, n, g_avg, delta):
    lr, wd, rescale, clip = _common(attrs)
    gamma1 = float(attrs.get("gamma1", 0.95))
    gamma2 = float(attrs.get("gamma2", 0.9))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(grad, rescale, clip) + wd * weight
    n2 = gamma1 * n + (1 - gamma1) * jnp.square(g)
    gavg2 = gamma1 * g_avg + (1 - gamma1) * g
    d2 = gamma2 * delta - lr * g / jnp.sqrt(n2 - jnp.square(gavg2) + eps)
    return weight + d2, n2, gavg2, d2


@register("ftrl_update", num_outputs=3, mutate_aux=(2, 3))
def _ftrl_update(attrs, weight, grad, z, n):
    lr, wd, rescale, clip = _common(attrs)
    lamda1 = float(attrs.get("lamda1", 0.01))
    beta = float(attrs.get("beta", 1.0))
    g = _prep_grad(grad, rescale, clip)
    n2 = n + jnp.square(g)
    sigma = (jnp.sqrt(n2) - jnp.sqrt(n)) / lr
    z2 = z + g - sigma * weight
    w = jnp.where(jnp.abs(z2) > lamda1,
                  -(z2 - jnp.sign(z2) * lamda1) / ((beta + jnp.sqrt(n2)) / lr + wd),
                  jnp.zeros_like(weight))
    return w, z2, n2


@register("signsgd_update")
def _signsgd_update(attrs, weight, grad):
    lr, wd, rescale, clip = _common(attrs)
    g = _prep_grad(grad, rescale, clip)
    return weight - lr * (jnp.sign(g) + wd * weight)


@register("signum_update", num_outputs=2, mutate_aux=(2,))
def _signum_update(attrs, weight, grad, mom):
    lr, wd, rescale, clip = _common(attrs)
    momentum = float(attrs.get("momentum", 0.0))
    wd_lh = float(attrs.get("wd_lh", 0.0))
    g = _prep_grad(grad, rescale, clip)
    new_mom = momentum * mom - (1 - momentum) * (g + wd * weight)
    w = (1 - lr * wd_lh) * weight + lr * jnp.sign(new_mom)
    return w, new_mom


@register("lamb_update_phase1", num_outputs=3, mutate_aux=(2, 3))
def _lamb_phase1(attrs, weight, grad, mean, var):
    beta1 = float(attrs.get("beta1", 0.9))
    beta2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-6))
    wd = float(attrs.get("wd", 0.0))
    t = int(attrs.get("t", 1))
    rescale = float(attrs.get("rescale_grad", 1.0))
    g = grad * rescale
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    if bool(attrs.get("bias_correction", True)):
        mhat = m / (1 - beta1 ** t)
        vhat = v / (1 - beta2 ** t)
    else:
        mhat, vhat = m, v
    return mhat / (jnp.sqrt(vhat) + eps) + wd * weight, m, v


@register("all_finite")
def _all_finite(attrs, *arrays):
    ok = jnp.asarray(True)
    for a in arrays:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(a.astype(jnp.float32))))
    return ok.astype(jnp.float32).reshape(1)


@register("multi_all_finite")
def _multi_all_finite(attrs, *arrays):
    return _all_finite(attrs, *arrays)


# --- aggregated multi-tensor updates (reference: optimizer_op.cc:320-406) ---
# One op updates N weights in a single dispatch; XLA fuses the per-weight
# elementwise updates into one kernel pass, which is exactly what the
# reference's hand-rolled MultiSGDKernel buys on GPU.

def _multi_common(attrs):
    n = int(attrs.get("num_weights", 1))
    def _floats(v):
        if isinstance(v, (int, float)):
            return [float(v)] * n
        return [float(x) for x in v]
    lrs = _floats(attrs["lrs"])
    wds = _floats(attrs["wds"])
    rescale = float(attrs.get("rescale_grad", 1.0))
    clip = attrs.get("clip_gradient", None)
    clip = None if clip in (None, -1, -1.0) else float(clip)
    return n, lrs, wds, rescale, clip


@register("multi_sgd_update",
          num_outputs=lambda a: int(a.get("num_weights", 1)))
def _multi_sgd_update(attrs, *args):
    n, lrs, wds, rescale, clip = _multi_common(attrs)
    outs = []
    for i in range(n):
        w, g = args[2 * i], args[2 * i + 1]
        gi = _prep_grad(g, rescale, clip)
        outs.append(w - lrs[i] * (gi + wds[i] * w))
    return tuple(outs)


@register("multi_sgd_mom_update",
          num_outputs=lambda a: 2 * int(a.get("num_weights", 1)),
          mutate_aux=lambda a: tuple(
              3 * i + 2 for i in range(int(a.get("num_weights", 1)))))
def _multi_sgd_mom_update(attrs, *args):
    n, lrs, wds, rescale, clip = _multi_common(attrs)
    momentum = float(attrs.get("momentum", 0.0))
    ws, ms = [], []
    for i in range(n):
        w, g, m = args[3 * i], args[3 * i + 1], args[3 * i + 2]
        gi = _prep_grad(g, rescale, clip)
        nm = momentum * m - lrs[i] * (gi + wds[i] * w)
        ws.append(w + nm)
        ms.append(nm)
    return tuple(ws) + tuple(ms)


@register("multi_mp_sgd_update",
          num_outputs=lambda a: 2 * int(a.get("num_weights", 1)),
          mutate_aux=lambda a: tuple(
              3 * i + 2 for i in range(int(a.get("num_weights", 1)))))
def _multi_mp_sgd_update(attrs, *args):
    n, lrs, wds, rescale, clip = _multi_common(attrs)
    ws, w32s = [], []
    for i in range(n):
        w, g, w32 = args[3 * i], args[3 * i + 1], args[3 * i + 2]
        gi = _prep_grad(g, rescale, clip, jnp.float32)
        nw32 = w32 - lrs[i] * (gi + wds[i] * w32)
        ws.append(nw32.astype(w.dtype))
        w32s.append(nw32)
    return tuple(ws) + tuple(w32s)


@register("multi_mp_sgd_mom_update",
          num_outputs=lambda a: 3 * int(a.get("num_weights", 1)),
          mutate_aux=lambda a: tuple(
              4 * i + 2 for i in range(int(a.get("num_weights", 1))))
          + tuple(4 * i + 3 for i in range(int(a.get("num_weights", 1)))))
def _multi_mp_sgd_mom_update(attrs, *args):
    n, lrs, wds, rescale, clip = _multi_common(attrs)
    momentum = float(attrs.get("momentum", 0.0))
    ws, ms, w32s = [], [], []
    for i in range(n):
        w, g, m, w32 = (args[4 * i], args[4 * i + 1],
                        args[4 * i + 2], args[4 * i + 3])
        gi = _prep_grad(g, rescale, clip, jnp.float32)
        nm = momentum * m - lrs[i] * (gi + wds[i] * w32)
        nw32 = w32 + nm
        ws.append(nw32.astype(w.dtype))
        ms.append(nm)
        w32s.append(nw32)
    return tuple(ws) + tuple(ms) + tuple(w32s)


# --- round-4 named-op gap closers -------------------------------------------

@register("ftml_update", num_outputs=4, mutate_aux=(2, 3, 4))
def _ftml_update(attrs, weight, grad, d, v, z):
    """FTML (reference: optimizer_op-inl.h FTMLKernel:~1215). Note the
    reference clips AFTER adding wd*weight (clip_grad applies to the
    regularized gradient), unlike sgd's clip-then-decay."""
    lr = float(attrs["lr"])
    beta1 = float(attrs.get("beta1", 0.6))
    beta2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-8))
    t = float(attrs["t"])
    wd = float(attrs.get("wd", 0.0))
    rescale = float(attrs.get("rescale_grad", 1.0))
    clip = attrs.get("clip_grad", None)
    clip = None if clip in (None, -1, -1.0) else float(clip)
    g = rescale * grad + wd * weight
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    v2 = beta2 * v + (1 - beta2) * jnp.square(g)
    d_t = (1 - beta1 ** t) / lr * (jnp.sqrt(v2 / (1 - beta2 ** t)) + eps)
    z2 = beta1 * z + (1 - beta1) * g - (d_t - beta1 * d) * weight
    return -z2 / d_t, d_t, v2, z2


@register("mp_nag_mom_update", num_outputs=3, mutate_aux=(2, 3))
def _mp_nag_mom_update(attrs, weight, grad, mom, weight32):
    """Multi-precision NAG: math in the f32 master copy (reference:
    optimizer_op.cc mp_nag_mom_update)."""
    lr, wd, rescale, clip = _common(attrs)
    momentum = float(attrs.get("momentum", 0.0))
    g = _prep_grad(grad, rescale, clip, jnp.float32) + wd * weight32
    new_mom = momentum * mom + g
    w32 = weight32 - lr * (g + momentum * new_mom)
    return w32.astype(weight.dtype), new_mom, w32


@register("_mp_adamw_update", alias=("mp_adamw_update",),
          num_outputs=4, mutate_aux=(2, 3, 4))
def _mp_adamw_update(attrs, weight, grad, mean, var, weight32):
    """Multi-precision AdamW (reference: contrib/adamw.cc
    _mp_adamw_update): adamw math on the f32 master weights."""
    lr, wd, rescale, clip = _common(attrs)
    eta = float(attrs.get("eta", 1.0))
    beta1 = float(attrs.get("beta1", 0.9))
    beta2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(grad, rescale, clip, jnp.float32)
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    w32 = weight32 - eta * (lr * m / (jnp.sqrt(v) + eps) + wd * weight32)
    return w32.astype(weight.dtype), m, v, w32


@register("_sparse_adagrad_update", alias=("sparse_adagrad_update",),
          num_outputs=2, mutate_aux=(2,))
def _sparse_adagrad_update(attrs, weight, grad, history):
    """AdaGrad with per-row lazy semantics (reference: optimizer_op.cc
    _sparse_adagrad_update — there grad is row_sparse and only touched
    rows update; densely a zero grad row leaves w/h unchanged, which this
    reproduces exactly: h += 0, w -= lr*0/... = w)."""
    lr = float(attrs["lr"])
    eps = float(attrs.get("epsilon", 1e-7))
    rescale = float(attrs.get("rescale_grad", 1.0))
    clip = attrs.get("clip_gradient", None)
    clip = None if clip in (None, -1, -1.0) else float(clip)
    g = _prep_grad(grad, rescale, clip)
    h2 = history + jnp.square(g)
    return weight - lr * g / (jnp.sqrt(h2) + eps), h2
