"""Neural-network ops.

Covers reference src/operator/nn/* (Convolution/Deconvolution + im2col CUDA,
cuDNN wrappers, Pooling pool.cuh, BatchNorm, LayerNorm, Dropout, Softmax
family, FullyConnected) and the fused RNN op (src/operator/rnn-inl.h:395).
TPU redesign: convs/matmuls lower to XLA conv_general_dilated/dot_general
which tile onto the MXU; the cuDNN autotuning layer has no equivalent because
XLA autotunes; fused RNN = lax.scan over a step function (compiled into one
loop on device, hidden-state in registers/VMEM instead of cuDNN descriptors).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register
from ..base import MXNetError


def _attr_bool(v):
    """Robust bool attr: accepts reference-style string attrs
    ("True"/"False"/"1"/"0") as well as Python bools."""
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes")
    return bool(v)


def _wb_names(attrs):
    """data/weight/bias input roles, honoring no_bias (FListInputNames
    parity: reference nn/fully_connected.cc ListArguments)."""
    if _attr_bool(attrs.get("no_bias", False)):
        return ("data", "weight")
    return ("data", "weight", "bias")


# --- FullyConnected (reference: nn/fully_connected.cc) ----------------------
@register("FullyConnected", input_names=_wb_names)
def _fully_connected(attrs, x, weight, *maybe_bias):
    x = x.astype(weight.dtype)  # AMP contract: weight dtype is authoritative
    if not bool(attrs.get("flatten", True)):
        out = jnp.matmul(x, weight.T)
    else:
        # explicit product, not -1: jnp's -1 inference divides by the
        # other dims' product and breaks on 0-size batches
        flat = 1
        for d in x.shape[1:]:
            flat *= d
        x2 = x.reshape(x.shape[0], flat)
        out = jnp.matmul(x2, weight.T)
    if maybe_bias and not bool(attrs.get("no_bias", False)):
        out = out + maybe_bias[0]
    return out


# --- Convolution (reference: nn/convolution.cc:399-527, im2col.cuh) ---------
def _conv_dim_numbers(ndim, layout):
    if layout in (None, "NCHW", "NCW", "NCDHW"):
        spec = "NC" + "DHW"[3 - (ndim - 2):]
        return lax.conv_dimension_numbers((1,) * ndim, (1,) * ndim,
                                          (spec, "OI" + spec[2:], spec))
    if layout in ("NHWC", "NWC", "NDHWC"):
        spatial = "DHW"[3 - (ndim - 2):]
        spec = "N" + spatial + "C"
        return lax.conv_dimension_numbers((1,) * ndim, (1,) * ndim,
                                          (spec, spatial + "IO", spec))
    raise ValueError(f"unsupported layout {layout}")


def _tupleize(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(int(x) for x in v)
    return t if t else (1,) * n


@register("Convolution", input_names=_wb_names)
def _convolution(attrs, x, weight, *maybe_bias):
    kernel = tuple(attrs["kernel"])
    nd = len(kernel)
    stride = _tupleize(attrs.get("stride"), nd)
    dilate = _tupleize(attrs.get("dilate"), nd)
    pad = _tupleize(attrs.get("pad"), nd) if attrs.get("pad") else (0,) * nd
    groups = int(attrs.get("num_group", 1))
    layout = attrs.get("layout", None) or ("NCW", "NCHW", "NCDHW")[nd - 1]
    dn = _conv_dim_numbers(nd + 2, layout)
    x = x.astype(weight.dtype)  # AMP contract: weight dtype is authoritative
    if (max(stride) > 1 and all(k == 1 for k in kernel)
            and all(p == 0 for p in pad)):
        # Strided 1x1 conv == spatial subsample + stride-1 1x1 conv (the
        # kernel only ever reads positions s*o).  Same forward FLOPs, but
        # the autodiff backward-data becomes a stride-1 dgrad plus a
        # zero-scatter pad instead of a conv over the zero-dilated input,
        # which XLA executes (and charges) at stride^2 x the useful work:
        # 4x on ResNet-50's downsample convs (HLO operation count of the
        # step at batch 8: 201.8 -> 183.3 GFLOP, no lhs-dilated
        # convolution left; a count, not a chip time).
        sp_axes = [i for i, ch in enumerate(layout) if ch in "DHW"]
        slicer = [slice(None)] * x.ndim
        for ax, s in zip(sp_axes, stride):
            slicer[ax] = slice(None, None, s)
        x = x[tuple(slicer)]
        stride = (1,) * nd
    # no preferred_element_type: TPU MXU accumulates bf16 convs in f32
    # already, and a mixed-dtype preferred type breaks the conv transpose
    # (backward) under jit
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=groups)
    if maybe_bias and not bool(attrs.get("no_bias", False)):
        b = maybe_bias[0]
        if layout.endswith("C"):
            out = out + b
        else:
            out = out + b.reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution", input_names=_wb_names)
def _deconvolution(attrs, x, weight, *maybe_bias):
    kernel = tuple(attrs["kernel"])
    nd = len(kernel)
    stride = _tupleize(attrs.get("stride"), nd)
    dilate = _tupleize(attrs.get("dilate"), nd)
    pad = _tupleize(attrs.get("pad"), nd) if attrs.get("pad") else (0,) * nd
    adj = _tupleize(attrs.get("adj"), nd) if attrs.get("adj") else (0,) * nd
    groups = int(attrs.get("num_group", 1))
    layout = attrs.get("layout", None) or ("NCW", "NCHW", "NCDHW")[nd - 1]
    dn = _conv_dim_numbers(nd + 2, layout)
    x = x.astype(weight.dtype)
    # transposed conv = lhs-dilated conv with flipped, IO-swapped kernel
    k_eff = [(k - 1) * d + 1 for k, d in zip(kernel, dilate)]
    tshape = attrs.get("target_shape")
    if tshape:
        # target_shape overrides pad/adj (reference deconvolution-inl.h:
        # InferPad — pad/adj attrs are IGNORED when a target is given)
        tshape = (tshape,) if isinstance(tshape, int) else tuple(tshape)
        if len(tshape) != nd:
            raise MXNetError(
                f"target_shape {tshape} must have {nd} dims to match "
                f"kernel {kernel}")
        in_sp = x.shape[2:] if not layout.endswith("C") else x.shape[1:-1]
        # reference InferPad (deconvolution-inl.h:138): total excess =
        # s*(i-1) + k_eff - target; odd totals put the EXTRA row in pad
        # (pad = (total+1)/2) and compensate with adj = total % 2
        totals = [stride[j] * (in_sp[j] - 1) + k_eff[j] - int(tshape[j])
                  for j in range(nd)]
        if any(t < 0 for t in totals):
            raise MXNetError(f"too big target shape {tshape}")
        pad = tuple((t + 1) // 2 for t in totals)
        adj = tuple(t % 2 for t in totals)
    padding = [(ke - 1 - p, ke - 1 - p + a) for ke, p, a in zip(k_eff, pad, adj)]
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    w = jnp.swapaxes(w, 0, 1)
    if groups > 1:
        # weight layout (Cin, Cout/g, *k) -> regroup for grouped transpose conv
        cin, coutg = weight.shape[0], weight.shape[1]
        w = weight.reshape((groups, cin // groups, coutg) + kernel)
        w = jnp.flip(w, axis=tuple(range(3, 3 + nd)))
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((coutg * groups, cin // groups) + kernel)
    out = lax.conv_general_dilated(
        x, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=groups)
    if maybe_bias and not bool(attrs.get("no_bias", False)):
        b = maybe_bias[0]
        out = out + (b if layout.endswith("C")
                     else b.reshape((1, -1) + (1,) * nd))
    return out


# --- Pooling (reference: nn/pooling.cc, pool.cuh) ---------------------------
@register("Pooling")
def _pooling(attrs, x):
    pool_type = attrs.get("pool_type", "max")
    global_pool = bool(attrs.get("global_pool", False))
    nd = x.ndim - 2
    layout = attrs.get("layout", None) or ("NCW", "NCHW", "NCDHW")[nd - 1]
    channel_last = layout.endswith("C")
    sp_axes = tuple(range(1, 1 + nd)) if channel_last else tuple(range(2, 2 + nd))
    if global_pool:
        if pool_type == "max":
            return jnp.max(x, axis=sp_axes, keepdims=True)
        return jnp.mean(x, axis=sp_axes, keepdims=True)
    kernel = tuple(attrs["kernel"])
    stride = _tupleize(attrs.get("stride"), nd)
    pad = _tupleize(attrs.get("pad"), nd) if attrs.get("pad") else (0,) * nd
    conv = attrs.get("pooling_convention", "valid")

    if channel_last:  # normalize to channel-first for the window extraction
        perm = (0, x.ndim - 1) + tuple(range(1, x.ndim - 1))
        x = x.transpose(perm)

    pad_lohi = [(p, p) for p in pad]
    if conv == "full":
        # ceil-mode: extend padding on the high side so the last window fits
        for i in range(nd):
            size = x.shape[2 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            if rem:
                pad_lohi[i] = (pad[i], pad[i] + stride[i] - rem)

    # lax.reduce_window is THE TPU pooling primitive: fwd fuses into a
    # windowed reduce, max-pool backward lowers to select_and_scatter_add
    # (hardware path) instead of a scatter. Measured on TPU v5e at the
    # ResNet stem shape (32,64,112,112): gather-windows fwd+bwd 4.62 ms vs
    # reduce_window 0.36 ms — the scatter-add backward was 13x slower.
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    padding = [(0, 0), (0, 0)] + [tuple(p) for p in pad_lohi]
    # init values MUST be python scalars: jax only recognizes the
    # max/add monoid (and so attaches the autodiff rule) for literal
    # identity inits — an array init falls back to the generic
    # reduce_window primitive, which the whole-graph vjp cannot linearize
    if pool_type == "max":
        if jnp.issubdtype(x.dtype, jnp.floating):
            init = -jnp.inf
        else:
            init = int(jnp.iinfo(x.dtype).min)
        out = lax.reduce_window(x, init, lax.max, window, strides, padding)
    elif pool_type in ("avg", "sum"):
        zero = 0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0
        summed = lax.reduce_window(x, zero, lax.add, window, strides,
                                   padding)
        if pool_type == "sum":
            out = summed
        elif bool(attrs.get("count_include_pad", True)):
            out = summed / jnp.asarray(float(np.prod(kernel)), x.dtype)
        else:
            # counts are identical across batch/channel — pool a (1,1,...)
            # ones tensor and broadcast
            ones = jnp.ones((1, 1) + x.shape[2:], x.dtype)
            counts = lax.reduce_window(ones, zero, lax.add,
                                       (1, 1) + tuple(kernel),
                                       strides, padding)
            out = summed / counts
    else:
        raise ValueError(f"pool_type {pool_type}")

    if channel_last:
        inv = (0,) + tuple(range(2, out.ndim)) + (1,)
        out = out.transpose(inv)
    return out


@register("UpSampling")
def _upsampling(attrs, x, *weights):
    scale = int(attrs["scale"])
    if attrs.get("sample_type", "nearest") == "nearest":
        return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    n, c, h, w = x.shape
    return jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")


# --- normalisation ----------------------------------------------------------
@register("BatchNorm", num_outputs=3, mutate_aux=(3, 4),
          input_names=("data", "gamma", "beta", "moving_mean", "moving_var"))
def _batch_norm(attrs, x, gamma, beta, moving_mean, moving_var):
    """Returns (out, new_moving_mean, new_moving_var).

    Reference nn/batch_norm.cc mutates the aux states in-place during
    training; here updated aux are explicit outputs (functional) and the
    caller writes them back (see gluon.nn.BatchNorm / executor aux handling).
    """
    eps = float(attrs.get("eps", 1e-3))
    momentum = float(attrs.get("momentum", 0.9))
    axis = int(attrs.get("axis", 1)) % x.ndim  # axis=-1 == channels-last
    training = bool(attrs.get("_training", False)) and not bool(
        attrs.get("use_global_stats", False))
    fix_gamma = bool(attrs.get("fix_gamma", True))
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    red_axes = tuple(i for i in range(x.ndim) if i != axis)
    bshape = tuple(x.shape[i] if i == axis else 1 for i in range(x.ndim))
    if training:
        # two-pass (x - mean)^2 statistics in f32: the one-pass
        # E[x^2]-E[x]^2 form catastrophically cancels for large-mean/
        # small-variance channels (measured: mean 1e3, std 1e-2 gives
        # var 0.0), corrupting inv AND the moving stats
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red_axes)
        var = jnp.var(xf, axis=red_axes)
        new_mm = moving_mean * momentum + mean.astype(moving_mean.dtype) * (1 - momentum)
        new_mv = moving_var * momentum + var.astype(moving_var.dtype) * (1 - momentum)
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    # normalization folded to one per-channel affine (a, b). The
    # elementwise pass computes in f32 and casts the result back: XLA
    # fuses the converts, so a bf16 input still costs one bf16 read +
    # one bf16 write of HBM while the a*x+b arithmetic (which cancels
    # ~|mean|-sized terms) happens at f32 in registers.
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    a = gamma.astype(jnp.float32) * inv
    b = beta.astype(jnp.float32) - mean.astype(jnp.float32) * a
    out = (x.astype(jnp.float32) * a.reshape(bshape)
           + b.reshape(bshape)).astype(x.dtype)
    return out, new_mm, new_mv


@register("LayerNorm", input_names=("data", "gamma", "beta"))
def _layer_norm(attrs, x, gamma, beta):
    axis = int(attrs.get("axis", -1))
    eps = float(attrs.get("eps", 1e-5))
    from .pallas_norm import plain_layer_norm
    if axis in (-1, x.ndim - 1) and gamma.ndim == 1:
        # the kernels subsystem owns the choice when opted in
        # (MXNET_KERNELS=reference|tuned); off returns None and the
        # legacy per-op gate below keeps its seed-era behavior
        from .. import kernels as _kernels
        kb = _kernels.get("layernorm", x.shape, x.dtype)
        if kb is not None:
            return kb(x, gamma, beta, eps)
        # trailing-axis LN takes the fused Pallas kernel (one HBM
        # read+write per element; pallas_norm.py) — the hot
        # transformer configuration
        from ._pallas_rows import kernel_wanted
        if kernel_wanted("MXNET_FUSED_LAYERNORM", x.shape[-1], x.dtype):
            from .pallas_norm import fused_layer_norm
            return fused_layer_norm(x, gamma, beta, eps=eps)
    return plain_layer_norm(x, gamma, beta, eps=eps, axis=axis)


@register("GroupNorm", input_names=("data", "gamma", "beta"))
def _group_norm(attrs, x, gamma, beta):
    ng = int(attrs.get("num_groups", 1))
    eps = float(attrs.get("eps", 1e-5))
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, ng, c // ng) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("InstanceNorm", input_names=("data", "gamma", "beta"))
def _instance_norm(attrs, x, gamma, beta):
    eps = float(attrs.get("eps", 1e-3))
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def _l2_normalization(attrs, x):
    eps = float(attrs.get("eps", 1e-10))
    mode = attrs.get("mode", "instance")
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, x.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / norm


@register("LRN")
def _lrn(attrs, x):
    nsize = int(attrs.get("nsize", 5))
    alpha = float(attrs.get("alpha", 1e-4))
    beta = float(attrs.get("beta", 0.75))
    knorm = float(attrs.get("knorm", 2.0))
    sq = jnp.square(x)
    pad = nsize // 2
    sq_pad = jnp.pad(sq, ((0, 0), (pad, pad)) + ((0, 0),) * (x.ndim - 2))
    acc = sum(sq_pad[:, i:i + x.shape[1]] for i in range(nsize))
    return x / jnp.power(knorm + alpha / nsize * acc, beta)


# --- activations ------------------------------------------------------------
@register("Activation")
def _activation(attrs, x):
    act = attrs["act_type"]
    return {
        "relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus, "softsign": jax.nn.soft_sign,
        "log_sigmoid": jax.nn.log_sigmoid, "silu": jax.nn.silu,
    }[act](x)


@register("LeakyReLU")
def _leaky_relu(attrs, x, *maybe_gamma):
    act = attrs.get("act_type", "leaky")
    slope = float(attrs.get("slope", 0.25))
    if act == "leaky":
        return jnp.where(x > 0, x, slope * x)
    if act == "prelu":
        gamma = maybe_gamma[0]
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if gamma.ndim == 1 and x.ndim > 1 else gamma
        return jnp.where(x > 0, x, g * x)
    if act == "elu":
        return jnp.where(x > 0, x, slope * jnp.expm1(x))
    if act == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))
    if act == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act == "rrelu":  # eval-mode deterministic (mean slope)
        lower, upper = float(attrs.get("lower_bound", 0.125)), float(attrs.get("upper_bound", 0.334))
        return jnp.where(x > 0, x, (lower + upper) / 2 * x)
    raise ValueError(act)


# --- softmax family (reference: nn/softmax-inl.h) ---------------------------
@register("softmax")
def _softmax(attrs, x, *maybe_length):
    axis = int(attrs.get("axis", -1))
    temp = attrs.get("temperature", None)
    if temp:
        x = x / float(temp)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def _log_softmax(attrs, x):
    axis = int(attrs.get("axis", -1))
    temp = attrs.get("temperature", None)
    if temp:
        x = x / float(temp)
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def _softmin(attrs, x):
    return jax.nn.softmax(-x, axis=int(attrs.get("axis", -1)))


def _softmax_output_grad(attrs, primals, cotangents):
    """Custom gradient matching reference softmax_output-inl.h: grad wrt data
    is (softmax - one_hot(label)) * grad_scale, label gets no grad."""
    data, label = primals
    grad_scale = float(attrs.get("grad_scale", 1.0))
    prob = jax.nn.softmax(data, axis=-1)
    if bool(attrs.get("multi_output", False)):
        oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[1], axis=1)
    else:
        oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1])
    ignore = attrs.get("ignore_label", None)
    g = (prob - oh) * grad_scale
    if ignore is not None and bool(attrs.get("use_ignore", False)):
        mask = (label != float(ignore)).astype(data.dtype)
        g = g * mask[..., None]
    norm = attrs.get("normalization", "null")
    if norm == "batch":
        g = g / data.shape[0]
    elif norm == "valid" and ignore is not None:
        g = g / jnp.maximum((label != float(ignore)).sum(), 1)
    return (g * cotangents[0].sum() if cotangents[0].ndim == 0 else g, None)


@register("SoftmaxOutput", fgradient=_softmax_output_grad, alias=("Softmax",),
          input_names=("data", "label"))
def _softmax_output(attrs, data, label):
    return jax.nn.softmax(data, axis=-1)


# --- regression outputs (reference: src/operator/regression_output.cc) ------
def _regression_grad(link, err_fn):
    def grad(attrs, primals, cotangents):
        data, label = primals
        grad_scale = float(attrs.get("grad_scale", 1.0))
        pred = link(data)
        g = err_fn(pred, label.reshape(pred.shape))
        # reference scales by grad_scale / num_output, where num_output is the
        # per-sample output width label.Size()/label.shape_[0]
        # (regression_output-inl.h:200-206) — NOT by batch size.
        num_output = 1
        for d in label.shape[1:]:
            num_output *= d
        g = g * (grad_scale / max(num_output, 1))
        ct = cotangents[0]
        return (g * (ct.sum() if ct.ndim == 0 else 1.0), None)
    return grad


@register("LinearRegressionOutput", input_names=("data", "label"),
          fgradient=_regression_grad(lambda x: x, lambda p, l: p - l))
def _linear_regression_output(attrs, data, label):
    return data


@register("MAERegressionOutput", input_names=("data", "label"),
          fgradient=_regression_grad(lambda x: x,
                                     lambda p, l: jnp.sign(p - l)))
def _mae_regression_output(attrs, data, label):
    return data


@register("LogisticRegressionOutput", input_names=("data", "label"),
          fgradient=_regression_grad(jax.nn.sigmoid, lambda p, l: p - l))
def _logistic_regression_output(attrs, data, label):
    return jax.nn.sigmoid(data)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(attrs, data, label):
    """Total softmax CE over the batch (reference loss_binary_op.cc:30).
    The kernels subsystem (MXNET_KERNELS=reference|tuned) owns the
    implementation when opted in; otherwise the legacy fused Pallas row
    kernel (pallas_softmax_ce.py, gated by MXNET_FUSED_SOFTMAX_CE) —
    one HBM pass over the logits either way."""
    from .pallas_softmax_ce import fused_softmax_ce
    if data.ndim == 2 and data.shape[0] > 0:
        from .. import kernels as _kernels
        kb = _kernels.get("softmax_ce", data.shape, data.dtype)
        if kb is not None:
            return jnp.sum(kb(data, label))
    return jnp.sum(fused_softmax_ce(data, label))


@register("CTCLoss", alias=("ctc_loss",))
def _ctc_loss(attrs, data, label, *lengths):
    """CTC via log-semiring dynamic program under lax.scan (reference uses
    warp-ctc / cudnn CTC, src/operator/nn/ctc_loss.cc)."""
    # data: (T, N, C) alphabet incl. blank at index 0 (MXNet convention)
    T, N, C = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    L = label.shape[1]
    blank = 0
    lab = label.astype(jnp.int32)
    # extended label sequence: blank l1 blank l2 ... blank, length 2L+1
    ext = jnp.full((N, 2 * L + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    neg_inf = jnp.asarray(-1e30, dtype=data.dtype)
    alpha0 = jnp.full((N, 2 * L + 1), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(logp[0], lab[:, :1], axis=-1)[:, 0])

    same_as_prev2 = jnp.concatenate(
        [jnp.ones((N, 2), dtype=bool),
         ext[:, 2:] == ext[:, :-2]], axis=1)

    if lengths and len(lengths) >= 1 and lengths[0] is not None:
        data_len = lengths[0].astype(jnp.int32)
    else:
        data_len = jnp.full((N,), T, dtype=jnp.int32)

    def step(alpha, inp):
        logp_t, t = inp
        a = alpha
        a1 = jnp.concatenate([jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
        a2 = jnp.concatenate([jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
        a2 = jnp.where(same_as_prev2, neg_inf, a2)
        m = jnp.maximum(jnp.maximum(a, a1), a2)
        s = m + jnp.log(jnp.exp(a - m) + jnp.exp(a1 - m) + jnp.exp(a2 - m) + 1e-30)
        emit = jnp.take_along_axis(logp_t, ext, axis=-1)
        # padded timesteps (t >= data_len) leave alpha untouched
        active = (t < data_len)[:, None]
        return jnp.where(active, s + emit, alpha), None

    alpha, _ = lax.scan(step, alpha0, (logp[1:], jnp.arange(1, T)))
    if lengths and len(lengths) >= 2:
        lab_len = lengths[1].astype(jnp.int32)
    else:
        lab_len = jnp.full((N,), L, dtype=jnp.int32)
    endp = 2 * lab_len - 1
    last = jnp.take_along_axis(alpha, endp[:, None], axis=1)[:, 0]
    last_b = jnp.take_along_axis(alpha, (2 * lab_len)[:, None], axis=1)[:, 0]
    m = jnp.maximum(last, last_b)
    ll = m + jnp.log(jnp.exp(last - m) + jnp.exp(last_b - m))
    return -ll


# --- sequence ops (reference: sequence_{mask,last,reverse}.cc) --------------
@register("SequenceMask")
def _sequence_mask(attrs, data, *maybe_len):
    if not bool(attrs.get("use_sequence_length", False)) or not maybe_len:
        return data
    value = float(attrs.get("value", 0.0))
    axis = int(attrs.get("axis", 0))  # time axis
    slen = maybe_len[0].astype(jnp.int32)
    T = data.shape[axis]
    pos = jnp.arange(T)
    if axis == 0:
        mask = pos[:, None] < slen[None, :]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = pos[None, :] < slen[:, None]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


@register("SequenceLast")
def _sequence_last(attrs, data, *maybe_len):
    axis = int(attrs.get("axis", 0))
    if bool(attrs.get("use_sequence_length", False)) and maybe_len:
        idx = maybe_len[0].astype(jnp.int32) - 1
        if axis == 0:
            return jnp.take_along_axis(
                data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0)[0]
        return jnp.take_along_axis(
            data, idx.reshape((-1, 1) + (1,) * (data.ndim - 2)), axis=1)[:, 0]
    return jnp.take(data, data.shape[axis] - 1, axis=axis)


@register("SequenceReverse")
def _sequence_reverse(attrs, data, *maybe_len):
    if bool(attrs.get("use_sequence_length", False)) and maybe_len:
        slen = maybe_len[0].astype(jnp.int32)
        T = data.shape[0]
        pos = jnp.arange(T)[:, None]
        rev = jnp.where(pos < slen[None, :], slen[None, :] - 1 - pos, pos)
        return jnp.take_along_axis(
            data, rev.reshape(rev.shape + (1,) * (data.ndim - 2)), axis=0)
    return jnp.flip(data, axis=0)


# --- Dropout (reference: nn/dropout-inl.h) ----------------------------------
@register("Dropout", is_random=True)
def _dropout(attrs, key, x):
    p = float(attrs.get("p", 0.5))
    training = bool(attrs.get("_training", False))
    mode = attrs.get("mode", "training")
    if (not training and mode != "always") or p <= 0.0:
        return x
    axes = tuple(attrs.get("axes", ()) or ())
    shape = tuple(1 if i in axes else s for i, s in enumerate(x.shape)) if axes else x.shape
    keep = jax.random.bernoulli(key, 1.0 - p, shape)
    return jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))


# --- fused RNN (reference: rnn-inl.h RNNOp — cuDNN descr. on GPU) -----------
def _rnn_cell_step(mode, W_ih, W_hh, b_ih, b_hh):
    def lstm(carry, x_t):
        h, c = carry
        gates = x_t @ W_ih.T + h @ W_hh.T + b_ih + b_hh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c2 = f * c + i * g
        h2 = o * jnp.tanh(c2)
        return (h2, c2), h2

    def gru(carry, x_t):
        (h,) = carry
        gi = x_t @ W_ih.T + b_ih
        gh = h @ W_hh.T + b_hh
        ir, iz, inew = jnp.split(gi, 3, axis=-1)
        hr, hz, hnew = jnp.split(gh, 3, axis=-1)
        r = jax.nn.sigmoid(ir + hr)
        z = jax.nn.sigmoid(iz + hz)
        n = jnp.tanh(inew + r * hnew)
        h2 = (1 - z) * n + z * h
        return (h2,), h2

    def rnn_tanh(carry, x_t):
        (h,) = carry
        h2 = jnp.tanh(x_t @ W_ih.T + h @ W_hh.T + b_ih + b_hh)
        return (h2,), h2

    def rnn_relu(carry, x_t):
        (h,) = carry
        h2 = jax.nn.relu(x_t @ W_ih.T + h @ W_hh.T + b_ih + b_hh)
        return (h2,), h2

    return {"lstm": lstm, "gru": gru, "rnn_tanh": rnn_tanh,
            "rnn_relu": rnn_relu}[mode]


def _rnn_gate_count(mode):
    return {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}[mode]


def rnn_unpack_params(params, mode, num_layers, input_size, hidden, bidirectional):
    """Slice the flat cuDNN-style parameter vector into per-layer weights.

    Layout matches reference rnn-inl.h (cuDNN canonical): all W_ih,W_hh per
    layer/direction first, then all b_ih,b_hh.
    """
    ng = _rnn_gate_count(mode)
    dirs = 2 if bidirectional else 1
    offset = 0
    weights, biases = [], []
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else hidden * dirs
        for _ in range(dirs):
            wih = params[offset:offset + ng * hidden * in_sz].reshape(ng * hidden, in_sz)
            offset += ng * hidden * in_sz
            whh = params[offset:offset + ng * hidden * hidden].reshape(ng * hidden, hidden)
            offset += ng * hidden * hidden
            weights.append((wih, whh))
    for layer in range(num_layers):
        for _ in range(dirs):
            bih = params[offset:offset + ng * hidden]
            offset += ng * hidden
            bhh = params[offset:offset + ng * hidden]
            offset += ng * hidden
            biases.append((bih, bhh))
    return weights, biases


def rnn_param_size(mode, num_layers, input_size, hidden, bidirectional):
    ng = _rnn_gate_count(mode)
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else hidden * dirs
        size += dirs * (ng * hidden * in_sz + ng * hidden * hidden + 2 * ng * hidden)
    return size


@register("RNN", num_outputs="_dynamic")
def _rnn(attrs, data, params, state, *maybe_state_cell):
    """Fused multi-layer (bi)RNN. data: (T, N, I) [seq-major like cuDNN]."""
    mode = attrs["mode"]
    hidden = int(attrs["state_size"])
    num_layers = int(attrs["num_layers"])
    bidir = bool(attrs.get("bidirectional", False))
    dirs = 2 if bidir else 1
    T, N, I = data.shape
    weights, biases = rnn_unpack_params(params, mode, num_layers, I, hidden, bidir)
    is_lstm = mode == "lstm"
    cell = maybe_state_cell[0] if is_lstm and maybe_state_cell else None

    x = data
    out_h, out_c = [], []
    for layer in range(num_layers):
        layer_outs = []
        for d in range(dirs):
            li = layer * dirs + d
            W_ih, W_hh = weights[li]
            b_ih, b_hh = biases[li]
            step = _rnn_cell_step(mode, W_ih, W_hh, b_ih, b_hh)
            h0 = state[li]
            carry0 = (h0, cell[li]) if is_lstm else (h0,)
            seq = jnp.flip(x, axis=0) if d == 1 else x
            carry, ys = lax.scan(step, carry0, seq)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            layer_outs.append(ys)
            out_h.append(carry[0])
            if is_lstm:
                out_c.append(carry[1])
        x = jnp.concatenate(layer_outs, axis=-1) if dirs == 2 else layer_outs[0]
        pdrop = float(attrs.get("p", 0.0))
        del pdrop  # inter-layer dropout handled at the gluon layer
    hN = jnp.stack(out_h, axis=0)
    if not bool(attrs.get("state_outputs", False)):
        return x
    if is_lstm:
        return x, hN, jnp.stack(out_c, axis=0)
    return x, hN


# --- SVMOutput (reference: src/operator/svm_output.cc) ----------------------
def _svm_output_grad(attrs, primals, cotangents):
    data, label = primals
    margin = float(attrs.get("margin", 1.0))
    reg = float(attrs.get("regularization_coefficient", 1.0))
    use_linear = bool(attrs.get("use_linear", False))
    out = data  # forward is identity
    k = jax.nn.one_hot(label.reshape(-1).astype(jnp.int32),
                       data.shape[-1], dtype=jnp.bool_)
    if use_linear:
        # L1-SVM (svm_output.cc L1_SVM): hinge subgradient
        g_true = -(margin > out).astype(data.dtype) * reg
        g_other = (margin > -out).astype(data.dtype) * reg
    else:
        # L2-SVM (svm_output.cc L2_SVM): squared hinge
        g_true = jnp.where(margin > out, -2 * reg * (margin - out), 0.0)
        g_other = jnp.where(margin > -out, 2 * reg * (margin + out), 0.0)
    g = jnp.where(k, g_true, g_other).astype(data.dtype)
    ct = cotangents[0]
    return (g * (ct.sum() if ct.ndim == 0 else 1.0), None)


@register("SVMOutput", fgradient=_svm_output_grad)
def _svm_output(attrs, data, label):
    return data


# --- SoftmaxActivation (reference: src/operator/softmax_activation.cc) ------
@register("SoftmaxActivation")
def _softmax_activation(attrs, x):
    mode = attrs.get("mode", "instance")
    if mode == "channel":
        return jax.nn.softmax(x, axis=1)
    flat = x.reshape(x.shape[0], -1)
    return jax.nn.softmax(flat, axis=-1).reshape(x.shape)
