"""Testing utilities.

Parity with reference python/mxnet/test_utils.py: numpy-as-oracle forward
checks, central numeric-gradient checker for backward, tolerance helper, and
a check_consistency-style cross-dtype harness (SURVEY.md §4 key takeaway).
"""
from __future__ import annotations

import numpy as np

from . import autograd
from . import ndarray as nd
from .context import cpu, current_context


def default_context():
    return current_context()


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-7, names=("a", "b")):
    a = a.asnumpy() if isinstance(a, nd.NDArray) else np.asarray(a)
    b = b.asnumpy() if isinstance(b, nd.NDArray) else np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                               err_msg=f"{names[0]} vs {names[1]}")


def same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def rand_ndarray(shape, stype="default", density=None, dtype=None, ctx=None):
    dtype = dtype or np.float32
    dense = np.random.uniform(-1, 1, size=shape).astype(dtype)
    if stype == "default":
        return nd.array(dense, ctx=ctx)
    if density is not None:
        mask = np.random.uniform(0, 1, size=shape) < density
        dense = dense * mask
    from .ndarray import sparse
    return sparse.array(dense, stype=stype, ctx=ctx, dtype=dtype)


def numeric_grad(f, inputs, eps=1e-4):
    """Central-difference numeric gradient of scalar-valued f(list[np]) -> float."""
    grads = []
    for i, x in enumerate(inputs):
        g = np.zeros_like(x, dtype=np.float64)
        flat = x.reshape(-1)
        gf = g.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + eps
            fp = f(inputs)
            flat[j] = orig - eps
            fm = f(inputs)
            flat[j] = orig
            gf[j] = (fp - fm) / (2 * eps)
        grads.append(g)
    return grads


def check_numeric_gradient(op_fn, input_arrays, rtol=1e-2, atol=1e-3, eps=1e-3):
    """Compare autograd backward of sum(op_fn(*inputs)) against numeric grads.

    Parity: check_numeric_gradient (reference test_utils.py:860), but the
    oracle loop runs the same jitted op on float64-upcast host values.
    """
    np_inputs = [np.asarray(a, dtype=np.float64) for a in input_arrays]

    def scalar_f(nps):
        args = [nd.array(x.astype(np.float32)) for x in nps]
        out = op_fn(*args)
        return float(out.sum().asscalar())

    expected = numeric_grad(scalar_f, [x.copy() for x in np_inputs], eps=eps)

    args = [nd.array(x.astype(np.float32)) for x in np_inputs]
    for a in args:
        a.attach_grad()
    with autograd.record():
        out = op_fn(*args)
        s = out.sum()
    s.backward()
    for a, e in zip(args, expected):
        assert_almost_equal(a.grad, e.astype(np.float32), rtol=rtol, atol=atol)


def consistency_devices():
    """The jax devices check_consistency crosses: the host CPU always,
    plus the TPU chip when its backend is initialized and reachable
    (skipped cleanly otherwise — the reference pattern is
    tests/python/gpu/test_operator_gpu.py rerunning the CPU suite on
    GPU; here one harness crosses backends in-process)."""
    import jax
    devs = []
    try:
        devs.append(jax.devices("cpu")[0])
    except RuntimeError:
        pass
    try:
        devs.append(jax.devices("tpu")[0])
    except RuntimeError:
        pass  # no tpu backend: cpu-only run
    return devs


def rand_shape_2d(dim0=10, dim1=10):
    """Random 2-D shape (parity: test_utils.py rand_shape_2d)."""
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1),
            np.random.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=num_dim))


def almost_equal(a, b, rtol=1e-5, atol=1e-7):
    a = a.asnumpy() if isinstance(a, nd.NDArray) else np.asarray(a)
    b = b.asnumpy() if isinstance(b, nd.NDArray) else np.asarray(b)
    return np.allclose(a, b, rtol=rtol, atol=atol)


def almost_equal_ignore_nan(a, b, rtol=1e-5, atol=1e-7):
    """Equality where positions that are NaN in BOTH arrays match
    (parity: test_utils.py almost_equal_ignore_nan)."""
    a = a.asnumpy() if isinstance(a, nd.NDArray) else np.asarray(a)
    b = b.asnumpy() if isinstance(b, nd.NDArray) else np.asarray(b)
    nan_mask = np.isnan(a)
    if not np.array_equal(nan_mask, np.isnan(b)):
        return False
    return np.allclose(a[~nan_mask], b[~nan_mask], rtol=rtol, atol=atol)


def assert_exception(f, exception_type, *args, **kwargs):
    """f(*args, **kwargs) must raise exception_type (parity:
    test_utils.py assert_exception)."""
    try:
        f(*args, **kwargs)
    except exception_type:
        return
    raise AssertionError(
        f"{f} did not raise {exception_type.__name__}")


def check_symbolic_forward(sym, inputs, expected, rtol=1e-4, atol=1e-5,
                           aux_states=None, ctx=None):
    """Bind a symbol with the given input arrays and compare every output
    (parity: test_utils.py check_symbolic_forward — the workhorse of the
    reference's test_operator.py)."""
    from .context import cpu as _cpu
    ctx = ctx or _cpu()
    arg_names = sym.list_arguments()
    args = {n: nd.array(np.asarray(x, np.float32))
            for n, x in zip(arg_names, inputs)}
    aux = None
    if aux_states is not None:
        aux = {n: nd.array(np.asarray(x, np.float32))
               for n, x in zip(sym.list_auxiliary_states(), aux_states)}
    ex = sym.bind(ctx, args, aux_states=aux)
    outs = ex.forward()
    expected = expected if isinstance(expected, (list, tuple)) else [expected]
    for o, w in zip(outs, expected):
        np.testing.assert_allclose(o.asnumpy().astype(np.float64),
                                   np.asarray(w, np.float64),
                                   rtol=rtol, atol=atol)
    return [o.asnumpy() for o in outs]


def check_symbolic_backward(sym, inputs, out_grads, expected_grads,
                            rtol=1e-4, atol=1e-5, ctx=None):
    """Bind, forward, backward with given head gradients, compare arg
    grads in list_arguments order (parity: test_utils.py
    check_symbolic_backward)."""
    from .context import cpu as _cpu
    ctx = ctx or _cpu()
    arg_names = sym.list_arguments()
    args = {n: nd.array(np.asarray(x, np.float32))
            for n, x in zip(arg_names, inputs)}
    grads = {n: nd.zeros(a.shape, dtype=a.dtype)
             for n, a in args.items()}
    ex = sym.bind(ctx, args, args_grad=grads, grad_req="write")
    ex.forward(is_train=True)
    ograds = [nd.array(np.asarray(g, np.float32))
              for g in (out_grads if isinstance(out_grads, (list, tuple))
                        else [out_grads])]
    ex.backward(ograds if len(ograds) > 1 else ograds[0])
    expected = expected_grads if isinstance(expected_grads, (list, tuple)) \
        else [expected_grads]
    got = []
    for n, w in zip(arg_names, expected):
        if w is None:
            continue
        g = ex.grad_dict[n]
        np.testing.assert_allclose(g.asnumpy().astype(np.float64),
                                   np.asarray(w, np.float64),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"grad mismatch for {n}")
        got.append(g.asnumpy())
    return got


def get_mnist_like(num_train=3000, num_val=500, translate=False, seed=7):
    """Synthetic MNIST-shaped classification data for convergence gates.

    Zero-egress stand-in for test_utils.get_mnist() (reference
    test_utils.py:1565, which downloads the real files). Two flavors:

    * ``translate=False``: each class is a fixed random 28x28 prototype
      plus gaussian noise — linearly separable, the MLP gate.
    * ``translate=True``: each class is a fixed 10x10 patch stamped at a
      random position on an empty 28x28 canvas plus noise — translation
      invariance is required, so convolution+pooling genuinely matters
      (a same-budget MLP plateaus well below the conv gate's threshold).

    Returns dict(train_data, train_label, test_data, test_label) with
    data shaped (N, 1, 28, 28) float32 in [0, 1], matching get_mnist().
    """
    rng = np.random.RandomState(seed)
    n = num_train + num_val
    y = rng.randint(0, 10, n)
    if not translate:
        protos = rng.rand(10, 1, 28, 28).astype(np.float32)
        x = protos[y] + rng.randn(n, 1, 28, 28).astype(np.float32) * 0.35
    else:
        patches = (rng.rand(10, 10, 10) > 0.5).astype(np.float32)
        x = rng.rand(n, 1, 28, 28).astype(np.float32) * 0.15
        rows = rng.randint(0, 28 - 10, n)
        cols = rng.randint(0, 28 - 10, n)
        for i in range(n):
            x[i, 0, rows[i]:rows[i] + 10, cols[i]:cols[i] + 10] += \
                patches[y[i]] * 0.85
    x = np.clip(x, 0.0, 1.0)
    y = y.astype(np.float32)
    return {"train_data": x[:num_train], "train_label": y[:num_train],
            "test_data": x[num_train:], "test_label": y[num_train:]}


def check_consistency(op_fn, input_shapes, dtypes=(np.float32, np.float16),
                      rtol=None, atol=None, devices=None):
    """Run the same op across devices × dtypes and cross-check every leg
    against the (cpu, dtypes[0]) reference (parity: check_consistency
    test_utils.py:1283, which ran [cpu, gpu] × [fp16, fp32, fp64])."""
    import jax
    devices = devices if devices is not None else consistency_devices()
    base_inputs = [np.random.uniform(-1, 1, size=s) for s in input_shapes]
    tol = {np.dtype(np.float16): 1e-2, np.dtype(np.float32): 1e-5}
    try:
        import ml_dtypes
        tol[np.dtype(ml_dtypes.bfloat16)] = 2e-2
    except ImportError:
        pass
    ref = None
    for dev in devices:
        for dt in dtypes:
            args = []
            for x in base_inputs:
                arr = jax.device_put(x.astype(dt), dev)
                args.append(nd.NDArray(arr, current_context()))
            out = op_fn(*args).asnumpy().astype(np.float64)
            if ref is None:
                ref = out    # (devices[0], dtypes[0]) is the oracle leg
                continue
            t = tol.get(np.dtype(dt), 1e-2)
            if dev is not devices[0]:
                # cross-DEVICE legs compare at accelerator matmul
                # precision (TPU f32 dots default to bf16-ish internals)
                t = max(t, 2e-3)
            np.testing.assert_allclose(
                ref, out, rtol=rtol or t, atol=atol or t,
                err_msg=f"inconsistent on {dev.platform}/{dt}")
    return ref
