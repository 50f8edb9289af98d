"""The KDA kernels (``ops.pallas_kda``: the chunked gated delta rule walked
chunk by chunk on a grid axis, the state in VMEM scratch) under the Pallas
interpreter on the CPU, against the plain chunked form
``_op_linear_attention.kda_scan`` and the step-by-step recurrence: the
output and the gradient of every input, chunks of 16 and 64, a length that
is no multiple of the chunk, one and three heads, β up to 2, decays of
e^-300 a step; and which implementation the op ``_contrib_kda_scan`` put
in a program, by the counter ``mxnet_kda_scan_lowered_total``."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import nd, telemetry
from mxnet_tpu.ops import pallas_kda
from mxnet_tpu.ops._op_linear_attention import SUB, kda_scan

from test_solar_open2 import _recurrence

SCAN_INPUTS = ("q", "k", "v", "g", "beta")
# (chunk, positions, heads): tails of 5 and 42 steps, sub-blocks of 16
CASES = [(16, 37, 1), (16, 37, 3), (64, 150, 1), (64, 150, 3)]
CASE_IDS = [f"chunk{c}-T{t}-h{h}" for c, t, h in CASES]


def _inputs(t, heads, g_value=None, dk=128, dv=128):
    """Two sequences of unit keys, β up to its bound of 2, decays from none
    to e^-20 a step (or ``g_value`` everywhere)."""
    rng = np.random.default_rng(t * 10 + heads)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    k = f(2, t, heads, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2.0 / (1.0 + np.exp(-4.0 * f(2, t, heads)))
    beta[:, ::5] = 2.0
    g = -np.exp(1.5 * f(2, t, heads, dk))
    g[:, 3::7] = -20.0
    g[:, 4::7] = 0.0
    if g_value is not None:
        g = np.full_like(g, g_value)
    return (f(2, t, heads, dk) / np.sqrt(dk), k, f(2, t, heads, dv),
            g.astype(np.float32), beta.astype(np.float32))


def _kernels(chunk):
    return lambda *a: pallas_kda.kda_scan(*a, chunk, SUB)


@functools.lru_cache(maxsize=None)
def _outputs_and_grads(case):
    """The output and the five gradients of a weighted sum, by the kernels
    and by the plain form (float32 products), for one case."""
    chunk, t, heads = case
    args = _inputs(t, heads)
    weight = np.random.default_rng(1).standard_normal(
        args[2].shape).astype(np.float32)

    def both(fn):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(jnp.asarray(weight))

    with jax.default_matmul_precision("highest"):
        want = both(lambda *a: kda_scan(*a, chunk))
    return args, both(_kernels(chunk)), want


@pytest.mark.parametrize("case", CASES, ids=CASE_IDS)
def test_kernels_match_the_plain_form_and_the_recurrence(case):
    args, got, want = _outputs_and_grads(case)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[0], _recurrence(*args), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("wrt", range(5), ids=SCAN_INPUTS)
@pytest.mark.parametrize("case", CASES, ids=CASE_IDS)
def test_kernel_gradients_match_the_plain_form(case, wrt):
    _, got, want = _outputs_and_grads(case)
    got, want = got[1 + wrt], want[1 + wrt]
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=2e-5 * float(np.abs(want).max()))


@pytest.mark.parametrize("wrt", range(5), ids=SCAN_INPUTS)
def test_kernel_gradients_match_the_recurrence(wrt):
    chunk, t, heads = CASES[1]
    args = _inputs(t, heads)
    weight = np.random.default_rng(1).standard_normal(
        args[2].shape).astype(np.float32)
    got = jax.grad(lambda *a: (_kernels(chunk)(*a) * weight).sum(),
                   argnums=wrt)(*args)
    want = jax.grad(lambda *a: (_recurrence(*a) * weight).sum(),
                    argnums=wrt)(*args)
    np.testing.assert_allclose(got, want, rtol=5e-4,
                               atol=5e-5 * float(np.abs(want).max()))


@pytest.mark.parametrize("chunk", [16, 64])
def test_kernels_exponentiate_nothing_positive(chunk):
    """Decays of e^-300 a step: the masked differences stay finite in the
    kernels too, forward and backward."""
    q, k, v, g, beta = _inputs(37, 3, g_value=-300.0)
    got = _kernels(chunk)(q, k, v, g, beta)
    np.testing.assert_allclose(got, _recurrence(q, k, v, g, beta),
                               rtol=1e-4, atol=1e-5)
    grads = jax.grad(lambda *a: _kernels(chunk)(*a).sum(),
                     argnums=range(5))(q, k, v, g, beta)
    assert all(np.isfinite(x).all() for x in grads)


def test_kernels_keep_the_input_dtype():
    q, k, v, g, beta = (jnp.asarray(a, jnp.bfloat16)
                        for a in _inputs(37, 1))
    out = _kernels(16)(q, k, v, g, beta)
    assert out.dtype == jnp.bfloat16 and out.shape == v.shape
    grads = jax.grad(lambda *a: _kernels(16)(*a).astype(jnp.float32).sum(),
                     argnums=range(5))(q, k, v, g, beta)
    assert all(x.dtype == jnp.bfloat16 for x in grads)


def _lowered(impl):
    return telemetry.REGISTRY.get("mxnet_kda_scan_lowered_total").value(
        {"impl": impl})


@pytest.mark.parametrize("dk,dv,chunk,impl", [
    (128, 128, 64, "pallas"),     # the token cells' heads and chunk
    (128, 128, 16, "pallas"),
    (8, 6, 16, "xla"),            # heads narrower than a lane tile
    (128, 128, 8, "xla"),         # a chunk that is not whole sub-blocks
])
def test_the_op_counts_the_implementation_it_traced(dk, dv, chunk, impl):
    args = _inputs(37, 1, dk=dk, dv=dv)
    before = {i: _lowered(i) for i in ("pallas", "xla")}
    got = nd.contrib.kda_scan(*map(nd.array, args), chunk_size=chunk)
    assert _lowered(impl) == before[impl] + 1
    other = "xla" if impl == "pallas" else "pallas"
    assert _lowered(other) == before[other]
    with jax.default_matmul_precision("highest"):
        want = kda_scan(*args, chunk)
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)


def test_the_shape_rule():
    assert pallas_kda.kernel_takes((1, 8192, 32, 128), (1, 8192, 32, 128),
                                   64, SUB)
    assert pallas_kda.kernel_takes((1, 8192, 8, 256), (1, 8192, 8, 128),
                                   32, SUB)
    for q, v, chunk in (((1, 64, 2, 8), (1, 64, 2, 8), 64),
                        ((1, 64, 2, 128), (1, 64, 2, 96), 64),
                        ((1, 64, 2, 512), (1, 64, 2, 128), 64),
                        ((1, 64, 2, 128), (1, 64, 2, 128), 24),
                        ((1, 256, 2, 128), (1, 256, 2, 128), 128)):
        assert not pallas_kda.kernel_takes(q, v, chunk, SUB)


def test_the_kernels_under_a_rematerialisation_boundary():
    """A boundary with no policy (a bare ``jax.checkpoint``) recomputes
    the forward kernel in its backward: the gradients are those of the call
    without one.  A layer's boundary keeps the kernel's outputs by name
    instead (``tests/test_remat_residuals.py``)."""
    case = CASES[1]
    args, got, _ = _outputs_and_grads(case)
    weight = np.random.default_rng(1).standard_normal(
        args[2].shape).astype(np.float32)
    scan = jax.checkpoint(_kernels(case[0]))
    grads = jax.grad(lambda *a: (scan(*a) * weight).sum(),
                     argnums=range(5))(*args)
    for g, want in zip(grads, got[1:]):
        np.testing.assert_allclose(g, want, rtol=1e-6, atol=1e-6)
