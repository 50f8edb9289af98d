"""NDArray semantics (parity target: reference tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_creation():
    a = nd.array([[1, 2], [3, 4]])
    assert a.shape == (2, 2)
    assert a.dtype == np.float32
    assert nd.zeros((3, 4)).asnumpy().sum() == 0
    assert nd.ones((3, 4)).asnumpy().sum() == 12
    assert np.allclose(nd.full((2,), 7).asnumpy(), 7)
    assert nd.arange(0, 10, 2).shape == (5,)
    # int64 narrows to int32 by design: TPU-native integer width (the
    # reference's int64 large-array indexing is a CPU capability)
    b = nd.array(np.arange(6, dtype=np.int64).reshape(2, 3))
    assert b.dtype == np.int32


def test_arithmetic():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([[5.0, 6.0], [7.0, 8.0]])
    assert np.allclose((a + b).asnumpy(), [[6, 8], [10, 12]])
    assert np.allclose((b - a).asnumpy(), 4)
    assert np.allclose((a * 2).asnumpy(), [[2, 4], [6, 8]])
    assert np.allclose((2 * a).asnumpy(), (a * 2).asnumpy())
    assert np.allclose((1 / a).asnumpy(), 1 / a.asnumpy())
    assert np.allclose((a ** 2).asnumpy(), a.asnumpy() ** 2)
    assert np.allclose((a - 1).asnumpy(), a.asnumpy() - 1)
    assert np.allclose((10 - a).asnumpy(), 10 - a.asnumpy())
    assert np.allclose((-a).asnumpy(), -a.asnumpy())


def test_comparison():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([3.0, 2.0, 1.0])
    assert np.allclose((a == b).asnumpy(), [0, 1, 0])
    assert np.allclose((a > b).asnumpy(), [0, 0, 1])
    assert np.allclose((a <= 2).asnumpy(), [1, 1, 0])


def test_inplace():
    a = nd.ones((2, 2))
    v0 = a.version
    a += 1
    assert np.allclose(a.asnumpy(), 2)
    assert a.version > v0
    a *= 3
    assert np.allclose(a.asnumpy(), 6)
    a[:] = 0
    assert np.allclose(a.asnumpy(), 0)


def test_indexing():
    a = nd.array(np.arange(24).reshape(2, 3, 4))
    assert np.allclose(a[1].asnumpy(), np.arange(12, 24).reshape(3, 4))
    assert np.allclose(a[0, 1].asnumpy(), [4, 5, 6, 7])
    assert np.allclose(a[:, 1:3].asnumpy(), a.asnumpy()[:, 1:3])
    a[0] = 0
    assert a.asnumpy()[0].sum() == 0
    a[1, 2, 3] = 99
    assert a.asnumpy()[1, 2, 3] == 99


def test_view_writeback():
    a = nd.array(np.arange(12).reshape(3, 4).astype(np.float32))
    v = a[1]
    v[:] = 0.0
    assert a.asnumpy()[1].sum() == 0


def test_reshape_family():
    a = nd.array(np.arange(24).reshape(2, 3, 4))
    assert a.reshape((6, 4)).shape == (6, 4)
    assert a.reshape((-1, 4)).shape == (6, 4)
    assert a.reshape((0, -1)).shape == (2, 12)
    assert a.reshape((-3, 4)).shape == (6, 4)
    assert a.flatten().shape == (2, 12)
    assert a.expand_dims(0).shape == (1, 2, 3, 4)
    assert a.transpose().shape == (4, 3, 2)
    assert a.swapaxes(0, 2).shape == (4, 3, 2)
    assert nd.moveaxis(a, 0, 2).shape == (3, 4, 2)


def test_reductions():
    x = np.random.randn(3, 4, 5).astype(np.float32)
    a = nd.array(x)
    assert np.allclose(a.sum().asnumpy(), x.sum(), rtol=1e-5)
    assert np.allclose(a.mean(axis=1).asnumpy(), x.mean(axis=1), rtol=1e-5)
    assert np.allclose(a.max(axis=(0, 2)).asnumpy(), x.max(axis=(0, 2)))
    assert np.allclose(a.argmax(axis=1).asnumpy(), x.argmax(axis=1))
    assert np.allclose(a.norm().asnumpy(), np.linalg.norm(x.ravel()), rtol=1e-5)


def test_dtype_cast():
    a = nd.array([1.5, 2.5])
    b = a.astype(np.int32)
    assert b.dtype == np.int32
    c = a.astype("float16")
    assert c.dtype == np.float16
    d = a.astype("bfloat16")
    assert d.dtype.name == "bfloat16"


def test_context_placement():
    a = nd.array([1, 2, 3], ctx=mx.cpu())
    assert a.context == mx.cpu()
    b = a.as_in_context(mx.cpu())
    assert b is a
    c = a.copy()
    assert np.allclose(c.asnumpy(), a.asnumpy())


def test_full_slice_assignment_copies_across_contexts():
    """``x[:] = y`` copies INTO x's storage: y's buffer moves to x's
    device.  Module.set_params / init_params(arg_params=...) rely on it —
    found on the chip, where a cpu-bound module adopted tpu buffers and
    the next jit refused the mixed devices."""
    import jax
    x = nd.zeros((3,), ctx=mx.cpu(0))
    y = nd.array([1, 2, 3], ctx=mx.cpu(1))
    x[:] = y
    assert x._data.devices() == {jax.devices("cpu")[0]}
    assert y._data.devices() == {jax.devices("cpu")[1]}
    assert np.array_equal(x.asnumpy(), [1, 2, 3])
    # a module bound on one device takes parameters that live on another
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    mod = mx.mod.Module(net, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", (1, 3))], for_training=False)
    mod.set_params({"fc_weight": nd.ones((2, 3), ctx=mx.cpu(1)),
                    "fc_bias": nd.zeros((2,), ctx=mx.cpu(1))}, {})
    mod.forward(mx.io.DataBatch(data=[nd.ones((1, 3))]), is_train=False)
    assert np.array_equal(mod.get_outputs()[0].asnumpy(), [[3, 3]])


def test_concat_stack_split():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    c = nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)
    parts = nd.split(nd.array(np.arange(12).reshape(2, 6)), num_outputs=3, axis=1)
    assert len(parts) == 3 and parts[0].shape == (2, 2)


def test_save_load_roundtrip(tmp_path):
    fname = str(tmp_path / "x.params")
    data = {"w": nd.array(np.random.randn(3, 4).astype(np.float32)),
            "b": nd.array(np.random.randn(4).astype(np.float32))}
    nd.save(fname, data)
    loaded = nd.load(fname)
    assert set(loaded) == {"w", "b"}
    assert np.allclose(loaded["w"].asnumpy(), data["w"].asnumpy())
    lst = [nd.ones((2,)), nd.zeros((3,))]
    nd.save(fname, lst)
    back = nd.load(fname)
    assert isinstance(back, list) and len(back) == 2


def test_scalar_ops():
    a = nd.array([4.0])
    assert a.asscalar() == 4.0
    assert float(a) == 4.0
    assert int(a) == 4
    assert len(nd.zeros((5, 2))) == 5


def test_waitall_and_sync():
    a = nd.ones((10, 10))
    b = a * 2
    b.wait_to_read()
    mx.waitall()
    assert np.allclose(b.asnumpy(), 2)


def test_take_onehot_pick():
    a = nd.array(np.arange(12).reshape(3, 4).astype(np.float32))
    idx = nd.array([0, 2], dtype=np.int32)
    t = a.take(idx)
    assert np.allclose(t.asnumpy(), a.asnumpy()[[0, 2]])
    oh = nd.array([0, 1, 2], dtype=np.int32).one_hot(4)
    assert np.allclose(oh.asnumpy(), np.eye(4)[:3])
    p = a.pick(nd.array([1, 0, 3], dtype=np.int32), axis=1)
    assert np.allclose(p.asnumpy(), [1, 4, 11])


def test_topk_sort():
    x = np.random.randn(4, 6).astype(np.float32)
    a = nd.array(x)
    v = a.topk(k=2, ret_typ="value")
    assert np.allclose(v.asnumpy(), -np.sort(-x, axis=1)[:, :2])
    assert np.allclose(a.sort().asnumpy(), np.sort(x, axis=1))


def test_integer_index_bounds_and_iteration():
    """Out-of-range integer indexing must raise IndexError (jax would
    silently clamp), which is also what makes `for row in arr` and
    list(arr) terminate instead of looping forever."""
    import numpy as _np
    import pytest as _pytest
    a = nd.array(_np.arange(6, dtype=_np.float32).reshape(3, 2))
    with _pytest.raises(IndexError):
        a[3]
    with _pytest.raises(IndexError):
        a[-4]
    _np.testing.assert_allclose(a[-1].asnumpy(), [4.0, 5.0])
    rows = [r.asnumpy() for r in a]
    assert len(rows) == 3
    _np.testing.assert_allclose(_np.stack(rows), a.asnumpy())


def test_dlpack_interop_with_torch():
    """DLPack exchange (parity: reference ndarray.py to_dlpack_for_read /
    from_dlpack): zero-copy-capable handoff to and from torch."""
    import torch
    x = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    # NDArray -> torch via the protocol (torch consumes __dlpack__)
    t = torch.from_dlpack(x)
    np.testing.assert_array_equal(t.numpy(), x.asnumpy())
    # torch -> NDArray
    src = torch.arange(8, dtype=torch.float32).reshape(2, 4) * 0.5
    back = nd.from_dlpack(src)
    np.testing.assert_array_equal(back.asnumpy(), src.numpy())
    # explicit capsule forms
    cap = nd.to_dlpack_for_read(x)
    t2 = torch.utils.dlpack.from_dlpack(cap)
    np.testing.assert_array_equal(t2.numpy(), x.asnumpy())
    # write capsule is a COPY (functional arrays: documented deviation)
    capw = nd.to_dlpack_for_write(x)
    t3 = torch.utils.dlpack.from_dlpack(capw)
    t3[0, 0] = 999.0
    assert float(x.asnumpy()[0, 0]) == 0.0
    # the reference-parity CAPSULE round trip (bare capsule in, NDArray out)
    rt = nd.from_dlpack(nd.to_dlpack_for_read(x))
    np.testing.assert_array_equal(rt.asnumpy(), x.asnumpy())
    assert rt.context.device_type in ("cpu", "tpu")
