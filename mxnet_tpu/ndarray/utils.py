"""NDArray serialization: mx.nd.save / mx.nd.load.

Byte-compatible with the reference wire format (src/ndarray/ndarray.cc:
NDARRAY_V2_MAGIC 0xF993fac9, list magic kMXAPINDArrayListMagic 0x112,
ndarray.cc:1593 Save / 1716 Load), so `.params` files move between the
reference and this framework in both directions. Sparse arrays use the same
aux-array layout (csr: indptr+indices; row_sparse: indices).
"""
from __future__ import annotations

import struct

import numpy as np

from ..base import MXNetError, dtype_code, dtype_from_code
from ..context import cpu
from .ndarray import NDArray, array
from .sparse import CSRNDArray, RowSparseNDArray

_LIST_MAGIC = 0x112
_V2_MAGIC = 0xF993FAC9
_V1_MAGIC = 0xF993FAC8
# storage type codes (include/mxnet/ndarray.h NDArrayStorageType)
_STYPE = {"default": 0, "row_sparse": 1, "csr": 2}
_STYPE_INV = {v: k for k, v in _STYPE.items()}
_NUM_AUX = {"default": 0, "row_sparse": 1, "csr": 2}


def _w_shape(buf, shape):
    buf.append(struct.pack("<I", len(shape)))
    buf.append(struct.pack(f"<{len(shape)}q", *shape) if shape else b"")


def _r_shape(f):
    (ndim,) = struct.unpack("<I", f.read(4))
    if ndim == 0:
        return ()
    return struct.unpack(f"<{ndim}q", f.read(8 * ndim))


def _save_one(buf, arr):
    stype = arr.stype
    buf.append(struct.pack("<I", _V2_MAGIC))
    buf.append(struct.pack("<i", _STYPE[stype]))
    if stype == "row_sparse":
        storage_shape = tuple(arr._data.shape)
        _w_shape(buf, storage_shape)
    elif stype == "csr":
        _w_shape(buf, tuple(arr._data.shape))
    _w_shape(buf, arr.shape)
    # context: dev_type=1 (cpu), dev_id=0 — arrays are always saved from host
    buf.append(struct.pack("<ii", 1, 0))
    data = np.asarray(arr._data)
    buf.append(struct.pack("<i", dtype_code(data.dtype)))
    if stype == "row_sparse":
        buf.append(struct.pack("<i", dtype_code(np.int64)))
        _w_shape(buf, tuple(np.asarray(arr._indices).shape))
    elif stype == "csr":
        buf.append(struct.pack("<i", dtype_code(np.int64)))  # indptr
        _w_shape(buf, tuple(np.asarray(arr._indptr).shape))
        buf.append(struct.pack("<i", dtype_code(np.int64)))  # indices
        _w_shape(buf, tuple(np.asarray(arr._indices).shape))
    buf.append(np.ascontiguousarray(data).tobytes())
    if stype == "row_sparse":
        buf.append(np.asarray(arr._indices, dtype=np.int64).tobytes())
    elif stype == "csr":
        buf.append(np.asarray(arr._indptr, dtype=np.int64).tobytes())
        buf.append(np.asarray(arr._indices, dtype=np.int64).tobytes())


def _load_one(f):
    (magic,) = struct.unpack("<I", f.read(4))
    if magic == _V1_MAGIC:
        shape = _r_shape(f)
        stype = "default"
        storage_shape = shape
        aux = []
    elif magic in (_V2_MAGIC, 0xF993FACA):
        (stype_code,) = struct.unpack("<i", f.read(4))
        stype = _STYPE_INV[stype_code]
        storage_shape = None
        if stype != "default":
            storage_shape = _r_shape(f)
        shape = _r_shape(f)
    else:
        # legacy: magic was ndim (uint32 dims follow) — not supported
        raise MXNetError("unsupported legacy NDArray format")
    struct.unpack("<ii", f.read(8))  # context, ignored (loaded to cpu)
    (type_flag,) = struct.unpack("<i", f.read(4))
    dtype = dtype_from_code(type_flag)
    aux_meta = []
    for _ in range(_NUM_AUX[stype]):
        (aux_type,) = struct.unpack("<i", f.read(4))
        aux_shape = _r_shape(f)
        aux_meta.append((dtype_from_code(aux_type), aux_shape))
    dshape = storage_shape if stype != "default" else shape
    n = int(np.prod(dshape)) if dshape else 1
    data = np.frombuffer(f.read(n * dtype.itemsize), dtype=dtype).reshape(dshape)
    if stype == "default":
        return array(data)
    aux_arrays = []
    for adtype, ashape in aux_meta:
        an = int(np.prod(ashape)) if ashape else 1
        aux_arrays.append(np.frombuffer(f.read(an * adtype.itemsize),
                                        dtype=adtype).reshape(ashape))
    import jax.numpy as jnp
    if stype == "row_sparse":
        return RowSparseNDArray(jnp.asarray(data), jnp.asarray(aux_arrays[0]),
                                shape)
    return CSRNDArray(jnp.asarray(data), jnp.asarray(aux_arrays[1]),
                      jnp.asarray(aux_arrays[0]), shape)


def save(fname, data):
    """Save list or str-keyed dict of NDArrays (parity: ndarray/utils.py:149).

    Atomic: bytes stream into ``{fname}.tmp-{pid}`` and ``os.replace``
    onto the target only after a successful flush, so a crash (or
    serialization error) mid-save can never leave a torn ``.params``
    file — the previous contents of ``fname`` survive intact
    (ISSUE 2 satellite: the legacy save path shares the checkpoint
    subsystem's no-torn-writes guarantee)."""
    import os
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        keys, arrays = list(data.keys()), list(data.values())
    else:
        keys, arrays = [], list(data)
    tmp = f"{fname}.tmp-{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(struct.pack("<QQ", _LIST_MAGIC, 0))
            f.write(struct.pack("<Q", len(arrays)))
            for a in arrays:
                buf = []
                _save_one(buf, a)
                f.write(b"".join(buf))
            f.write(struct.pack("<Q", len(keys)))
            for k in keys:
                kb = k.encode("utf-8")
                f.write(struct.pack("<Q", len(kb)))
                f.write(kb)
            f.flush()
        os.replace(tmp, fname)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load(fname):
    """Load NDArrays saved by save() or by the reference (utils.py:222)."""
    with open(fname, "rb") as f:
        header, _ = struct.unpack("<QQ", f.read(16))
        if header != _LIST_MAGIC:
            raise MXNetError("Invalid NDArray file format")
        (n,) = struct.unpack("<Q", f.read(8))
        arrays = [_load_one(f) for _ in range(n)]
        (nk,) = struct.unpack("<Q", f.read(8))
        keys = []
        for _ in range(nk):
            (ln,) = struct.unpack("<Q", f.read(8))
            keys.append(f.read(ln).decode("utf-8"))
    if keys:
        return dict(zip(keys, arrays))
    return arrays


# --- DLPack interop (parity: ndarray.py:4058 to_dlpack_for_read /
# to_dlpack_for_write / from_dlpack:4121).  Backed by the array API's
# native __dlpack__ protocol, so exchange with torch/numpy/cupy is
# zero-copy where the producer allows it. ---------------------------------
def to_dlpack_for_read(data):
    """A DLPack capsule view of ``data`` for READING (parity:
    to_dlpack_for_read).  Materialization is a sync point, so async
    device failures surface here as MXNetError (the same contract as
    wait_to_read/asnumpy)."""
    data.wait_to_read()  # MXNetError-wrapping sync (ndarray.py contract)
    return data._data.__dlpack__()


def to_dlpack_for_write(data):
    """DLPack capsule for writing (parity: to_dlpack_for_write).

    jax buffers are immutable, so a WRITABLE export cannot alias the
    original: the capsule wraps a host copy, and the caller's writes are
    NOT reflected back (documented deviation — functional arrays have no
    in-place aliasing to give)."""
    import numpy as np
    host = np.array(data.asnumpy())  # fresh, writable
    return host.__dlpack__()


class _CapsuleProducer:
    """Adapter: jax's from_dlpack wants a protocol OBJECT, while the
    reference API traffics in bare capsules.  A bare capsule carries no
    device tag, so it is presented as host memory (kDLCPU) — which is
    what this API's own to_dlpack_for_read/-write produce off-device;
    cross-device exchange should hand over the producer object itself."""

    def __init__(self, capsule):
        self._capsule = capsule

    def __dlpack__(self, **_kwargs):
        return self._capsule

    def __dlpack_device__(self):
        return (1, 0)  # kDLCPU


def from_dlpack(dlpack):
    """NDArray from a DLPack capsule or any __dlpack__-capable producer
    (torch tensors, numpy arrays, ...) — parity: from_dlpack."""
    import jax
    from ..context import Context, cpu, gpu, tpu
    from .ndarray import NDArray
    if not hasattr(dlpack, "__dlpack__"):  # bare capsule (reference form)
        dlpack = _CapsuleProducer(dlpack)
    arr = jax.dlpack.from_dlpack(dlpack)
    # label the context from where the buffer actually landed
    dev = getattr(arr, "device", None)
    platform = getattr(dev, "platform", "cpu")
    ctor = {"cpu": cpu, "gpu": gpu, "cuda": gpu, "tpu": tpu}.get(
        platform, cpu)
    return NDArray(arr, ctor(getattr(dev, "id", 0)))
