"""ctypes loader for the native data-plane library (src/io_native.cc).

The reference implements its IO hot path in C++ (RecordIO parsing +
image batch assembly, src/io/iter_image_recordio_2.cc); this module loads
the TPU framework's native equivalent, building it on first use with
`make -C src` when a toolchain is present. Every caller has a pure-Python
fallback — absence of a compiler degrades performance, never capability.

Env: MXNET_NATIVE_IO=0 disables the native path entirely.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_LOCK = threading.Lock()
_LIB = None
_TRIED = False

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
_LIB_PATH = os.path.join(_SRC_DIR, "build", "libmxnet_tpu_io.so")
_LIB_SOURCE = os.path.join(_SRC_DIR, "io_native.cc")


def _stale():
    """No library yet, or one older than its source (``src/build/`` is
    not under version control: a checkout can carry a library built from
    another commit's ``io_native.cc``)."""
    try:
        return os.path.getmtime(_LIB_PATH) < os.path.getmtime(_LIB_SOURCE)
    except OSError:
        return not os.path.exists(_LIB_PATH)


def _build():
    try:
        subprocess.run(["make", "-C", _SRC_DIR],
                       check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError):
        return False  # no toolchain / build failure: pure-Python paths


def _bind(lib):
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.mxio_scan_records.restype = ctypes.c_int64
    lib.mxio_scan_records.argtypes = [ctypes.c_char_p, i64p, i64p, i32p,
                                      ctypes.c_int64]
    lib.mxio_gather.restype = ctypes.c_int32
    lib.mxio_gather.argtypes = [ctypes.c_char_p, i64p, i64p,
                                ctypes.c_int64, u8p, i64p]
    lib.mxio_batch_transform.restype = None
    lib.mxio_batch_transform.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, u8p, f32p, f32p, f32p]
    lib.mxio_batch_transform_f32.restype = None
    lib.mxio_batch_transform_f32.argtypes = [
        f32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, u8p, f32p, f32p, f32p]
    lib.mxio_version.restype = ctypes.c_int32
    lib.mxio_version.argtypes = []
    lib.mxio_pipe_create.restype = ctypes.c_void_p
    lib.mxio_pipe_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_uint64, f32p, f32p, ctypes.c_int32, ctypes.c_int32]
    lib.mxio_pipe_next.restype = ctypes.c_int64
    lib.mxio_pipe_next.argtypes = [ctypes.c_void_p, f32p, f32p]
    lib.mxio_pipe_reset.restype = None
    lib.mxio_pipe_reset.argtypes = [ctypes.c_void_p]
    lib.mxio_pipe_num_batches.restype = ctypes.c_int64
    lib.mxio_pipe_num_batches.argtypes = [ctypes.c_void_p]
    lib.mxio_pipe_destroy.restype = None
    lib.mxio_pipe_destroy.argtypes = [ctypes.c_void_p]
    return lib


def get_lib():
    """The loaded native library, or None (fallback to Python)."""
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        from .config import get as _cfg
        if not _cfg("MXNET_NATIVE_IO"):
            return None
        if _stale() and not _build():
            return None
        try:
            _LIB = _bind(ctypes.CDLL(_LIB_PATH))
        except OSError:
            _LIB = None
        return _LIB


def available():
    return get_lib() is not None


def _fptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _u8ptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i64ptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def scan_records(path, max_records=None):
    """Frame table of a .rec file: (offsets, lengths, cflags) int64/int32
    arrays of payload byte ranges. Raises on scan failure; returns None
    when the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    if max_records is None:
        # every frame is >= 8 bytes (header alone), so this bound is
        # exact — no silent truncation possible
        max_records = max(os.path.getsize(path) // 8, 1)
    offsets = np.empty(max_records, np.int64)
    lengths = np.empty(max_records, np.int64)
    cflags = np.empty(max_records, np.int32)
    n = lib.mxio_scan_records(
        path.encode(), _i64ptr(offsets), _i64ptr(lengths),
        cflags.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), max_records)
    if n < 0:
        raise IOError(f"native recordio scan failed for {path}")
    return offsets[:n].copy(), lengths[:n].copy(), cflags[:n].copy()


def gather(path, offsets, lengths):
    """Read byte ranges into one contiguous buffer; returns (buf,
    out_offsets) or None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    offsets = np.ascontiguousarray(offsets, np.int64)
    lengths = np.ascontiguousarray(lengths, np.int64)
    out_offsets = np.zeros(len(offsets), np.int64)
    np.cumsum(lengths[:-1], out=out_offsets[1:])
    buf = np.empty(int(lengths.sum()), np.uint8)
    rc = lib.mxio_gather(path.encode(), _i64ptr(offsets), _i64ptr(lengths),
                         len(offsets), _u8ptr(buf), _i64ptr(out_offsets))
    if rc != 0:
        raise IOError(f"native gather failed for {path}")
    return buf, out_offsets


def batch_transform(images, mirror=None, mean=None, std=None):
    """Fused cast+normalize+mirror+HWC->NCHW batch pack.

    images: [N,H,W,C] uint8 or float32 (contiguous). Returns [N,C,H,W]
    float32, or None when the native library is unavailable.
    """
    lib = get_lib()
    if lib is None:
        return None
    images = np.ascontiguousarray(images)
    n, h, w, c = images.shape
    if c > 16:
        return None  # mean/std channel buffer limit in the kernel
    out = np.empty((n, c, h, w), np.float32)
    mir = None
    if mirror is not None:
        mir = np.ascontiguousarray(mirror, np.uint8)
    # keep the contiguous copies alive across the call
    mean_c = np.ascontiguousarray(mean, np.float32).ravel() \
        if mean is not None else None
    std_c = np.ascontiguousarray(std, np.float32).ravel() \
        if std is not None else None
    meanp = _fptr(mean_c) if mean_c is not None else None
    stdp = _fptr(std_c) if std_c is not None else None
    if images.dtype == np.uint8:
        lib.mxio_batch_transform(
            _u8ptr(images), n, h, w, c,
            _u8ptr(mir) if mir is not None else None, meanp, stdp,
            _fptr(out))
    else:
        images = images.astype(np.float32, copy=False)
        lib.mxio_batch_transform_f32(
            _fptr(images), n, h, w, c,
            _u8ptr(mir) if mir is not None else None, meanp, stdp,
            _fptr(out))
    return out


class RecordPipe:
    """Native threaded record pipeline (reference: the
    iter_image_recordio_2.cc parser threads + ready-batch ring).  Reads
    RAW-pixel records (IRHeader + h*w*c uint8 body) and produces
    normalized NCHW float32 batches assembled by C++ worker threads that
    run ahead of the consumer.  Returns None from the constructor path
    (via create()) when the native lib is unavailable."""

    def __init__(self, handle, lib, batch, shape, label_width):
        self._h = handle
        self._lib = lib
        self.batch = batch
        self.shape = shape            # (c, h, w)
        self.label_width = label_width

    @classmethod
    def create(cls, path, batch_size, data_shape, label_width=1,
               shuffle=False, rand_mirror=False, seed=0, mean=None,
               std=None, prefetch=4, num_threads=2):
        lib = get_lib()
        if lib is None:
            return None
        c, h, w = data_shape
        mean_c = np.ascontiguousarray(mean, np.float32).ravel() \
            if mean is not None else None
        std_c = np.ascontiguousarray(std, np.float32).ravel() \
            if std is not None else None
        handle = lib.mxio_pipe_create(
            str(path).encode(), batch_size, h, w, c, label_width,
            1 if shuffle else 0, 1 if rand_mirror else 0, seed,
            _fptr(mean_c) if mean_c is not None else None,
            _fptr(std_c) if std_c is not None else None,
            prefetch, num_threads)
        if not handle:
            return None
        return cls(handle, lib, batch_size, data_shape, label_width)

    @property
    def num_batches(self):
        return int(self._lib.mxio_pipe_num_batches(self._h))

    def next_batch(self):
        """(data NCHW float32, label) or None at epoch end."""
        c, h, w = self.shape
        data = np.empty((self.batch, c, h, w), np.float32)
        label = np.empty((self.batch, self.label_width), np.float32)
        rc = int(self._lib.mxio_pipe_next(self._h, _fptr(data),
                                          _fptr(label)))
        if rc == -1:
            return None
        if rc < -1:
            raise RuntimeError(f"native record pipe IO error ({rc})")
        return data, label

    def reset(self):
        self._lib.mxio_pipe_reset(self._h)

    def __del__(self):
        try:
            if self._h:
                self._lib.mxio_pipe_destroy(self._h)
                self._h = None
        except Exception:  # graftlint: disable=swallowed-error -- __del__ during interpreter teardown must stay silent
            pass
