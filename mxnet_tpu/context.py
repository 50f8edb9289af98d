"""Device contexts.

Re-design of the reference ``Context`` (include/mxnet/base.h:105-128,
python/mxnet/context.py): device kinds are cpu/tpu (gpu aliases to tpu).
A Context maps onto a concrete ``jax.Device``; ``cpu_pinned``/``cpu_shared``
collapse to cpu (XLA manages transfer staging).
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

_DEVTYPE2STR = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
_STR2DEVTYPE = {v: k for k, v in _DEVTYPE2STR.items()}


class Context:
    """A device context. ``Context('tpu', 0)`` or via helpers ``mx.tpu(0)``."""

    _default_ctx = threading.local()
    devtype2str = _DEVTYPE2STR
    devstr2type = _STR2DEVTYPE

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in _STR2DEVTYPE:
                raise MXNetError(f"unknown device type {device_type}")
            self.device_type = device_type
            self.device_id = device_id

    @property
    def device_typeid(self):
        return _STR2DEVTYPE[self.device_type]

    def _canonical_kind(self):
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            return "cpu"
        return self.device_type

    @property
    def jax_device(self):
        """The concrete jax.Device this context denotes."""
        kind = self._canonical_kind()
        if kind == "cpu":
            # ADDRESSABLE devices only: in a multi-process job
            # jax.devices() spans every host, and a context must never
            # denote a device this process cannot touch (device_put to
            # a non-addressable device is an error)
            devs = jax.local_devices(backend="cpu") \
                if _has_platform("cpu") else jax.local_devices()
        else:
            devs = _accel_devices()
            if not devs:
                raise MXNetError(
                    f"no accelerator device available for ctx {self} "
                    f"(jax backend: {jax.default_backend()})"
                )
        if self.device_id >= len(devs):
            raise MXNetError(f"device_id {self.device_id} out of range for {kind} "
                             f"({len(devs)} devices)")
        return devs[self.device_id]

    def __eq__(self, other):
        return (isinstance(other, Context) and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls):
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return cpu()

    def empty_cache(self):
        """Parity with gpu Context.empty_cache — XLA owns the HBM arena."""


def _has_platform(name):
    try:
        return len(jax.devices(name)) > 0
    except RuntimeError:
        return False


def _accel_devices():
    """ADDRESSABLE tpu devices — local, for the same multi-process reason
    as the cpu branch of Context.jax_device.  Empty when jax has no tpu
    backend: ``mx.tpu()`` / ``mx.gpu()`` then raise instead of quietly
    denoting the host."""
    try:
        return jax.local_devices(backend="tpu")
    except RuntimeError:
        return []


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias: the accelerator context (maps to TPU here; kept for script parity
    with reference python/mxnet/context.py gpu())."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    """Number of accelerator devices (parity: mx.context.num_gpus)."""
    return len(_accel_devices())


def num_tpus():
    return len(_accel_devices())


def device_memory_info(ctx=None):
    """Memory stats of a context's device as a dict (bytes_in_use,
    bytes_limit, peak_bytes_in_use, …) from the PJRT allocator.

    Parity: the reference's Context.gpu_memory_info / storage-pool env
    introspection (include/mxnet/base.h, src/storage/); here the HBM
    pool is owned by PJRT, whose live stats are surfaced directly.
    """
    ctx = ctx or current_context()
    dev = ctx.jax_device
    stats = None
    try:
        stats = dev.memory_stats()
    except Exception:
        stats = None
    if not stats:
        raise MXNetError(
            f"device {dev} does not expose memory stats "
            "(host CPU backends have no PJRT allocator pool)")
    return dict(stats)


def gpu_memory_info(device_id=0):
    """(free, total) bytes for an accelerator device (parity:
    mx.context.gpu_memory_info)."""
    stats = device_memory_info(Context("gpu", device_id))
    total = int(stats.get("bytes_limit", 0))
    used = int(stats.get("bytes_in_use", 0))
    return max(total - used, 0), total


def current_context():
    return Context.default_ctx()
