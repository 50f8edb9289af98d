"""Runtime feature detection (parity: python/mxnet/runtime.py +
include/mxnet/libinfo.h feature flags). Features reflect what the TPU
runtime actually provides."""
from __future__ import annotations

import collections

import jax

Feature = collections.namedtuple("Feature", ["name", "enabled"])


def _detect():
    try:
        platform = jax.default_backend()
    except Exception:
        platform = "unknown"
    feats = {
        "TPU": platform == "tpu",
        "CPU": True,
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "OPENMP": True,          # XLA threadpool
        "BLAS_OPEN": True,       # XLA dot
        "LAPACK": True,          # jax.scipy.linalg
        "MKLDNN": False,
        "XLA": True,
        "PALLAS": True,
        "F16C": True,
        "INT64_TENSOR_SIZE": False,  # int32 index space (TPU-native width)
        "SIGNAL_HANDLER": True,
        "DEBUG": False,
        "DIST_KVSTORE": True,
        "SSE": True,
        "PROFILER": True,
    }
    return {k: Feature(k, v) for k, v in feats.items()}


class Features(dict):
    """Check the library for compile-time features
    (parity: runtime.py Features)."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            dict.__init__(cls.instance, _detect())
        return cls.instance

    def __repr__(self):
        return f"[{', '.join(f'✔ {n}' if f.enabled else f'✖ {n}' for n, f in self.items())}]"

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"Feature '{feature_name}' is unknown, "
                               "known features are: %s" % list(self.keys()))
        return self[feature_name].enabled


def feature_list():
    return list(Features().values())
