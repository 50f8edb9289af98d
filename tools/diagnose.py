#!/usr/bin/env python
"""Environment diagnostic (parity: reference tools/diagnose.py).

Prints platform, python, package versions, jax backend/devices, native
library availability, and the typed env-var configuration.
"""
import os
import platform
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.machine(), platform.architecture()[0])
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("release      :", platform.release())

    print("----------Framework Info----------")
    import mxnet_tpu as mx
    print("mxnet_tpu    :", mx.__version__)
    import jax
    print("jax          :", jax.__version__)
    import numpy as np
    print("numpy        :", np.__version__)
    try:
        import jaxlib
        print("jaxlib       :", jaxlib.__version__)
    except Exception:
        pass
    print("default bkend:", jax.default_backend())
    try:
        print("devices      :", jax.devices())
    except Exception as e:
        print("devices      : <unavailable:", e, ">")

    print("----------Native Libraries----------")
    from mxnet_tpu import _native
    print("io_native    :", "loaded" if _native.available() else "absent")
    predict = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "build",
        "libmxnet_tpu_predict.so")
    print("predict ABI  :", "built" if os.path.exists(predict) else "absent")

    print("----------Environment----------")
    from mxnet_tpu import config
    for name in sorted(config._REGISTRY):
        cur = os.environ.get(name)
        if cur is not None:
            print(f"{name}={cur}")
    for var in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR"):
        if os.environ.get(var):
            print(f"{var}={os.environ[var]}")


if __name__ == "__main__":
    main()
