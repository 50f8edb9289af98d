"""Test harness configuration.

Forces an 8-device virtual CPU mesh (parity with the reference's strategy of
running the whole unit suite per backend, SURVEY.md §4): sharding/collective
tests exercise real multi-device code paths without TPU hardware.  Unit
tests are CPU-only by design: the chip is driven by ``chip_smoke.py``.
"""
import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# hermetic persistent-compilation-cache location: a test that triggers
# mxnet_tpu.compile.ensure_persistent_cache must never write artifacts
# into the checkout's .jax_cache or a directory placed from outside
# (JAX_COMPILATION_CACHE_DIR outranks the knob below, and the cache tests
# assert on the knob's versioned namespace)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ.setdefault(
    "MXNET_COMPILE_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "mxnet-tpu-test-compile-cache"))
# hermetic flight-recorder dump location: watchdog fires / chaos kills
# inside tests must not litter the developer's cwd with
# mxnet-flight-*.json rings (tests that assert on dumps pin their own
# MXNET_FLIGHT_DIR via monkeypatch)
_flight_dir = os.path.join(tempfile.gettempdir(), "mxnet-tpu-test-flight")
os.makedirs(_flight_dir, exist_ok=True)
os.environ.setdefault("MXNET_FLIGHT_DIR", _flight_dir)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seeded():
    """Reproducible-but-varied RNG per test (parity: with_seed() decorator
    in reference tests/python/unittest/common.py). MXNET_TEST_SEED varies
    the base seed — tools/flakiness_checker.py sets it per trial."""
    import mxnet_tpu as mx
    seed = int(os.environ.get("MXNET_TEST_SEED", 0))
    np.random.seed(seed)
    mx.random.seed(seed)
    yield
