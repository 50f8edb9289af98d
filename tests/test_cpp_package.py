"""Fluent C++ package tests (cpp-package/: parity with the reference's
cpp-package/ — Operator builder, generated op.hpp wrappers, NDArray,
autograd — over the general C ABI src/c_api.h).

1. The generated op.hpp is in sync with the live registry (regenerate
   and diff — the reference's CI regenerated op.h the same way).
2. cpp-package/examples/mlp.cpp compiles with g++ and TRAINS to
   convergence in a fresh process (exit 0 only if final loss < 0.5x
   initial) — the C++ analog of tests/python/train gates.
"""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB = os.path.join(_REPO, "src", "build", "libmxnet_tpu_c.so")


def _build_lib():
    if os.path.exists(_LIB):
        return True
    try:
        subprocess.run(["make", "-C", os.path.join(_REPO, "src"), "capi"],
                       check=True, capture_output=True, timeout=180)
        return os.path.exists(_LIB)
    except Exception:
        return False


needs_lib = pytest.mark.skipif(not _build_lib(),
                               reason="c api library not buildable")


def test_op_hpp_in_sync(tmp_path):
    # Regenerate in a FRESH interpreter: tests earlier in the suite register
    # ad-hoc ops into the live registry, which would leak into generate().
    out = tmp_path / "op.hpp"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run(
        [sys.executable, os.path.join(_REPO, "cpp-package",
                                      "OpWrapperGenerator.py"), str(out)],
        check=True, timeout=300, cwd=_REPO, env=env)
    want = out.read_text()
    path = os.path.join(_REPO, "cpp-package", "include", "mxnet_tpu",
                        "op.hpp")
    got = open(path).read()
    assert got == want, (
        "cpp-package/include/mxnet_tpu/op.hpp is stale — rerun "
        "python cpp-package/OpWrapperGenerator.py")


@needs_lib
def test_cpp_mlp_trains(tmp_path):
    exe = tmp_path / "mlp"
    cfg = subprocess.run(
        [sys.executable, "-c",
         "import sysconfig;v=sysconfig.get_config_vars();"
         "print(repr(v.get('LIBDIR','')));print(repr(v['LDVERSION']))"],
        capture_output=True, text=True, check=True).stdout.splitlines()
    libdir, ldver = eval(cfg[0]), eval(cfg[1])
    if not libdir:
        pytest.skip("python build exposes no LIBDIR to link against")
    src = os.path.join(_REPO, "cpp-package", "examples", "mlp.cpp")
    subprocess.run(
        ["g++", "-std=c++17", "-O2", src, "-o", str(exe),
         "-L", os.path.dirname(_LIB), "-lmxnet_tpu_c",
         f"-L{libdir}", f"-lpython{ldver}", "-lm",
         f"-Wl,-rpath,{os.path.dirname(_LIB)}", f"-Wl,-rpath,{libdir}"],
        check=True, capture_output=True, timeout=180)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([str(exe)], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "PASS" in r.stdout
