"""C predict ABI tests (src/c_predict_api.cc, parity:
include/mxnet/c_predict_api.h).

Two modes: (1) ctypes loads the library into this interpreter (the ABI
joins the running CPython); (2) a standalone C program embeds a fresh
interpreter — the reference deployment shape for non-Python hosts."""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB = os.path.join(_REPO, "src", "build", "libmxnet_tpu_predict.so")


def _build_lib():
    if os.path.exists(_LIB):
        return True
    try:
        subprocess.run(["make", "-C", os.path.join(_REPO, "src"),
                        "predict"], check=True, capture_output=True,
                       timeout=180)
        return os.path.exists(_LIB)
    except Exception:
        return False


needs_lib = pytest.mark.skipif(not _build_lib(),
                               reason="predict library not buildable")


def _export_mlp(tmp_path):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 4).astype(np.float32))
    ref = net(x).asnumpy()
    prefix = str(tmp_path / "mlp")
    net.export(prefix)
    return prefix, x.asnumpy(), ref


def _bind_api(lib):
    u32 = ctypes.c_uint32
    lib.MXPredCreate.restype = ctypes.c_int
    lib.MXPredCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, u32, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(u32), ctypes.POINTER(u32),
        ctypes.POINTER(ctypes.c_void_p)]
    lib.MXPredSetInput.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.POINTER(ctypes.c_float), u32]
    lib.MXPredForward.argtypes = [ctypes.c_void_p]
    lib.MXPredGetOutputShape.argtypes = [
        ctypes.c_void_p, u32, ctypes.POINTER(ctypes.POINTER(u32)),
        ctypes.POINTER(u32)]
    lib.MXPredGetOutput.argtypes = [ctypes.c_void_p, u32,
                                    ctypes.POINTER(ctypes.c_float), u32]
    lib.MXPredFree.argtypes = [ctypes.c_void_p]
    lib.MXGetLastError.restype = ctypes.c_char_p
    return lib


@needs_lib
def test_ctypes_roundtrip(tmp_path):
    prefix, xin, ref = _export_mlp(tmp_path)
    sym_json = open(prefix + "-symbol.json").read().encode()
    params = open(prefix + "-0000.params", "rb").read()

    lib = _bind_api(ctypes.CDLL(_LIB))
    handle = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shape = (ctypes.c_uint32 * 2)(2, 4)
    rc = lib.MXPredCreate(sym_json, params, len(params), 1, 0, 1, keys,
                          indptr, shape, ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError()

    data = np.ascontiguousarray(xin, np.float32)
    rc = lib.MXPredSetInput(
        handle, b"data",
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), data.size)
    assert rc == 0, lib.MXGetLastError()
    assert lib.MXPredForward(handle) == 0, lib.MXGetLastError()

    sd = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    assert lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sd),
                                    ctypes.byref(ndim)) == 0
    out_shape = tuple(sd[i] for i in range(ndim.value))
    assert out_shape == (2, 3)

    out = np.zeros(out_shape, np.float32)
    rc = lib.MXPredGetOutput(
        handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size)
    assert rc == 0, lib.MXGetLastError()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    lib.MXPredFree(handle)


_C_MAIN = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

typedef void* PredictorHandle;
typedef unsigned int mx_uint;
extern int MXPredCreate(const char*, const void*, int, int, int, mx_uint,
                        const char**, const mx_uint*, const mx_uint*,
                        PredictorHandle*);
extern int MXPredSetInput(PredictorHandle, const char*, const float*,
                          mx_uint);
extern int MXPredForward(PredictorHandle);
extern int MXPredGetOutput(PredictorHandle, mx_uint, float*, mx_uint);
extern int MXPredFree(PredictorHandle);
extern const char* MXGetLastError();

static char* slurp(const char* path, long* size) {
  FILE* f = fopen(path, "rb");
  if (!f) return NULL;
  fseek(f, 0, SEEK_END); *size = ftell(f); fseek(f, 0, SEEK_SET);
  char* buf = malloc(*size + 1);
  if (fread(buf, 1, *size, f) != (size_t)*size) { fclose(f); return NULL; }
  buf[*size] = 0; fclose(f);
  return buf;
}

int main(int argc, char** argv) {
  long sym_size, param_size;
  char* sym = slurp(argv[1], &sym_size);
  char* params = slurp(argv[2], &param_size);
  if (!sym || !params) return 2;
  const char* keys[1] = {"data"};
  mx_uint indptr[2] = {0, 2};
  mx_uint shape[2] = {2, 4};
  PredictorHandle h;
  if (MXPredCreate(sym, params, (int)param_size, 1, 0, 1, keys, indptr,
                   shape, &h) != 0) {
    fprintf(stderr, "create: %s\n", MXGetLastError());
    return 3;
  }
  float in[8];
  for (int i = 0; i < 8; ++i) in[i] = (float)i * 0.25f - 1.0f;
  if (MXPredSetInput(h, "data", in, 8) != 0) return 4;
  if (MXPredForward(h) != 0) {
    fprintf(stderr, "fwd: %s\n", MXGetLastError());
    return 5;
  }
  float out[6];
  if (MXPredGetOutput(h, 0, out, 6) != 0) return 6;
  for (int i = 0; i < 6; ++i) printf("%.6f\n", out[i]);
  MXPredFree(h);
  return 0;
}
"""


@needs_lib
def test_standalone_c_program(tmp_path):
    """True embedding: a C binary (no Python host) drives inference."""
    prefix, _xin, _ref = _export_mlp(tmp_path)
    c_src = tmp_path / "main.c"
    c_src.write_text(_C_MAIN)
    exe = str(tmp_path / "predict_demo")
    try:
        subprocess.run(
            ["gcc", str(c_src), "-o", exe,
             f"-L{os.path.dirname(_LIB)}", "-lmxnet_tpu_predict",
             f"-Wl,-rpath,{os.path.dirname(_LIB)}"],
            check=True, capture_output=True, timeout=120)
    except Exception:
        pytest.skip("no C toolchain for the standalone binary")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    site = [p for p in sys.path if "site-packages" in p]
    env["PYTHONPATH"] = os.pathsep.join([_REPO] + site)
    proc = subprocess.run(
        [exe, prefix + "-symbol.json", prefix + "-0000.params"],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr
    got = np.asarray([float(x) for x in proc.stdout.split()],
                     np.float32).reshape(2, 3)
    # python-side reference with the same fixed input
    xin = (np.arange(8, dtype=np.float32) * 0.25 - 1.0).reshape(2, 4)
    from mxnet_tpu.c_predict import Predictor
    p = Predictor(open(prefix + "-symbol.json").read(),
                  open(prefix + "-0000.params", "rb").read(),
                  {"data": (2, 4)})
    p.set_input("data", xin.tobytes())
    p.forward()
    ref = np.frombuffer(p.output_bytes(0), np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


_CPP_MAIN = r"""
#include <mxnet_tpu/predictor.hpp>
#include <cstdio>
#include <fstream>
#include <sstream>

static std::string slurp(const char* p) {
  std::ifstream f(p, std::ios::binary);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

int main(int argc, char** argv) {
  (void)argc;
  mxnet_tpu::Predictor pred(slurp(argv[1]), slurp(argv[2]),
                            {{"data", {2, 4}}});
  std::vector<float> in(8);
  for (int i = 0; i < 8; ++i) in[i] = i * 0.25f - 1.0f;
  pred.SetInput("data", in.data(), in.size());
  pred.Forward();
  auto shape = pred.GetOutputShape(0);
  if (shape.size() != 2 || shape[0] != 2 || shape[1] != 3) return 7;
  for (float v : pred.GetOutput(0)) std::printf("%.6f\n", v);
  return 0;
}
"""


@needs_lib
def test_cpp_package_wrapper(tmp_path):
    """Header-only C++ fluent API (cpp-package/) over the C ABI."""
    prefix, _xin, _ref = _export_mlp(tmp_path)
    cpp = tmp_path / "main.cc"
    cpp.write_text(_CPP_MAIN)
    exe = str(tmp_path / "cpp_demo")
    inc = os.path.join(_REPO, "cpp-package", "include")
    try:
        subprocess.run(
            ["g++", "-std=c++17", str(cpp), "-o", exe, f"-I{inc}",
             f"-L{os.path.dirname(_LIB)}", "-lmxnet_tpu_predict",
             f"-Wl,-rpath,{os.path.dirname(_LIB)}"],
            check=True, capture_output=True, timeout=120)
    except subprocess.CalledProcessError as e:
        pytest.fail(f"cpp compile failed: {e.stderr.decode()[-2000:]}")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    site = [p for p in sys.path if "site-packages" in p]
    env["PYTHONPATH"] = os.pathsep.join([_REPO] + site)
    proc = subprocess.run(
        [exe, prefix + "-symbol.json", prefix + "-0000.params"],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr
    got = np.asarray([float(x) for x in proc.stdout.split()], np.float32)
    assert got.shape == (6,) and np.isfinite(got).all()
