"""General C API tests (src/c_api.cc; parity: include/mxnet/c_api.h
training-critical subset — MXNDArray*, MXImperativeInvokeEx:1063,
MXAutogradBackwardEx:1152, MXSymbol*, MXExecutorBind (c_api.h:1993),
MXKVStore*).

Two modes, mirroring test_c_predict.py: (1) ctypes joins the running
interpreter; (2) a standalone C program embeds a fresh CPython and trains
LeNet ONE STEP end-to-end — symbol compose, bind, forward, backward, SGD
update — proving training (not just predict) is reachable from C.
"""
import ctypes
import os
import subprocess
import sys

import numpy as np
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

u32 = ctypes.c_uint32
vp = ctypes.c_void_p


def _lib():
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    # argtypes matter: a bare int handle would be truncated to c_int
    cp, cpp, u32p = ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p), \
        ctypes.POINTER(u32)
    vpp = ctypes.POINTER(vp)
    intp = ctypes.POINTER(ctypes.c_int)
    lib.MXNDArrayCreateEx.argtypes = [u32p, u32, ctypes.c_int,
                                      ctypes.c_int, ctypes.c_int,
                                      ctypes.c_int, vpp]
    lib.MXNDArraySyncCopyFromCPU.argtypes = [vp, vp, ctypes.c_size_t]
    lib.MXNDArraySyncCopyToCPU.argtypes = [vp, vp, ctypes.c_size_t]
    lib.MXNDArrayGetShape.argtypes = [vp, u32p, ctypes.POINTER(u32p)]
    lib.MXNDArrayGetDType.argtypes = [vp, intp]
    lib.MXNDArraySave.argtypes = [cp, u32, vpp, cpp]
    lib.MXNDArrayLoad.argtypes = [cp, u32p, ctypes.POINTER(vpp), u32p,
                                  ctypes.POINTER(cpp)]
    lib.MXNDArrayFree.argtypes = [vp]
    lib.MXNDArrayGetGrad.argtypes = [vp, vpp]
    lib.MXImperativeInvokeEx.argtypes = [cp, ctypes.c_int, vpp, intp,
                                         ctypes.POINTER(vpp),
                                         ctypes.c_int, cpp, cpp]
    lib.MXAutogradSetIsRecording.argtypes = [ctypes.c_int, intp]
    lib.MXAutogradSetIsTraining.argtypes = [ctypes.c_int, intp]
    lib.MXAutogradMarkVariables.argtypes = [u32, vpp, u32p, vpp]
    lib.MXAutogradBackward.argtypes = [u32, vpp, vpp, ctypes.c_int]
    lib.MXSymbolCreateVariable.argtypes = [cp, vpp]
    lib.MXSymbolCreateOp.argtypes = [cp, u32, cpp, cpp, u32, vpp, cp, vpp]
    lib.MXSymbolCreateFromJSON.argtypes = [cp, vpp]
    lib.MXSymbolSaveToJSON.argtypes = [vp, cpp]
    lib.MXSymbolListArguments.argtypes = [vp, u32p, ctypes.POINTER(cpp)]
    lib.MXSymbolListOutputs.argtypes = [vp, u32p, ctypes.POINTER(cpp)]
    lib.MXSymbolFree.argtypes = [vp]
    lib.MXExecutorBind.argtypes = [vp, ctypes.c_int, ctypes.c_int, u32,
                                   cpp, vpp, cpp, u32, cpp, vpp, vpp]
    lib.MXExecutorForward.argtypes = [vp, ctypes.c_int]
    lib.MXExecutorBackward.argtypes = [vp, u32, vpp]
    lib.MXExecutorOutputs.argtypes = [vp, u32p, ctypes.POINTER(vpp)]
    lib.MXExecutorArgGrad.argtypes = [vp, cp, vpp]
    lib.MXExecutorFree.argtypes = [vp]
    lib.MXKVStoreCreate.argtypes = [cp, vpp]
    lib.MXKVStoreInit.argtypes = [vp, u32, intp, vpp]
    lib.MXKVStorePush.argtypes = [vp, u32, intp, vpp, ctypes.c_int]
    lib.MXKVStorePull.argtypes = [vp, u32, intp, vpp, ctypes.c_int]
    lib.MXKVStoreGetRank.argtypes = [vp, intp]
    lib.MXKVStoreGetGroupSize.argtypes = [vp, intp]
    lib.MXKVStoreFree.argtypes = [vp]
    lib.MXListAllOpNames.argtypes = [u32p, ctypes.POINTER(cpp)]
    lib.MXGetVersion.argtypes = [intp]
    # round-4 additions: views, infer-shape, cached op, data iter,
    # recordio, profiler
    lib.MXNDArrayReshape.argtypes = [vp, ctypes.c_int, intp, vpp]
    lib.MXNDArraySlice.argtypes = [vp, u32, u32, vpp]
    lib.MXNDArrayAt.argtypes = [vp, u32, vpp]
    lib.MXNDArrayGetContext.argtypes = [vp, intp, intp]
    lib.MXRandomSeed.argtypes = [ctypes.c_int]
    u32pp = ctypes.POINTER(u32p)
    lib.MXSymbolInferShape.argtypes = [vp, u32, cpp, u32p, u32p,
                                       u32p, u32pp, ctypes.POINTER(u32pp),
                                       u32p, u32pp, ctypes.POINTER(u32pp),
                                       u32p, u32pp, ctypes.POINTER(u32pp),
                                       intp]
    lib.MXCreateCachedOp.argtypes = [vp, vpp]
    lib.MXInvokeCachedOp.argtypes = [vp, ctypes.c_int, vpp, intp,
                                     ctypes.POINTER(vpp)]
    lib.MXFreeCachedOp.argtypes = [vp]
    lib.MXListDataIters.argtypes = [u32p, ctypes.POINTER(cpp)]
    lib.MXDataIterCreateIter.argtypes = [cp, u32, cpp, cpp, vpp]
    lib.MXDataIterBeforeFirst.argtypes = [vp]
    lib.MXDataIterNext.argtypes = [vp, intp]
    lib.MXDataIterGetData.argtypes = [vp, vpp]
    lib.MXDataIterGetLabel.argtypes = [vp, vpp]
    lib.MXDataIterGetPadNum.argtypes = [vp, intp]
    lib.MXDataIterFree.argtypes = [vp]
    lib.MXRecordIOWriterCreate.argtypes = [cp, vpp]
    lib.MXRecordIOWriterWriteRecord.argtypes = [vp, ctypes.c_char_p,
                                                ctypes.c_size_t]
    lib.MXRecordIOWriterFree.argtypes = [vp]
    lib.MXRecordIOReaderCreate.argtypes = [cp, vpp]
    lib.MXRecordIOReaderReadRecord.argtypes = [vp, ctypes.POINTER(cp),
                                               ctypes.POINTER(
                                                   ctypes.c_size_t)]
    lib.MXRecordIOReaderFree.argtypes = [vp]
    lib.MXSetProcessProfilerConfig.argtypes = [ctypes.c_int, cpp, cpp]
    lib.MXSetProcessProfilerState.argtypes = [ctypes.c_int]
    lib.MXDumpProcessProfile.argtypes = [ctypes.c_int]
    lib.MXAggregateProfileStatsPrint.argtypes = [ctypes.POINTER(cp),
                                                 ctypes.c_int]
    return lib


def _err(lib):
    return lib.MXGetLastError().decode()


def _mk_ndarray(lib, arr):
    arr = np.ascontiguousarray(arr, np.float32)
    shape = (u32 * arr.ndim)(*arr.shape)
    h = vp()
    rc = lib.MXNDArrayCreateEx(shape, arr.ndim, 1, 0, 0, 0,
                               ctypes.byref(h))
    assert rc == 0, _err(lib)
    rc = lib.MXNDArraySyncCopyFromCPU(h, arr.ctypes.data_as(vp),
                                      ctypes.c_size_t(arr.nbytes))
    assert rc == 0, _err(lib)
    return h


def _to_numpy(lib, h):
    ndim = u32()
    pdata = ctypes.POINTER(u32)()
    assert lib.MXNDArrayGetShape(h, ctypes.byref(ndim),
                                 ctypes.byref(pdata)) == 0, _err(lib)
    shape = tuple(pdata[i] for i in range(ndim.value))
    out = np.zeros(shape, np.float32)
    rc = lib.MXNDArraySyncCopyToCPU(h, out.ctypes.data_as(vp),
                                    ctypes.c_size_t(out.nbytes))
    assert rc == 0, _err(lib)
    return out


@needs_lib
class TestCtypes:
    def test_ndarray_roundtrip_and_save_load(self, tmp_path):
        lib = _lib()
        x = np.random.RandomState(0).randn(3, 4).astype(np.float32)
        h = _mk_ndarray(lib, x)
        np.testing.assert_allclose(_to_numpy(lib, h), x)
        dt = ctypes.c_int()
        assert lib.MXNDArrayGetDType(h, ctypes.byref(dt)) == 0
        assert dt.value == 0  # float32
        fname = str(tmp_path / "arr.params").encode()
        keys = (ctypes.c_char_p * 1)(b"weight")
        handles = (vp * 1)(h)
        assert lib.MXNDArraySave(fname, 1, handles, keys) == 0, _err(lib)
        out_size = u32()
        out_arrs = ctypes.POINTER(vp)()
        name_size = u32()
        names = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXNDArrayLoad(fname, ctypes.byref(out_size),
                                 ctypes.byref(out_arrs),
                                 ctypes.byref(name_size),
                                 ctypes.byref(names)) == 0, _err(lib)
        assert out_size.value == 1 and names[0] == b"weight"
        np.testing.assert_allclose(_to_numpy(lib, out_arrs[0]), x)
        lib.MXNDArrayFree(h)

    def test_imperative_invoke(self):
        lib = _lib()
        a = _mk_ndarray(lib, np.full((2, 2), 3.0))
        num_out = ctypes.c_int(0)
        outs = ctypes.POINTER(vp)()
        rc = lib.MXImperativeInvokeEx(b"square", 1, (vp * 1)(a),
                                      ctypes.byref(num_out),
                                      ctypes.byref(outs), 0, None, None)
        assert rc == 0, _err(lib)
        assert num_out.value == 1
        np.testing.assert_allclose(_to_numpy(lib, outs[0]), 9.0)

    def test_autograd(self):
        lib = _lib()
        x = _mk_ndarray(lib, np.ones((2, 3)))
        g = _mk_ndarray(lib, np.zeros((2, 3)))
        assert lib.MXAutogradMarkVariables(
            1, (vp * 1)(x), (u32 * 1)(1), (vp * 1)(g)) == 0, _err(lib)
        prev = ctypes.c_int()
        assert lib.MXAutogradSetIsRecording(1, ctypes.byref(prev)) == 0
        num_out = ctypes.c_int(0)
        outs = ctypes.POINTER(vp)()
        assert lib.MXImperativeInvokeEx(b"square", 1, (vp * 1)(x),
                                        ctypes.byref(num_out),
                                        ctypes.byref(outs), 0, None,
                                        None) == 0
        y = outs[0]
        num_out = ctypes.c_int(0)          # reset: fresh outputs wanted
        outs = ctypes.POINTER(vp)()
        assert lib.MXImperativeInvokeEx(b"sum", 1, (vp * 1)(y),
                                        ctypes.byref(num_out),
                                        ctypes.byref(outs), 0, None,
                                        None) == 0
        s = outs[0]
        assert lib.MXAutogradSetIsRecording(0, ctypes.byref(prev)) == 0
        assert lib.MXAutogradBackward(1, (vp * 1)(s), None, 0) == 0, \
            _err(lib)
        gh = vp()
        assert lib.MXNDArrayGetGrad(x, ctypes.byref(gh)) == 0
        np.testing.assert_allclose(_to_numpy(lib, gh), 2.0)

    def test_kvstore(self):
        lib = _lib()
        kv = vp()
        assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0, \
            _err(lib)
        v = _mk_ndarray(lib, np.array([1.0, 2.0], np.float32))
        keys = (ctypes.c_int * 1)(3)
        assert lib.MXKVStoreInit(kv, 1, keys, (vp * 1)(v)) == 0, _err(lib)
        assert lib.MXKVStorePush(kv, 1, keys, (vp * 1)(v), 0) == 0
        out = _mk_ndarray(lib, np.zeros(2, np.float32))
        assert lib.MXKVStorePull(kv, 1, keys, (vp * 1)(out), 0) == 0
        np.testing.assert_allclose(_to_numpy(lib, out), [1.0, 2.0])
        rank = ctypes.c_int()
        size = ctypes.c_int()
        assert lib.MXKVStoreGetRank(kv, ctypes.byref(rank)) == 0
        assert lib.MXKVStoreGetGroupSize(kv, ctypes.byref(size)) == 0
        assert (rank.value, size.value) == (0, 1)
        lib.MXKVStoreFree(kv)

    def test_symbol_and_executor_train_step(self):
        """Full symbolic train step through the C ABI from ctypes."""
        lib = _lib()
        data = vp()
        assert lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)) == 0
        w = vp()
        assert lib.MXSymbolCreateVariable(b"w", ctypes.byref(w)) == 0
        label = vp()
        assert lib.MXSymbolCreateVariable(b"label",
                                          ctypes.byref(label)) == 0
        fc = vp()
        keys = (ctypes.c_char_p * 2)(b"num_hidden", b"no_bias")
        vals = (ctypes.c_char_p * 2)(b"3", b"True")
        assert lib.MXSymbolCreateOp(b"FullyConnected", 2, keys, vals, 2,
                                    (vp * 2)(data, w), b"fc",
                                    ctypes.byref(fc)) == 0, _err(lib)
        out = vp()
        assert lib.MXSymbolCreateOp(b"SoftmaxOutput", 0, None, None, 2,
                                    (vp * 2)(fc, label), b"sm",
                                    ctypes.byref(out)) == 0, _err(lib)
        # serde roundtrip
        js = ctypes.c_char_p()
        assert lib.MXSymbolSaveToJSON(out, ctypes.byref(js)) == 0
        out2 = vp()
        assert lib.MXSymbolCreateFromJSON(js, ctypes.byref(out2)) == 0, \
            _err(lib)
        n = u32()
        strs = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXSymbolListArguments(out2, ctypes.byref(n),
                                         ctypes.byref(strs)) == 0
        args = [strs[i].decode() for i in range(n.value)]
        assert args == ["data", "w", "label"]

        rs = np.random.RandomState(2)
        xs = {"data": rs.randn(4, 5).astype(np.float32),
              "w": rs.randn(3, 5).astype(np.float32) * 0.1,
              "label": np.array([0, 1, 2, 0], np.float32)}
        handles = [_mk_ndarray(lib, xs[a]) for a in args]
        reqs = (ctypes.c_char_p * 3)(b"null", b"write", b"null")
        names = (ctypes.c_char_p * 3)(*[a.encode() for a in args])
        ex = vp()
        assert lib.MXExecutorBind(out2, 1, 0, 3, names,
                                  (vp * 3)(*handles), reqs, 0, None, None,
                                  ctypes.byref(ex)) == 0, _err(lib)
        assert lib.MXExecutorForward(ex, 1) == 0, _err(lib)
        on = u32()
        oh = ctypes.POINTER(vp)()
        assert lib.MXExecutorOutputs(ex, ctypes.byref(on),
                                     ctypes.byref(oh)) == 0
        probs = _to_numpy(lib, oh[0])
        np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-5)
        assert lib.MXExecutorBackward(ex, 0, None) == 0, _err(lib)
        gw = vp()
        assert lib.MXExecutorArgGrad(ex, b"w", ctypes.byref(gw)) == 0
        grad = _to_numpy(lib, gw)
        assert np.isfinite(grad).all() and np.abs(grad).sum() > 0
        # SGD update through the imperative ABI
        wh = handles[1]
        before = _to_numpy(lib, wh)
        num_out = ctypes.c_int(1)
        outp = (vp * 1)(wh)
        outs_pp = ctypes.cast(outp, ctypes.POINTER(vp))
        k = (ctypes.c_char_p * 1)(b"lr")
        v = (ctypes.c_char_p * 1)(b"0.1")
        assert lib.MXImperativeInvokeEx(b"sgd_update", 2, (vp * 2)(wh, gw),
                                        ctypes.byref(num_out),
                                        ctypes.byref(outs_pp), 1, k,
                                        v) == 0, _err(lib)
        after = _to_numpy(lib, wh)
        assert not np.allclose(before, after)
        lib.MXExecutorFree(ex)

    def test_misc(self):
        lib = _lib()
        ver = ctypes.c_int()
        assert lib.MXGetVersion(ctypes.byref(ver)) == 0
        assert ver.value > 0
        n = u32()
        strs = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXListAllOpNames(ctypes.byref(n),
                                    ctypes.byref(strs)) == 0
        names = {strs[i].decode() for i in range(n.value)}
        assert "FullyConnected" in names and len(names) > 300


_C_MAIN = r"""
// Standalone C program: train LeNet ONE STEP end-to-end via the ABI.
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <math.h>

typedef void* H;
typedef unsigned int mx_uint;
extern const char* MXGetLastError();
extern int MXNDArrayCreateEx(const mx_uint*, mx_uint, int, int, int, int,
                             H*);
extern int MXNDArraySyncCopyFromCPU(H, const void*, size_t);
extern int MXNDArraySyncCopyToCPU(H, void*, size_t);
extern int MXSymbolCreateVariable(const char*, H*);
extern int MXSymbolCreateOp(const char*, mx_uint, const char**,
                            const char**, mx_uint, H*, const char*, H*);
extern int MXSymbolListArguments(H, mx_uint*, const char***);
extern int MXExecutorBind(H, int, int, mx_uint, const char**, H*,
                          const char**, mx_uint, const char**, H*, H*);
extern int MXExecutorForward(H, int);
extern int MXExecutorBackward(H, mx_uint, H*);
extern int MXExecutorOutputs(H, mx_uint*, H**);
extern int MXExecutorArgGrad(H, const char*, H*);
extern int MXImperativeInvokeEx(const char*, int, H*, int*, H**, int,
                                const char**, const char**);
extern int MXNDArrayCreateSparseEx(int, const mx_uint*, mx_uint, int, int,
                                   int, int, mx_uint, int*, mx_uint*,
                                   const mx_uint*, H*);
extern int MXNDArrayGetStorageType(H, int*);
extern int MXNDArraySyncCopyFromNDArray(H, const H, const int);
extern int MXNDArraySyncCheckFormat(H, const int);
extern int MXKVStoreCreate(const char*, H*);
extern int MXKVStoreInit(H, mx_uint, const int*, H*);
extern int MXKVStorePush(H, mx_uint, const int*, H*, int);
extern int MXKVStorePull(H, mx_uint, const int*, H*, int);

#define CHECK(x) if ((x) != 0) { \
  fprintf(stderr, "FAIL %s: %s\n", #x, MXGetLastError()); return 1; }

static H nd(const mx_uint* shape, mx_uint ndim, const float* data,
            size_t n) {
  H h = NULL;
  if (MXNDArrayCreateEx(shape, ndim, 1, 0, 0, 0, &h) != 0) return NULL;
  if (data && MXNDArraySyncCopyFromCPU(h, data, n * 4) != 0) return NULL;
  return h;
}

int main(void) {
  // LeNet-ish: conv(8@5x5) -> tanh -> maxpool2 -> fc10 -> softmax
  H data, c1w, c1b, fcw, fcb, label;
  CHECK(MXSymbolCreateVariable("data", &data));
  CHECK(MXSymbolCreateVariable("c1w", &c1w));
  CHECK(MXSymbolCreateVariable("c1b", &c1b));
  CHECK(MXSymbolCreateVariable("fcw", &fcw));
  CHECK(MXSymbolCreateVariable("fcb", &fcb));
  CHECK(MXSymbolCreateVariable("label", &label));

  const char* ck[2] = {"kernel", "num_filter"};
  const char* cv[2] = {"(5, 5)", "8"};
  H conv, act, pool, fc, net;
  H cin[3] = {data, c1w, c1b};
  CHECK(MXSymbolCreateOp("Convolution", 2, ck, cv, 3, cin, "c1", &conv));
  const char* ak[1] = {"act_type"};
  const char* av[1] = {"tanh"};
  CHECK(MXSymbolCreateOp("Activation", 1, ak, av, 1, &conv, "a1", &act));
  const char* pk[3] = {"pool_type", "kernel", "stride"};
  const char* pv[3] = {"max", "(2, 2)", "(2, 2)"};
  CHECK(MXSymbolCreateOp("Pooling", 3, pk, pv, 1, &act, "p1", &pool));
  const char* fk[1] = {"num_hidden"};
  const char* fv[1] = {"10"};
  H fin[3] = {pool, fcw, fcb};
  CHECK(MXSymbolCreateOp("FullyConnected", 1, fk, fv, 3, fin, "fc", &fc));
  H sin[2] = {fc, label};
  CHECK(MXSymbolCreateOp("SoftmaxOutput", 0, NULL, NULL, 2, sin, "sm",
                         &net));

  mx_uint nargs = 0;
  const char** argnames = NULL;
  CHECK(MXSymbolListArguments(net, &nargs, &argnames));
  if (nargs != 6) { fprintf(stderr, "args %u\n", nargs); return 1; }

  // shapes: data(4,1,28,28) c1w(8,1,5,5) c1b(8) fcw(10,1152) fcb(10)
  mx_uint sh_data[4] = {4, 1, 28, 28};
  mx_uint sh_c1w[4] = {8, 1, 5, 5};
  mx_uint sh_c1b[1] = {8};
  mx_uint sh_fcw[2] = {10, 8 * 12 * 12};
  mx_uint sh_fcb[1] = {10};
  mx_uint sh_lab[1] = {4};
  float xbuf[4 * 28 * 28], wbuf[10 * 1152], lbuf[4] = {0, 1, 2, 3};
  unsigned seed = 42;
  for (size_t i = 0; i < sizeof(xbuf) / 4; ++i) {
    seed = seed * 1664525u + 1013904223u;
    xbuf[i] = ((float)(seed >> 8) / 16777216.0f - 0.5f);
  }
  for (size_t i = 0; i < sizeof(wbuf) / 4; ++i) {
    seed = seed * 1664525u + 1013904223u;
    wbuf[i] = ((float)(seed >> 8) / 16777216.0f - 0.5f) * 0.1f;
  }
  float cwbuf[8 * 25];
  for (size_t i = 0; i < 200; ++i) cwbuf[i] = wbuf[i] * 0.5f;
  float zeros[1152] = {0};

  H h_data = nd(sh_data, 4, xbuf, 4 * 28 * 28);
  H h_c1w = nd(sh_c1w, 4, cwbuf, 200);
  H h_c1b = nd(sh_c1b, 1, zeros, 8);
  H h_fcw = nd(sh_fcw, 2, wbuf, 10 * 1152);
  H h_fcb = nd(sh_fcb, 1, zeros, 10);
  H h_lab = nd(sh_lab, 1, lbuf, 4);
  if (!h_data || !h_c1w || !h_c1b || !h_fcw || !h_fcb || !h_lab) {
    fprintf(stderr, "nd: %s\n", MXGetLastError());
    return 1;
  }

  const char* names[6] = {"data", "c1w", "c1b", "fcw", "fcb", "label"};
  H arrs[6] = {h_data, h_c1w, h_c1b, h_fcw, h_fcb, h_lab};
  const char* reqs[6] = {"null", "write", "write", "write", "write",
                         "null"};
  H ex = NULL;
  CHECK(MXExecutorBind(net, 1, 0, 6, names, arrs, reqs, 0, NULL, NULL,
                       &ex));
  CHECK(MXExecutorForward(ex, 1));
  mx_uint nout = 0;
  H* outs = NULL;
  CHECK(MXExecutorOutputs(ex, &nout, &outs));
  float probs[40];
  CHECK(MXNDArraySyncCopyToCPU(outs[0], probs, sizeof(probs)));
  float loss0 = 0;
  for (int r = 0; r < 4; ++r) loss0 -= logf(probs[r * 10 + (int)lbuf[r]]);
  CHECK(MXExecutorBackward(ex, 0, NULL));

  // SGD step on every weight through the imperative ABI
  const char* wnames[4] = {"c1w", "c1b", "fcw", "fcb"};
  H warrs[4] = {h_c1w, h_c1b, h_fcw, h_fcb};
  for (int i = 0; i < 4; ++i) {
    H g = NULL;
    CHECK(MXExecutorArgGrad(ex, wnames[i], &g));
    if (!g) { fprintf(stderr, "no grad %s\n", wnames[i]); return 1; }
    H ins[2] = {warrs[i], g};
    int no = 1;
    H outbuf[1] = {warrs[i]};
    H* op = outbuf;
    const char* k[1] = {"lr"};
    const char* v[1] = {"0.5"};
    CHECK(MXImperativeInvokeEx("sgd_update", 2, ins, &no, &op, 1, k, v));
  }

  // loss after one step must decrease on the same batch
  CHECK(MXExecutorForward(ex, 1));
  CHECK(MXExecutorOutputs(ex, &nout, &outs));
  CHECK(MXNDArraySyncCopyToCPU(outs[0], probs, sizeof(probs)));
  float loss1 = 0;
  for (int r = 0; r < 4; ++r) loss1 -= logf(probs[r * 10 + (int)lbuf[r]]);
  printf("loss %.6f -> %.6f\n", loss0, loss1);
  if (!(loss1 < loss0)) { fprintf(stderr, "no improvement\n"); return 1; }

  // ---- sparse path (round-5): build a row_sparse gradient in C, push
  // it through the kvstore, pull the dense result back -----------------
  mx_uint sh_sp[2] = {4, 3};
  H hsp = NULL;
  CHECK(MXNDArrayCreateSparseEx(1, sh_sp, 2, 1, 0, 0, 0, 1, NULL, NULL,
                                NULL, &hsp));
  int stype = -9;
  CHECK(MXNDArrayGetStorageType(hsp, &stype));
  if (stype != 1) { fprintf(stderr, "stype %d\n", stype); return 1; }
  float spdata[6] = {1, 2, 3, 4, 5, 6};
  float spidx[2] = {1, 3};
  mx_uint sh_d[2] = {2, 3};
  mx_uint sh_i[1] = {2};
  H hd = nd(sh_d, 2, spdata, 6);
  H hi = nd(sh_i, 1, spidx, 2);
  CHECK(MXNDArraySyncCopyFromNDArray(hsp, hd, -1));
  CHECK(MXNDArraySyncCopyFromNDArray(hsp, hi, 0));
  CHECK(MXNDArraySyncCheckFormat(hsp, 1));
  H kv = NULL;
  CHECK(MXKVStoreCreate("local", &kv));
  int kvkeys[1] = {3};
  float zero12[12] = {0};
  H hw = nd(sh_sp, 2, zero12, 12);
  CHECK(MXKVStoreInit(kv, 1, kvkeys, &hw));
  CHECK(MXKVStorePush(kv, 1, kvkeys, &hsp, 0));
  H hout = nd(sh_sp, 2, zero12, 12);
  CHECK(MXKVStorePull(kv, 1, kvkeys, &hout, 0));
  float dense[12];
  CHECK(MXNDArraySyncCopyToCPU(hout, dense, sizeof(dense)));
  float want[12] = {0, 0, 0, 1, 2, 3, 0, 0, 0, 4, 5, 6};
  for (int i = 0; i < 12; ++i) {
    if (fabsf(dense[i] - want[i]) > 1e-6f) {
      fprintf(stderr, "sparse mismatch @%d: %f\n", i, dense[i]);
      return 1;
    }
  }
  printf("C-SPARSE-OK\n");
  printf("C-TRAIN-OK\n");
  return 0;
}
"""


@needs_lib
def test_standalone_c_training(tmp_path):
    """A fresh C process (embedding its own interpreter) composes LeNet,
    binds, runs fwd/bwd, applies SGD, and sees the loss decrease."""
    csrc = tmp_path / "train.c"
    csrc.write_text(_C_MAIN)
    exe = tmp_path / "train"
    cfg = subprocess.run(
        [sys.executable, "-c",
         "import sysconfig;v=sysconfig.get_config_vars();"
         "print(v.get('LIBDIR',''));print(v['LDVERSION'])"],
        capture_output=True, text=True, check=True).stdout.split()
    libdir, ldver = cfg[0], cfg[1]
    subprocess.run(
        ["gcc", str(csrc), "-o", str(exe), "-L",
         os.path.dirname(_LIB), "-lmxnet_tpu_c",
         f"-L{libdir}", f"-lpython{ldver}", "-lm",
         f"-Wl,-rpath,{os.path.dirname(_LIB)}", f"-Wl,-rpath,{libdir}"],
        check=True, capture_output=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([str(exe)], capture_output=True, text=True,
                      timeout=300, env=env)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "C-TRAIN-OK" in r.stdout
    assert "C-SPARSE-OK" in r.stdout


@needs_lib
class TestCtypesRound4:
    """Round-4 C API surface: views, infer-shape, cached op, data iter,
    RecordIO, profiler (parity: reference c_api.h MXNDArraySlice:699,
    MXSymbolInferShape:1482, MXCreateCachedOpEx:1376, MXDataIter*:2195+,
    MXRecordIO*:2283+, MXSetProcessProfilerConfig)."""

    def test_views_and_context(self):
        lib = _lib()
        x = np.arange(24, dtype=np.float32).reshape(6, 4)
        h = _mk_ndarray(lib, x)
        out = vp()
        dims = (ctypes.c_int * 2)(3, 8)
        assert lib.MXNDArrayReshape(h, 2, dims, ctypes.byref(out)) == 0
        np.testing.assert_allclose(_to_numpy(lib, out), x.reshape(3, 8))
        sl = vp()
        assert lib.MXNDArraySlice(h, 1, 3, ctypes.byref(sl)) == 0
        np.testing.assert_allclose(_to_numpy(lib, sl), x[1:3])
        at = vp()
        assert lib.MXNDArrayAt(h, 2, ctypes.byref(at)) == 0
        np.testing.assert_allclose(_to_numpy(lib, at), x[2])
        dt, di = ctypes.c_int(), ctypes.c_int()
        assert lib.MXNDArrayGetContext(h, ctypes.byref(dt),
                                       ctypes.byref(di)) == 0
        assert dt.value in (1, 6)  # cpu or tpu
        assert lib.MXRandomSeed(7) == 0
        for hh in (h, out, sl, at):
            lib.MXNDArrayFree(hh)

    def test_infer_shape(self):
        lib = _lib()
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        fc = vp()
        k = (ctypes.c_char_p * 1)(b"num_hidden")
        v = (ctypes.c_char_p * 1)(b"8")
        ins = (vp * 1)(x)
        assert lib.MXSymbolCreateOp(b"FullyConnected", 1, k, v, 1, ins,
                                    b"fc", ctypes.byref(fc)) == 0, _err(lib)
        ind = (u32 * 2)(0, 2)
        sdata = (u32 * 2)(5, 3)
        keys = (ctypes.c_char_p * 1)(b"x")
        u32p_t = ctypes.POINTER(u32)
        iss, oss, ass_ = u32(), u32(), u32()
        isn, osn, asn = u32p_t(), u32p_t(), u32p_t()
        isd = ctypes.POINTER(u32p_t)()
        osd = ctypes.POINTER(u32p_t)()
        asd = ctypes.POINTER(u32p_t)()
        comp = ctypes.c_int()
        rc = lib.MXSymbolInferShape(
            fc, 1, keys, ind, sdata,
            ctypes.byref(iss), ctypes.byref(isn), ctypes.byref(isd),
            ctypes.byref(oss), ctypes.byref(osn), ctypes.byref(osd),
            ctypes.byref(ass_), ctypes.byref(asn), ctypes.byref(asd),
            ctypes.byref(comp))
        assert rc == 0, _err(lib)
        outs = [tuple(osd[i][j] for j in range(osn[i]))
                for i in range(oss.value)]
        assert outs == [(5, 8)], outs
        args_shapes = [tuple(isd[i][j] for j in range(isn[i]))
                       for i in range(iss.value)]
        assert (5, 3) in args_shapes and (8, 3) in args_shapes
        assert comp.value == 1

    def test_cached_op(self):
        lib = _lib()
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        act = vp()
        k = (ctypes.c_char_p * 1)(b"act_type")
        v = (ctypes.c_char_p * 1)(b"relu")
        ins = (vp * 1)(x)
        assert lib.MXSymbolCreateOp(b"Activation", 1, k, v, 1, ins, b"a",
                                    ctypes.byref(act)) == 0, _err(lib)
        co = vp()
        assert lib.MXCreateCachedOp(act, ctypes.byref(co)) == 0, _err(lib)
        data = np.array([[-1.0, 2.0], [3.0, -4.0]], np.float32)
        h = _mk_ndarray(lib, data)
        inh = (vp * 1)(h)
        nout = ctypes.c_int(0)
        outs = ctypes.POINTER(vp)()
        for _ in range(2):  # second call hits the executor cache
            assert lib.MXInvokeCachedOp(co, 1, inh, ctypes.byref(nout),
                                        ctypes.byref(outs)) == 0, _err(lib)
            np.testing.assert_allclose(_to_numpy(lib, outs[0]),
                                       np.maximum(data, 0))
        # cache-hit with a DIFFERENT input handle must not mutate the
        # first input (the executor binds slot copies, not caller arrays)
        data2 = -data
        h2 = _mk_ndarray(lib, data2)
        inh2 = (vp * 1)(h2)
        assert lib.MXInvokeCachedOp(co, 1, inh2, ctypes.byref(nout),
                                    ctypes.byref(outs)) == 0, _err(lib)
        np.testing.assert_allclose(_to_numpy(lib, outs[0]),
                                   np.maximum(data2, 0))
        np.testing.assert_allclose(_to_numpy(lib, h), data)  # unharmed
        assert lib.MXFreeCachedOp(co) == 0

    def test_data_iter(self, tmp_path):
        lib = _lib()
        n = u32()
        arr = cpp_t = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXListDataIters(ctypes.byref(n), ctypes.byref(arr)) == 0
        names = [arr[i].decode() for i in range(n.value)]
        assert "CSVIter" in names and "LibSVMIter" in names
        csv = tmp_path / "d.csv"
        np.savetxt(csv, np.arange(24, dtype=np.float32).reshape(6, 4),
                   delimiter=",")
        it = vp()
        keys = (ctypes.c_char_p * 3)(b"data_csv", b"data_shape",
                                     b"batch_size")
        vals = (ctypes.c_char_p * 3)(str(csv).encode(), b"(4,)", b"2")
        assert lib.MXDataIterCreateIter(b"CSVIter", 3, keys, vals,
                                        ctypes.byref(it)) == 0, _err(lib)
        for _pass in range(2):  # second pass after BeforeFirst
            seen = []
            has = ctypes.c_int()
            while True:
                assert lib.MXDataIterNext(it, ctypes.byref(has)) == 0
                if not has.value:
                    break
                d = vp()
                assert lib.MXDataIterGetData(it, ctypes.byref(d)) == 0
                seen.append(_to_numpy(lib, d))
                pad = ctypes.c_int()
                assert lib.MXDataIterGetPadNum(it,
                                               ctypes.byref(pad)) == 0
                lib.MXNDArrayFree(d)
            got = np.concatenate(seen)
            np.testing.assert_allclose(
                got, np.arange(24, dtype=np.float32).reshape(6, 4))
            assert lib.MXDataIterBeforeFirst(it) == 0
        assert lib.MXDataIterFree(it) == 0

    def test_recordio_roundtrip(self, tmp_path):
        lib = _lib()
        rec = str(tmp_path / "t.rec").encode()
        w = vp()
        assert lib.MXRecordIOWriterCreate(rec, ctypes.byref(w)) == 0
        payloads = [b"hello", b"tpu world", b"x" * 1000]
        for p in payloads:
            assert lib.MXRecordIOWriterWriteRecord(w, p, len(p)) == 0
        assert lib.MXRecordIOWriterFree(w) == 0
        r = vp()
        assert lib.MXRecordIOReaderCreate(rec, ctypes.byref(r)) == 0
        buf = ctypes.c_char_p()
        sz = ctypes.c_size_t()
        got = []
        while True:
            assert lib.MXRecordIOReaderReadRecord(
                r, ctypes.byref(buf), ctypes.byref(sz)) == 0
            if not buf.value and sz.value == 0:
                break
            got.append(ctypes.string_at(buf, sz.value))
        assert got == payloads
        assert lib.MXRecordIOReaderFree(r) == 0
        # python reader agrees (format compatibility)
        from mxnet_tpu.recordio import MXRecordIO
        rd = MXRecordIO(rec.decode(), "r")
        assert [rd.read() for _ in range(3)] == payloads
        rd.close()

    def test_profiler(self, tmp_path):
        lib = _lib()
        keys = (ctypes.c_char_p * 2)(b"aggregate_stats", b"filename")
        fname = str(tmp_path / "p.json").encode()
        vals = (ctypes.c_char_p * 2)(b"1", fname)
        assert lib.MXSetProcessProfilerConfig(2, keys, vals) == 0, \
            _err(lib)
        assert lib.MXSetProcessProfilerState(1) == 0
        # run one op so something is recorded
        h = _mk_ndarray(lib, np.ones((4, 4), np.float32))
        outs = ctypes.POINTER(vp)()
        nout = ctypes.c_int(0)
        assert lib.MXImperativeInvokeEx(b"relu", 1, (vp * 1)(h),
                                        ctypes.byref(nout),
                                        ctypes.byref(outs), 0, None,
                                        None) == 0, _err(lib)
        assert lib.MXSetProcessProfilerState(0) == 0
        stats = ctypes.c_char_p()
        assert lib.MXAggregateProfileStatsPrint(ctypes.byref(stats),
                                                1) == 0
        assert stats.value is not None
        assert lib.MXDumpProcessProfile(1) == 0
        assert os.path.exists(fname)


@needs_lib
class TestCtypesRound4b:
    """Second C-API widening batch: infer-type, symbol attrs/views,
    executor reshape, string-key kvstore, raw-bytes serde, device count
    (reference c_api.h MXSymbolInferType:1553, MXSymbolGetAttr,
    MXExecutorReshapeEx, MXKVStoreInitEx:1714+, MXNDArraySaveRawBytes)."""

    def _fc(self, lib):
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        fc = vp()
        k = (ctypes.c_char_p * 1)(b"num_hidden")
        v = (ctypes.c_char_p * 1)(b"8")
        assert lib.MXSymbolCreateOp(b"FullyConnected", 1, k, v, 1,
                                    (vp * 1)(x), b"fc",
                                    ctypes.byref(fc)) == 0, _err(lib)
        return fc

    def test_infer_type(self):
        lib = _lib()
        intp = ctypes.POINTER(ctypes.c_int)
        lib.MXSymbolInferType.argtypes = [
            vp, u32, ctypes.POINTER(ctypes.c_char_p), intp,
            ctypes.POINTER(u32), ctypes.POINTER(intp),
            ctypes.POINTER(u32), ctypes.POINTER(intp),
            ctypes.POINTER(u32), ctypes.POINTER(intp),
            intp]
        fc = self._fc(lib)
        keys = (ctypes.c_char_p * 1)(b"x")
        codes = (ctypes.c_int * 1)(0)  # float32
        iss, oss, ass_ = u32(), u32(), u32()
        isd, osd, asd = intp(), intp(), intp()
        comp = ctypes.c_int()
        assert lib.MXSymbolInferType(
            fc, 1, keys, codes,
            ctypes.byref(iss), ctypes.byref(isd),
            ctypes.byref(oss), ctypes.byref(osd),
            ctypes.byref(ass_), ctypes.byref(asd),
            ctypes.byref(comp)) == 0, _err(lib)
        assert comp.value == 1
        assert [isd[i] for i in range(iss.value)].count(0) == iss.value
        assert osd[0] == 0  # float32 output

    def test_symbol_attrs_and_views(self):
        lib = _lib()
        lib.MXSymbolGetAttr.argtypes = [vp, ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_char_p),
                                        ctypes.POINTER(ctypes.c_int)]
        lib.MXSymbolSetAttr.argtypes = [vp, ctypes.c_char_p,
                                        ctypes.c_char_p]
        lib.MXSymbolGetInternals.argtypes = [vp, vpp_t()]
        lib.MXSymbolGetOutput.argtypes = [vp, u32, vpp_t()]
        fc = self._fc(lib)
        out = ctypes.c_char_p()
        ok = ctypes.c_int()
        assert lib.MXSymbolGetAttr(fc, b"ctx_group", ctypes.byref(out),
                                   ctypes.byref(ok)) == 0
        assert ok.value == 0
        assert lib.MXSymbolSetAttr(fc, b"ctx_group", b"dev1") == 0
        assert lib.MXSymbolGetAttr(fc, b"ctx_group", ctypes.byref(out),
                                   ctypes.byref(ok)) == 0
        assert ok.value == 1 and out.value == b"dev1"
        internals = vp()
        assert lib.MXSymbolGetInternals(fc, ctypes.byref(internals)) == 0
        n = u32()
        arr = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXSymbolListOutputs(internals, ctypes.byref(n),
                                       ctypes.byref(arr)) == 0
        names = [arr[i].decode() for i in range(n.value)]
        assert any("fc" in s for s in names), names
        first = vp()
        assert lib.MXSymbolGetOutput(internals, 0,
                                     ctypes.byref(first)) == 0, _err(lib)

    def test_executor_reshape(self):
        lib = _lib()
        u32p_t = ctypes.POINTER(u32)
        lib.MXExecutorReshape.argtypes = [
            vp, ctypes.c_int, ctypes.c_int, u32,
            ctypes.POINTER(ctypes.c_char_p), u32p_t, u32p_t, vpp_t()]
        fc = self._fc(lib)
        # bind at batch 4
        x = _mk_ndarray(lib, np.ones((4, 3), np.float32))
        w = _mk_ndarray(lib, np.ones((8, 3), np.float32) * 0.5)
        b = _mk_ndarray(lib, np.zeros((8,), np.float32))
        names = (ctypes.c_char_p * 3)(b"x", b"fc_weight", b"fc_bias")
        arrs = (vp * 3)(x, w, b)
        reqs = (ctypes.c_char_p * 3)(b"null", b"null", b"null")
        ex = vp()
        assert lib.MXExecutorBind(fc, 1, 0, 3, names, arrs, reqs, 0,
                                  None, None, ctypes.byref(ex)) == 0, \
            _err(lib)
        # reshape x to batch 6
        ind = (u32 * 2)(0, 2)
        sdata = (u32 * 2)(6, 3)
        keys = (ctypes.c_char_p * 1)(b"x")
        ex2 = vp()
        assert lib.MXExecutorReshape(ex, 0, 1, 1, keys, ind, sdata,
                                     ctypes.byref(ex2)) == 0, _err(lib)
        assert lib.MXExecutorForward(ex2, 0) == 0, _err(lib)
        nout = u32()
        outs = ctypes.POINTER(vp)()
        assert lib.MXExecutorOutputs(ex2, ctypes.byref(nout),
                                     ctypes.byref(outs)) == 0
        got = _to_numpy(lib, outs[0])
        # resized args get FRESH (zero) data arrays; only params are
        # shared (the reference reshape/bucketing contract) — so the
        # output is bias-only zeros at the new batch size
        assert got.shape == (6, 8), got.shape
        np.testing.assert_allclose(got, 0.0)
        # the original executor still works at its own batch size
        assert lib.MXExecutorForward(ex, 0) == 0, _err(lib)
        assert lib.MXExecutorOutputs(ex, ctypes.byref(nout),
                                     ctypes.byref(outs)) == 0
        np.testing.assert_allclose(_to_numpy(lib, outs[0]), 1.5)

    def test_kvstore_string_keys(self):
        lib = _lib()
        cpp_t2 = ctypes.POINTER(ctypes.c_char_p)
        lib.MXKVStoreInitEx.argtypes = [vp, u32, cpp_t2, vpp_t()]
        lib.MXKVStorePushEx.argtypes = [vp, u32, cpp_t2, vpp_t(),
                                        ctypes.c_int]
        lib.MXKVStorePullEx.argtypes = [vp, u32, cpp_t2, vpp_t(),
                                        ctypes.c_int]
        kv = vp()
        assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
        keys = (ctypes.c_char_p * 1)(b"weight")
        val = _mk_ndarray(lib, np.full((4,), 2.0, np.float32))
        assert lib.MXKVStoreInitEx(kv, 1, keys, (vp * 1)(val)) == 0, \
            _err(lib)
        grad = _mk_ndarray(lib, np.ones((4,), np.float32))
        assert lib.MXKVStorePushEx(kv, 1, keys, (vp * 1)(grad), 0) == 0
        out = _mk_ndarray(lib, np.zeros((4,), np.float32))
        assert lib.MXKVStorePullEx(kv, 1, keys, (vp * 1)(out), 0) == 0
        # local kvstore without an updater: push REPLACES the stored
        # value (reference KVStoreLocal contract)
        np.testing.assert_allclose(_to_numpy(lib, out), 1.0)
        lib.MXKVStoreFree(kv)

    def test_raw_bytes_roundtrip(self):
        lib = _lib()
        lib.MXNDArraySaveRawBytes.argtypes = [
            vp, ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p)]
        lib.MXNDArrayLoadFromRawBytes.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, vpp_t()]
        x = np.random.RandomState(3).randn(3, 5).astype(np.float32)
        h = _mk_ndarray(lib, x)
        size = ctypes.c_size_t()
        buf = ctypes.c_char_p()
        assert lib.MXNDArraySaveRawBytes(h, ctypes.byref(size),
                                         ctypes.byref(buf)) == 0, _err(lib)
        raw = ctypes.string_at(buf, size.value)
        h2 = vp()
        assert lib.MXNDArrayLoadFromRawBytes(raw, len(raw),
                                             ctypes.byref(h2)) == 0, \
            _err(lib)
        np.testing.assert_allclose(_to_numpy(lib, h2), x)

    def test_gpu_count(self):
        lib = _lib()
        lib.MXGetGPUCount.argtypes = [ctypes.POINTER(ctypes.c_int)]
        n = ctypes.c_int(-1)
        assert lib.MXGetGPUCount(ctypes.byref(n)) == 0
        assert n.value >= 0


def vpp_t():
    return ctypes.POINTER(vp)


@needs_lib
class TestRound5Groups:
    """Sparse NDArray, C updaters, executor monitor, MXCustomOpRegister
    (VERDICT r4 item 5; reference c_api.h:577+, 2170, 2503, 2745)."""

    def _lib5(self):
        lib = _lib()
        u32p = ctypes.POINTER(u32)
        vpp = ctypes.POINTER(vp)
        intp = ctypes.POINTER(ctypes.c_int)
        lib.MXNDArrayCreateSparseEx.argtypes = [
            ctypes.c_int, u32p, u32, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, u32, intp, u32p, u32p, vpp]
        lib.MXNDArrayGetStorageType.argtypes = [vp, intp]
        lib.MXNDArraySyncCopyFromNDArray.argtypes = [vp, vp, ctypes.c_int]
        lib.MXNDArraySyncCheckFormat.argtypes = [vp, ctypes.c_bool]
        lib.MXNDArrayGetAuxType.argtypes = [vp, u32, intp]
        lib.MXNDArrayGetAuxNDArray.argtypes = [vp, u32, vpp]
        lib.MXNDArrayGetDataNDArray.argtypes = [vp, vpp]
        lib.MXKVStoreSetUpdater.argtypes = [vp, vp, vp]
        lib.MXExecutorSetMonitorCallbackEX.argtypes = [vp, vp, vp,
                                                       ctypes.c_bool]
        lib.MXCustomOpRegister.argtypes = [vp, vp]
        return lib

    def test_sparse_row_sparse_create_fill_read(self):
        lib = self._lib5()
        shape = (u32 * 2)(4, 3)
        h = vp()
        rc = lib.MXNDArrayCreateSparseEx(1, shape, 2, 1, 0, 0, 0, 1,
                                         None, None, None,
                                         ctypes.byref(h))
        assert rc == 0, _err(lib)
        st = ctypes.c_int()
        assert lib.MXNDArrayGetStorageType(h, ctypes.byref(st)) == 0
        assert st.value == 1  # row_sparse
        data = np.array([[1, 2, 3], [4, 5, 6]], np.float32)
        idx = np.array([1, 3], np.float32)  # cast to int32 by the aux copy
        hd, hi = _mk_ndarray(lib, data), _mk_ndarray(lib, idx)
        assert lib.MXNDArraySyncCopyFromNDArray(h, hd, -1) == 0, _err(lib)
        assert lib.MXNDArraySyncCopyFromNDArray(h, hi, 0) == 0, _err(lib)
        assert lib.MXNDArraySyncCheckFormat(h, True) == 0, _err(lib)
        dense = np.zeros((4, 3), np.float32)
        dense[[1, 3]] = data
        np.testing.assert_allclose(_to_numpy(lib, h), dense)
        # aux/data accessors give dense copies
        at = ctypes.c_int()
        assert lib.MXNDArrayGetAuxType(h, 0, ctypes.byref(at)) == 0
        assert at.value == 4  # int32 (documented narrowing from int64)
        ha, hda = vp(), vp()
        assert lib.MXNDArrayGetAuxNDArray(h, 0, ctypes.byref(ha)) == 0
        assert lib.MXNDArrayGetDataNDArray(h, ctypes.byref(hda)) == 0
        np.testing.assert_allclose(_to_numpy(lib, hda), data)
        # malformed indices (unsorted) must fail the full check
        hbad = _mk_ndarray(lib, np.array([3, 1], np.float32))
        assert lib.MXNDArraySyncCopyFromNDArray(h, hbad, 0) == 0
        assert lib.MXNDArraySyncCheckFormat(h, True) != 0
        for x in (h, hd, hi, ha, hda, hbad):
            lib.MXNDArrayFree(x)

    def test_sparse_csr_create_fill_read(self):
        lib = self._lib5()
        shape = (u32 * 2)(3, 4)
        h = vp()
        assert lib.MXNDArrayCreateSparseEx(2, shape, 2, 1, 0, 0, 0, 2,
                                           None, None, None,
                                           ctypes.byref(h)) == 0, _err(lib)
        st = ctypes.c_int()
        lib.MXNDArrayGetStorageType(h, ctypes.byref(st))
        assert st.value == 2  # csr
        data = np.array([1.0, 2.0, 3.0], np.float32)
        indptr = np.array([0, 2, 3, 3], np.float32)
        indices = np.array([0, 2, 1], np.float32)
        hd = _mk_ndarray(lib, data)
        hp = _mk_ndarray(lib, indptr)
        hi = _mk_ndarray(lib, indices)
        assert lib.MXNDArraySyncCopyFromNDArray(h, hd, -1) == 0, _err(lib)
        assert lib.MXNDArraySyncCopyFromNDArray(h, hp, 0) == 0, _err(lib)
        assert lib.MXNDArraySyncCopyFromNDArray(h, hi, 1) == 0, _err(lib)
        assert lib.MXNDArraySyncCheckFormat(h, True) == 0, _err(lib)
        dense = np.array([[1, 0, 2, 0], [0, 3, 0, 0], [0, 0, 0, 0]],
                         np.float32)
        np.testing.assert_allclose(_to_numpy(lib, h), dense)
        for x in (h, hd, hp, hi):
            lib.MXNDArrayFree(x)

    def test_kvstore_c_updater(self):
        lib = self._lib5()
        kv = vp()
        assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
        w0 = _mk_ndarray(lib, np.full((4,), 10.0, np.float32))
        keys = (ctypes.c_int * 1)(7)
        assert lib.MXKVStoreInit(kv, 1, keys, (vp * 1)(w0)) == 0, _err(lib)

        UPD = ctypes.CFUNCTYPE(None, ctypes.c_int, vp, vp, vp)
        seen = []

        @UPD
        def updater(key, recv, local, _ctx):
            # SGD-style: local -= 0.5 * recv, through the C API itself
            seen.append(key)
            num_out = ctypes.c_int(0)
            outs = ctypes.POINTER(vp)()
            k = (ctypes.c_char_p * 1)(b"scalar")
            v = (ctypes.c_char_p * 1)(b"0.5")
            rc = lib.MXImperativeInvokeEx(b"_mul_scalar", 1, (vp * 1)(recv),
                                          ctypes.byref(num_out),
                                          ctypes.byref(outs), 1, k, v)
            assert rc == 0, _err(lib)
            scaled = outs[0]
            out_arr = (vp * 1)(local)
            outp = ctypes.cast(out_arr, ctypes.POINTER(vp))
            n2 = ctypes.c_int(1)
            rc = lib.MXImperativeInvokeEx(
                b"elemwise_sub", 2, (vp * 2)(local, scaled),
                ctypes.byref(n2), ctypes.byref(outp), 0, None, None)
            assert rc == 0, _err(lib)
            lib.MXNDArrayFree(scaled)

        assert lib.MXKVStoreSetUpdater(
            kv, ctypes.cast(updater, vp), None) == 0, _err(lib)
        g = _mk_ndarray(lib, np.full((4,), 2.0, np.float32))
        assert lib.MXKVStorePush(kv, 1, keys, (vp * 1)(g), 0) == 0, _err(lib)
        out = _mk_ndarray(lib, np.zeros((4,), np.float32))
        assert lib.MXKVStorePull(kv, 1, keys, (vp * 1)(out), 0) == 0
        np.testing.assert_allclose(_to_numpy(lib, out), 9.0)  # 10 - 0.5*2
        assert seen == [7]
        for x in (w0, g, out):
            lib.MXNDArrayFree(x)
        lib.MXKVStoreFree(kv)

    def test_executor_monitor_callback(self):
        lib = self._lib5()
        var = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(var)) == 0
        sq = vp()
        assert lib.MXSymbolCreateOp(b"square", 0, None, None, 1,
                                    (vp * 1)(var), b"sq",
                                    ctypes.byref(sq)) == 0, _err(lib)
        x = _mk_ndarray(lib, np.full((2, 2), 3.0, np.float32))
        ex = vp()
        names = (ctypes.c_char_p * 1)(b"x")
        reqs = (ctypes.c_char_p * 1)(b"null")
        assert lib.MXExecutorBind(sq, 1, 0, 1, names, (vp * 1)(x),
                                  reqs, 0, None, None,
                                  ctypes.byref(ex)) == 0, _err(lib)
        MON = ctypes.CFUNCTYPE(None, ctypes.c_char_p, vp, vp)
        seen = []

        @MON
        def monitor(name, arr_handle, _ctx):
            seen.append((name.decode(), float(_to_numpy(lib,
                                                        arr_handle)[0, 0])))

        assert lib.MXExecutorSetMonitorCallbackEX(
            ex, ctypes.cast(monitor, vp), None, False) == 0, _err(lib)
        assert lib.MXExecutorForward(ex, 0) == 0, _err(lib)
        assert seen and any(v == 9.0 for _n, v in seen), seen
        lib.MXExecutorFree(ex)
        lib.MXNDArrayFree(x)

    def test_custom_op_register_full_protocol(self):
        lib = self._lib5()
        keep = []  # every callback/array the C side must keep alive

        GEN = ctypes.CFUNCTYPE(ctypes.c_int)
        LIST = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p)),
            vp)
        INFER = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int)), vp)
        CREATEOP = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint)),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            vp, vp)
        FB = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(vp),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.c_int, vp)
        CREATOR = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p), vp)

        class CBList(ctypes.Structure):
            _fields_ = [("num_callbacks", ctypes.c_int),
                        ("callbacks", ctypes.POINTER(GEN)),
                        ("contexts", ctypes.POINTER(vp))]

        def _scale(handle_in, handle_out, factor):
            """out = factor * in via the C API (what a real plugin does)."""
            k = (ctypes.c_char_p * 1)(b"scalar")
            v = (ctypes.c_char_p * 1)(str(factor).encode())
            out_arr = (vp * 1)(handle_out)
            outp = ctypes.cast(out_arr, ctypes.POINTER(vp))
            n = ctypes.c_int(1)
            rc = lib.MXImperativeInvokeEx(b"_mul_scalar", 1,
                                          (vp * 1)(handle_in),
                                          ctypes.byref(n),
                                          ctypes.byref(outp), 1, k, v)
            assert rc == 0, _err(lib)

        @LIST
        def list_args(out, _ctx):
            arr = (ctypes.c_char_p * 2)(b"data", None)
            keep.append(arr)
            out[0] = arr
            return 1

        @LIST
        def list_outs(out, _ctx):
            arr = (ctypes.c_char_p * 2)(b"output", None)
            keep.append(arr)
            out[0] = arr
            return 1

        @INFER
        def infer_shape(num_tensor, ndims, shapes, _ctx):
            # one input, one output: output shape = input shape
            ndims[1] = ndims[0]
            keep.append(shapes[0])
            shapes[1] = shapes[0]
            return 1

        @FB
        def forward(size, ptrs, tags, _reqs, _is_train, _state):
            ins = [ptrs[i] for i in range(size) if tags[i] == 0]
            outs = [ptrs[i] for i in range(size) if tags[i] == 1]
            _scale(ins[0], outs[0], 2.0)
            return 1

        @FB
        def backward(size, ptrs, tags, _reqs, _is_train, _state):
            ograds = [ptrs[i] for i in range(size) if tags[i] == 3]
            igrads = [ptrs[i] for i in range(size) if tags[i] == 2]
            _scale(ograds[0], igrads[0], 2.0)
            return 1

        @CREATEOP
        def create_op(_ctx_str, _n, _shapes, _ndims, _dtypes, ret, _state):
            cbs = (GEN * 3)(GEN(), ctypes.cast(forward, GEN),
                            ctypes.cast(backward, GEN))
            ctxs = (vp * 3)()
            keep.extend([cbs, ctxs])
            lst = ctypes.cast(ret, ctypes.POINTER(CBList))
            lst[0].num_callbacks = 3
            lst[0].callbacks = cbs
            lst[0].contexts = ctxs
            return 1

        @CREATOR
        def creator(_op_type, _nk, _keys, _vals, ret):
            # CustomOpPropCallbacks order: del, list_args, list_outs,
            # list_aux, infer_shape, bwd_dep, create_operator
            cbs = (GEN * 7)(GEN(), ctypes.cast(list_args, GEN),
                            ctypes.cast(list_outs, GEN), GEN(),
                            ctypes.cast(infer_shape, GEN), GEN(),
                            ctypes.cast(create_op, GEN))
            ctxs = (vp * 7)()
            keep.extend([cbs, ctxs])
            lst = ctypes.cast(ret, ctypes.POINTER(CBList))
            lst[0].num_callbacks = 7
            lst[0].callbacks = cbs
            lst[0].contexts = ctxs
            return 1

        keep.extend([list_args, list_outs, infer_shape, forward, backward,
                     create_op, creator])
        assert lib.MXCustomOpRegister(
            b"c_scale2", ctypes.cast(creator, vp)) == 0, _err(lib)

        # the C-registered op is a first-class custom op: imperative,
        # gradient, and the same registry as Python custom ops
        import mxnet_tpu as mx
        from mxnet_tpu import nd
        x = nd.array(np.array([1.0, -2.0, 3.5], np.float32))
        x.attach_grad()
        with mx.autograd.record():
            y = nd.Custom(x, op_type="c_scale2")
        np.testing.assert_allclose(y.asnumpy(), [2.0, -4.0, 7.0])
        y.backward()
        np.testing.assert_allclose(x.grad.asnumpy(), [2.0, 2.0, 2.0])


@needs_lib
class TestRound5Width:
    """Op discovery, symbol compose/copy, autograd state, kvstore extras,
    load-from-buffer (round-5 width batch; reference c_api.h:963+, 1168,
    660, 2538+)."""

    def test_op_discovery(self):
        lib = _lib()
        lib.MXSymbolListAtomicSymbolCreators.argtypes = [
            ctypes.POINTER(u32), ctypes.POINTER(ctypes.POINTER(vp))]
        n = u32()
        creators = ctypes.POINTER(vp)()
        assert lib.MXSymbolListAtomicSymbolCreators(
            ctypes.byref(n), ctypes.byref(creators)) == 0, _err(lib)
        assert n.value > 400
        # find Convolution and read its info
        lib.MXSymbolGetAtomicSymbolName.argtypes = [
            vp, ctypes.POINTER(ctypes.c_char_p)]
        found = None
        for i in range(n.value):
            nm = ctypes.c_char_p()
            assert lib.MXSymbolGetAtomicSymbolName(
                creators[i], ctypes.byref(nm)) == 0
            if nm.value == b"Convolution":
                found = creators[i]
        assert found is not None
        name = ctypes.c_char_p()
        desc = ctypes.c_char_p()
        nargs = u32()
        anames = ctypes.POINTER(ctypes.c_char_p)()
        atypes = ctypes.POINTER(ctypes.c_char_p)()
        adescs = ctypes.POINTER(ctypes.c_char_p)()
        kv = ctypes.c_char_p()
        rt = ctypes.c_char_p()
        lib.MXSymbolGetAtomicSymbolInfo.argtypes = [
            vp, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(u32),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p)),
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p)]
        assert lib.MXSymbolGetAtomicSymbolInfo(
            found, ctypes.byref(name), ctypes.byref(desc),
            ctypes.byref(nargs), ctypes.byref(anames), ctypes.byref(atypes),
            ctypes.byref(adescs), ctypes.byref(kv),
            ctypes.byref(rt)) == 0, _err(lib)
        assert name.value == b"Convolution"

    def test_symbol_compose_copy_name(self):
        lib = _lib()
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        sq = vp()
        assert lib.MXSymbolCreateOp(b"square", 0, None, None, 1,
                                    (vp * 1)(x), b"sq", ctypes.byref(sq)) == 0
        # copy, then compose the copy's free var with a fresh variable
        cp = vp()
        assert lib.MXSymbolCopy(sq, ctypes.byref(cp)) == 0, _err(lib)
        y = vp()
        assert lib.MXSymbolCreateVariable(b"y", ctypes.byref(y)) == 0
        keys = (ctypes.c_char_p * 1)(b"x")
        assert lib.MXSymbolCompose(cp, b"sq2", 1, keys,
                                   (vp * 1)(y)) == 0, _err(lib)
        nargs = u32()
        names = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXSymbolListArguments(cp, ctypes.byref(nargs),
                                         ctypes.byref(names)) == 0
        assert nargs.value == 1 and names[0] == b"y"
        # the original is untouched
        assert lib.MXSymbolListArguments(sq, ctypes.byref(nargs),
                                         ctypes.byref(names)) == 0
        assert names[0] == b"x"
        nout = u32()
        assert lib.MXSymbolGetNumOutputs(sq, ctypes.byref(nout)) == 0
        assert nout.value == 1
        nm = ctypes.c_char_p()
        ok = ctypes.c_int()
        assert lib.MXSymbolGetName(sq, ctypes.byref(nm),
                                   ctypes.byref(ok)) == 0
        assert nm.value == b"sq"

    def test_autograd_state_and_detach(self):
        lib = _lib()
        cur = ctypes.c_bool(True)
        assert lib.MXAutogradIsRecording(ctypes.byref(cur)) == 0
        assert cur.value is False
        prev = ctypes.c_int()
        assert lib.MXAutogradSetIsRecording(1, ctypes.byref(prev)) == 0
        assert lib.MXAutogradIsRecording(ctypes.byref(cur)) == 0
        assert cur.value is True
        assert lib.MXAutogradSetIsRecording(0, ctypes.byref(prev)) == 0
        h = _mk_ndarray(lib, np.ones((2,), np.float32))
        d = vp()
        assert lib.MXNDArrayDetach(h, ctypes.byref(d)) == 0, _err(lib)
        np.testing.assert_allclose(_to_numpy_1d(lib, d, 2), 1.0)
        lib.MXNDArrayFree(h)
        lib.MXNDArrayFree(d)

    def test_load_from_buffer_and_kvstore_extras(self, tmp_path):
        lib = _lib()
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        h = _mk_ndarray(lib, x)
        fname = str(tmp_path / "buf.params").encode()
        keys = (ctypes.c_char_p * 1)(b"w")
        assert lib.MXNDArraySave(fname, 1, (vp * 1)(h), keys) == 0
        blob = open(fname.decode(), "rb").read()
        lib.MXNDArrayLoadFromBuffer.argtypes = [
            vp, ctypes.c_size_t, ctypes.POINTER(u32),
            ctypes.POINTER(ctypes.POINTER(vp)), ctypes.POINTER(u32),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p))]
        n = u32()
        arrs = ctypes.POINTER(vp)()
        nn = u32()
        names = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXNDArrayLoadFromBuffer(
            blob, len(blob), ctypes.byref(n), ctypes.byref(arrs),
            ctypes.byref(nn), ctypes.byref(names)) == 0, _err(lib)
        assert n.value == 1 and names[0] == b"w"
        np.testing.assert_allclose(_to_numpy(lib, arrs[0]), x)

        kv = vp()
        assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
        t = ctypes.c_char_p()
        assert lib.MXKVStoreGetType(kv, ctypes.byref(t)) == 0
        assert t.value == b"local"
        ikeys = (ctypes.c_int * 1)(1)
        w = _mk_ndarray(lib, np.zeros((3,), np.float32))
        assert lib.MXKVStoreInit(kv, 1, ikeys, (vp * 1)(w)) == 0
        g = _mk_ndarray(lib, np.full((3,), 2.0, np.float32))
        out = _mk_ndarray(lib, np.zeros((3,), np.float32))
        assert lib.MXKVStorePushPull(kv, 1, ikeys, (vp * 1)(g),
                                     (vp * 1)(out), 0) == 0, _err(lib)
        np.testing.assert_allclose(_to_numpy_1d(lib, out, 3), 2.0)
        assert lib.MXKVStoreBarrier(kv) == 0
        dead = ctypes.c_int(-1)
        assert lib.MXKVStoreGetNumDeadNode(kv, 0, ctypes.byref(dead),
                                           5) == 0
        assert dead.value == 0
        lib.MXKVStoreFree(kv)

    def test_memory_info_and_shutdown(self):
        lib = _lib()
        free = ctypes.c_uint64()
        total = ctypes.c_uint64()
        lib.MXGetGPUMemoryInformation64.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        assert lib.MXGetGPUMemoryInformation64(
            0, ctypes.byref(free), ctypes.byref(total)) == 0
        assert lib.MXNotifyShutdown() == 0


def _to_numpy_1d(lib, h, n):
    out = np.zeros((n,), np.float32)
    rc = lib.MXNDArraySyncCopyToCPU(h, out.ctypes.data_as(vp),
                                    ctypes.c_size_t(out.nbytes))
    assert rc == 0, _err(lib)
    return out


@needs_lib
class TestRound5Batch3:
    """SimpleBind, PS env/roles/server loop, symbol attr listing
    (reference c_api.h:2046, 2290, 2559+, MXSymbolListAttr)."""

    def test_simple_bind_trains(self):
        lib = _lib()
        lib.MXExecutorSimpleBindEx.restype = ctypes.c_int
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        fc = vp()
        k = (ctypes.c_char_p * 1)(b"num_hidden")
        v = (ctypes.c_char_p * 1)(b"3")
        assert lib.MXSymbolCreateOp(b"FullyConnected", 1, k, v, 1,
                                    (vp * 1)(x), b"fc",
                                    ctypes.byref(fc)) == 0, _err(lib)
        # provide only the data shape; weights/bias are inferred+allocated
        shp_names = (ctypes.c_char_p * 1)(b"x")
        shp_data = (ctypes.c_int * 2)(2, 5)
        shp_idx = (u32 * 2)(0, 2)
        n_in = u32()
        in_args = ctypes.POINTER(vp)()
        arg_grads = ctypes.POINTER(vp)()
        n_aux = u32()
        aux = ctypes.POINTER(vp)()
        ex = vp()
        rc = lib.MXExecutorSimpleBindEx(
            fc, 1, 0,                      # cpu
            0, None, None, None,           # g2c
            0, None, None,                 # grad reqs (default write)
            1, shp_names, shp_data, shp_idx,
            0, None, None,                 # dtypes
            0, None, None,                 # stypes
            0, None, None, None, None, None, None,  # shared
            ctypes.byref(n_in), ctypes.byref(in_args),
            ctypes.byref(arg_grads), ctypes.byref(n_aux),
            ctypes.byref(aux), None, ctypes.byref(ex))
        assert rc == 0, _err(lib)
        assert n_in.value == 3  # x, fc_weight, fc_bias
        # fill data + weight through the returned handles and run a step
        xbuf = np.random.RandomState(0).randn(2, 5).astype(np.float32)
        wbuf = np.random.RandomState(1).randn(3, 5).astype(np.float32)
        assert lib.MXNDArraySyncCopyFromCPU(
            in_args[0], xbuf.ctypes.data_as(vp), xbuf.nbytes) == 0
        assert lib.MXNDArraySyncCopyFromCPU(
            in_args[1], wbuf.ctypes.data_as(vp), wbuf.nbytes) == 0
        assert lib.MXExecutorForward(ex, 1) == 0, _err(lib)
        nout = u32()
        outs = ctypes.POINTER(vp)()
        assert lib.MXExecutorOutputs(ex, ctypes.byref(nout),
                                     ctypes.byref(outs)) == 0
        got = _to_numpy(lib, outs[0])
        np.testing.assert_allclose(got, xbuf @ wbuf.T, rtol=1e-4,
                                   atol=1e-4)
        assert lib.MXExecutorBackward(ex, 0, None) == 0, _err(lib)
        # grads were allocated by simple_bind (grad_req defaulted write)
        g = _to_numpy(lib, arg_grads[1])
        assert np.abs(g).sum() > 0

    def test_ps_env_roles_and_run_server(self, monkeypatch):
        import threading
        lib = _lib()
        # MXInitPSEnv writes into os.environ; register the UNDO state
        # BEFORE it runs (setenv on an absent var records delete-on-undo
        # — delenv(raising=False) on an absent var records NOTHING, the
        # leak that broke test_parallel/test_tools when suite-ordered)
        monkeypatch.setenv("DMLC_ROLE", "placeholder")
        monkeypatch.setenv("DMLC_PS_ROOT_PORT", "placeholder")
        keys = (ctypes.c_char_p * 2)(b"DMLC_ROLE", b"DMLC_PS_ROOT_PORT")
        vals = (ctypes.c_char_p * 2)(b"server", b"19873")
        assert lib.MXInitPSEnv(2, keys, vals) == 0, _err(lib)
        ret = ctypes.c_int(-1)
        assert lib.MXKVStoreIsServerNode(ctypes.byref(ret)) == 0
        assert ret.value == 1
        assert lib.MXKVStoreIsWorkerNode(ctypes.byref(ret)) == 0
        assert ret.value == 0

        kv = vp()
        assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
        CTRL = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_char_p, vp)
        seen = []

        @CTRL
        def controller(head, body, _h):
            seen.append((head, body))

        lib.MXKVStoreRunServer.argtypes = [vp, vp, vp]
        done = []

        def run():
            rc = lib.MXKVStoreRunServer(kv, ctypes.cast(controller, vp),
                                        None)
            done.append(rc)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        import time as _time
        from mxnet_tpu.kvstore_server import KVClient
        deadline = _time.time() + 10
        client = None
        while client is None and _time.time() < deadline:
            try:
                client = KVClient("127.0.0.1", 19873, rank=0,
                                  num_workers=1, heartbeat_interval=0)
            except OSError:
                _time.sleep(0.1)
        assert client is not None, "server did not come up"
        client.send_command("42", b"hello-from-worker")
        client.stop_server()
        t.join(timeout=10)
        assert done == [0]
        assert (42, b"hello-from-worker") in seen

    def test_symbol_list_attr(self):
        lib = _lib()
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        assert lib.MXSymbolSetAttr(x, b"lr_mult", b"2.5") == 0, _err(lib)
        n = u32()
        pairs = ctypes.POINTER(ctypes.c_char_p)()
        lib.MXSymbolListAttrShallow.argtypes = [
            vp, ctypes.POINTER(u32),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p))]
        assert lib.MXSymbolListAttrShallow(
            x, ctypes.byref(n), ctypes.byref(pairs)) == 0, _err(lib)
        got = {pairs[2 * i].decode(): pairs[2 * i + 1].decode()
               for i in range(n.value)}
        assert any("lr_mult" in k for k in got), got

    def test_simple_bind_with_aux_and_global_req(self):
        """BatchNorm has aux states — the three out-arrays must not share
        a buffer; and the reference's global-req convention (list_len=0 +
        one type) must reach the python side."""
        lib = _lib()
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        bn = vp()
        assert lib.MXSymbolCreateOp(b"BatchNorm", 0, None, None, 1,
                                    (vp * 1)(x), b"bn",
                                    ctypes.byref(bn)) == 0, _err(lib)
        shp_names = (ctypes.c_char_p * 1)(b"x")
        shp_data = (ctypes.c_int * 4)(2, 3, 4, 4)
        shp_idx = (u32 * 2)(0, 4)
        req_types = (ctypes.c_char_p * 1)(b"null")  # global: inference
        n_in = u32()
        in_args = ctypes.POINTER(vp)()
        arg_grads = ctypes.POINTER(vp)()
        n_aux = u32()
        aux = ctypes.POINTER(vp)()
        ex = vp()
        rc = lib.MXExecutorSimpleBindEx(
            bn, 1, 0, 0, None, None, None,
            0, None, req_types,            # global grad_req
            1, shp_names, shp_data, shp_idx,
            0, None, None, 0, None, None,
            0, None, None, None, None, None, None,
            ctypes.byref(n_in), ctypes.byref(in_args),
            ctypes.byref(arg_grads), ctypes.byref(n_aux),
            ctypes.byref(aux), None, ctypes.byref(ex))
        assert rc == 0, _err(lib)
        assert n_in.value == 3 and n_aux.value == 2  # x,gamma,beta + mm,mv
        # in_args must still be valid AFTER aux_states was produced
        # (regression: shared thread-local buffer clobbered it)
        shp_n = u32()
        pdata = ctypes.POINTER(u32)()
        assert lib.MXNDArrayGetShape(in_args[0], ctypes.byref(shp_n),
                                     ctypes.byref(pdata)) == 0
        assert [pdata[i] for i in range(shp_n.value)] == [2, 3, 4, 4]
        assert lib.MXNDArrayGetShape(aux[0], ctypes.byref(shp_n),
                                     ctypes.byref(pdata)) == 0
        assert [pdata[i] for i in range(shp_n.value)] == [3]
        # global 'null': no grads allocated
        assert all(not arg_grads[i] for i in range(n_in.value))

    def test_misc_batch4(self):
        lib = _lib()
        # profiler legacy aliases
        assert lib.MXSetProfilerState(0) == 0
        # feature flags
        class LibFeature(ctypes.Structure):
            _fields_ = [("name", ctypes.c_char_p),
                        ("enabled", ctypes.c_bool)]
        feats = ctypes.POINTER(LibFeature)()
        size = ctypes.c_size_t()
        lib.MXLibInfoFeatures.argtypes = [
            ctypes.POINTER(ctypes.POINTER(LibFeature)),
            ctypes.POINTER(ctypes.c_size_t)]
        assert lib.MXLibInfoFeatures(ctypes.byref(feats),
                                     ctypes.byref(size)) == 0, _err(lib)
        assert size.value > 0
        names = {feats[i].name.decode() for i in range(size.value)}
        assert names  # non-empty feature set
        # numpy-shape toggle round trip
        prev = ctypes.c_int(-1)
        assert lib.MXSetIsNumpyShape(1, ctypes.byref(prev)) == 0
        cur = ctypes.c_int(-1)
        assert lib.MXIsNumpyShape(ctypes.byref(cur)) == 0
        assert cur.value == 1
        assert lib.MXSetIsNumpyShape(0, ctypes.byref(prev)) == 0
        assert prev.value == 1
        # engine bulk size returns the previous value
        prevb = ctypes.c_int(-1)
        assert lib.MXEngineSetBulkSize(30, ctypes.byref(prevb)) == 0
        assert lib.MXEngineSetBulkSize(15, ctypes.byref(prevb)) == 0
        assert prevb.value == 30
        # per-context seed + cache drop + MiB memory info
        assert lib.MXRandomSeedContext(7, 1, 0) == 0
        assert lib.MXStorageEmptyCache(1, 0) == 0
        free = ctypes.c_int(); tot = ctypes.c_int()
        assert lib.MXGetGPUMemoryInformation(0, ctypes.byref(free),
                                             ctypes.byref(tot)) == 0
        assert lib.MXKVStoreSetBarrierBeforeExit(None, 1) == 0

    def test_final_width_batch(self, tmp_path):
        lib = _lib()
        x = vp()
        assert lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)) == 0
        sq = vp()
        assert lib.MXSymbolCreateOp(b"square", 0, None, None, 1,
                                    (vp * 1)(x), b"sq",
                                    ctypes.byref(sq)) == 0
        # file round trip
        fname = str(tmp_path / "sym.json").encode()
        assert lib.MXSymbolSaveToFile(sq, fname) == 0, _err(lib)
        loaded = vp()
        assert lib.MXSymbolCreateFromFile(fname,
                                          ctypes.byref(loaded)) == 0
        n = u32()
        names = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXSymbolListArguments(loaded, ctypes.byref(n),
                                         ctypes.byref(names)) == 0
        assert n.value == 1 and names[0] == b"x"
        # partial shape inference: no shapes provided -> complete=0
        u32p = ctypes.POINTER(u32)
        isz = u32(); indim = u32p(); idata = ctypes.POINTER(u32p)()
        osz = u32(); ondim = u32p(); odata = ctypes.POINTER(u32p)()
        asz = u32(); andim = u32p(); adata = ctypes.POINTER(u32p)()
        comp = ctypes.c_int(-1)
        lib.MXSymbolInferShapePartial.argtypes = [
            vp, u32, ctypes.POINTER(ctypes.c_char_p), u32p, u32p,
            ctypes.POINTER(u32), ctypes.POINTER(u32p),
            ctypes.POINTER(ctypes.POINTER(u32p)),
            ctypes.POINTER(u32), ctypes.POINTER(u32p),
            ctypes.POINTER(ctypes.POINTER(u32p)),
            ctypes.POINTER(u32), ctypes.POINTER(u32p),
            ctypes.POINTER(ctypes.POINTER(u32p)),
            ctypes.POINTER(ctypes.c_int)]
        rc = lib.MXSymbolInferShapePartial(
            sq, 0, None, (u32 * 1)(0), None,
            ctypes.byref(isz), ctypes.byref(indim), ctypes.byref(idata),
            ctypes.byref(osz), ctypes.byref(ondim), ctypes.byref(odata),
            ctypes.byref(asz), ctypes.byref(andim), ctypes.byref(adata),
            ctypes.byref(comp))
        assert rc == 0, _err(lib)
        assert comp.value == 0  # nothing known -> incomplete, no error
        # invoke alias + 64-bit views
        a = _mk_ndarray(lib, np.arange(6, dtype=np.float32).reshape(3, 2))
        no = ctypes.c_int(0)
        outs = ctypes.POINTER(vp)()
        assert lib.MXImperativeInvoke(b"square", 1, (vp * 1)(a),
                                      ctypes.byref(no), ctypes.byref(outs),
                                      0, None, None) == 0
        row = vp()
        lib.MXNDArrayAt64.argtypes = [vp, ctypes.c_int64,
                                      ctypes.POINTER(vp)]
        assert lib.MXNDArrayAt64(a, 1, ctypes.byref(row)) == 0, _err(lib)
        sl = vp()
        lib.MXNDArraySlice64.argtypes = [vp, ctypes.c_int64,
                                         ctypes.c_int64,
                                         ctypes.POINTER(vp)]
        assert lib.MXNDArraySlice64(a, 0, 2, ctypes.byref(sl)) == 0
        # gradient compression config reaches the kvstore
        kv = vp()
        assert lib.MXKVStoreCreate(b"device", ctypes.byref(kv)) == 0
        k = (ctypes.c_char_p * 1)(b"type")
        v = (ctypes.c_char_p * 1)(b"2bit")
        assert lib.MXKVStoreSetGradientCompression(kv, 1, k, v) == 0, \
            _err(lib)
        # iterator info by name
        nm = ctypes.c_char_p(); desc = ctypes.c_char_p()
        na = u32()
        assert lib.MXDataIterGetIterInfo(
            b"CSVIter", ctypes.byref(nm), ctypes.byref(desc),
            ctypes.byref(na), None, None, None) == 0, _err(lib)
        assert nm.value == b"CSVIter"
