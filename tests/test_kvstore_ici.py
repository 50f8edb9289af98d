"""KVStore('ici') — XLA-collective allreduce store — plus dist big-array
chunking and the widened sparse dot paths.

Parity targets: SURVEY.md §5 KVStore('ici') north star;
kvstore_dist.h:243 big-array key sharding; dot-inl.h DotDnsRsp/DotDnsCsr."""
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kvstore as kvs
from mxnet_tpu import gluon


def _ctxs(n):
    devs = jax.devices("cpu")
    if len(devs) < n:
        pytest.skip(f"need {n} virtual devices")
    return [mx.Context("cpu", i) for i in range(n)]


class TestKVStoreICI:
    def test_push_pull_allreduce(self):
        ctxs = _ctxs(4)
        kv = kvs.create("ici")
        assert kv.type == "ici"
        rng = np.random.RandomState(0)
        base = rng.randn(6, 3).astype(np.float32)
        kv.init("w", mx.nd.array(base, ctx=ctxs[0]))
        grads = [mx.nd.array(rng.randn(6, 3).astype(np.float32), ctx=c)
                 for c in ctxs]
        kv.push("w", grads)
        outs = [mx.nd.zeros((6, 3), ctx=c) for c in ctxs]
        kv.pull("w", out=outs)
        expect = np.sum([g.asnumpy() for g in grads], axis=0)
        for c, o in zip(ctxs, outs):
            np.testing.assert_allclose(o.asnumpy(), expect,
                                       rtol=1e-5, atol=1e-6)
            # the pulled buffer must LIVE on its context's device
            assert next(iter(o._data.devices())).id == c.device_id

    def test_updater_runs_in_store(self):
        ctxs = _ctxs(2)
        kv = kvs.create("ici")
        kv.init("w", mx.nd.ones((4,), ctx=ctxs[0]))
        kv._set_updater(lambda key, g, w: w.__isub__(0.1 * g))
        kv.push("w", [mx.nd.ones((4,), ctx=c) for c in ctxs])
        out = mx.nd.zeros((4,), ctx=ctxs[1])
        kv.pull("w", out=[out])
        np.testing.assert_allclose(out.asnumpy(), 1.0 - 0.1 * 2.0,
                                   rtol=1e-6)

    def test_trainer_ici_matches_local(self):
        ctxs = _ctxs(2)

        def train(kv_name):
            from mxnet_tpu import random as _r
            np.random.seed(0)
            net = gluon.nn.Dense(3, in_units=4)
            net.initialize(mx.initializer.Constant(0.1), ctx=ctxs)
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1}, kvstore=kv_name)
            L = gluon.loss.L2Loss()
            rng = np.random.RandomState(1)
            for _ in range(3):
                xs = rng.randn(8, 4).astype(np.float32)
                ys = rng.randn(8, 3).astype(np.float32)
                losses = []
                with mx.autograd.record():
                    for i, c in enumerate(ctxs):
                        xb = mx.nd.array(xs[i * 4:(i + 1) * 4], ctx=c)
                        yb = mx.nd.array(ys[i * 4:(i + 1) * 4], ctx=c)
                        losses.append(L(net(xb), yb))
                mx.autograd.backward(losses)
                tr.step(8)
            # key by param-name suffix: the gluon name counter advances
            # between the two train() runs (dense0 -> dense1)
            return {k.rsplit("_", 1)[-1]: v.list_data()[0].asnumpy()
                    for k, v in net.collect_params().items()}

        w_local = train("local")
        w_ici = train("ici")
        assert set(w_local) == set(w_ici) == {"weight", "bias"}
        for k in w_local:
            np.testing.assert_allclose(w_ici[k], w_local[k],
                                       rtol=1e-5, atol=1e-6)


class TestBigArrayChunking:
    def test_chunk_layout(self):
        from mxnet_tpu.kvstore import KVStoreDist
        os.environ["MXNET_KVSTORE_BIGARRAY_BOUND"] = "100"
        try:
            layout = KVStoreDist._chunk_layout("w", (50, 10))
            assert len(layout) == 5
            assert layout[0] == ("w#chunk0", 0, 10)
            assert layout[-1] == ("w#chunk4", 40, 50)
            # small array: single plain key
            assert KVStoreDist._chunk_layout("v", (5, 2)) == [("v", 0, 5)]
        finally:
            del os.environ["MXNET_KVSTORE_BIGARRAY_BOUND"]

    _CHUNK_WORKER = """
import os, sys
import numpy as np
rank = int(sys.argv[1]); num_workers = int(sys.argv[2]); port = int(sys.argv[3])
os.environ["DMLC_RANK"] = str(rank)
os.environ["DMLC_NUM_WORKER"] = str(num_workers)
os.environ["DMLC_PS_ROOT_URI"] = "127.0.0.1"
os.environ["DMLC_PS_ROOT_PORT"] = str(port)
os.environ["MXNET_KVSTORE_BIGARRAY_BOUND"] = "64"
import mxnet_tpu as mx
from mxnet_tpu import kvstore as kvs
kv = kvs.create("dist_sync")
rng = np.random.RandomState(2)
big = rng.randn(40, 8).astype(np.float32)  # 320 elements > bound 64
kv.init("w", mx.nd.array(big))
pre = mx.nd.zeros((40, 8))
kv.pull("w", out=pre)  # chunked init round-trips the exact values
g = np.full((40, 8), rank + 1.0, np.float32)
kv.push("w", mx.nd.array(g))
kv.barrier()
out = mx.nd.zeros((40, 8))
kv.pull("w", out=out)
# row_sparse pull on the CHUNKED key: rows span chunk boundaries
rows = mx.nd.array(np.asarray([2, 17, 35], np.float32))
rs_out = mx.nd.sparse.row_sparse_array(
    (np.zeros((3, 8), np.float32), [2, 17, 35]), shape=(40, 8))
kv.row_sparse_pull("w", out=rs_out, row_ids=rows)
np.save(sys.argv[4], np.stack([pre.asnumpy(), out.asnumpy()]))
np.save(sys.argv[4] + ".rs.npy", rs_out.data.asnumpy())
"""

    def test_dist_chunked_roundtrip(self, tmp_path):
        """Big arrays cross the wire in row chunks; workers still see
        bit-identical aggregated values (2 real processes, TCP)."""
        import subprocess
        import sys
        from mxnet_tpu.kvstore_server import KVServer
        num_workers = 2
        port = 19321
        server = KVServer(port=port, num_workers=num_workers)
        t = threading.Thread(target=server.run, daemon=True)
        t.start()
        time.sleep(0.2)
        script = str(tmp_path / "worker.py")
        with open(script, "w") as f:
            f.write(self._CHUNK_WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        outs = [str(tmp_path / f"o{r}.npy") for r in range(num_workers)]
        procs = [subprocess.Popen(
            [sys.executable, script, str(r), str(num_workers), str(port),
             outs[r]], env=env) for r in range(num_workers)]
        for p in procs:
            assert p.wait(timeout=120) == 0
        server._stop.set()
        rng = np.random.RandomState(2)
        big = rng.randn(40, 8).astype(np.float32)
        results = [np.load(o) for o in outs]
        for pre, post in results:
            # chunked init round-trips exactly; push aggregate (no
            # updater: store <- sum of pushes = 1+2) reassembles too
            np.testing.assert_allclose(pre, big, rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(post, 3.0, rtol=1e-6)
        np.testing.assert_array_equal(results[0], results[1])
        # row_sparse pull across chunk boundaries returns the post-push
        # rows (all 3.0 here)
        for o in outs:
            rs = np.load(o + ".rs.npy")
            assert rs.shape == (3, 8)
            np.testing.assert_allclose(rs, 3.0, rtol=1e-6)


class TestSparseDotBreadth:
    def _rsp(self, shape, idx, rng):
        from mxnet_tpu.ndarray import sparse as sp
        data = rng.randn(len(idx), shape[1]).astype(np.float32)
        return sp.row_sparse_array((data, idx), shape=shape), data

    def test_rsp_dense(self):
        from mxnet_tpu.ndarray import sparse as sp
        rng = np.random.RandomState(3)
        a, data = self._rsp((6, 4), [1, 4], rng)
        b = mx.nd.array(rng.randn(4, 3).astype(np.float32))
        out = sp.dot(a, b)
        dense_a = a.todense().asnumpy()
        np.testing.assert_allclose(out.asnumpy(), dense_a @ b.asnumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_rsp_dense_transpose_a(self):
        from mxnet_tpu.ndarray import sparse as sp
        rng = np.random.RandomState(4)
        a, data = self._rsp((6, 4), [0, 2, 5], rng)
        b = mx.nd.array(rng.randn(6, 3).astype(np.float32))
        out = sp.dot(a, b, transpose_a=True)
        dense_a = a.todense().asnumpy()
        np.testing.assert_allclose(out.asnumpy(), dense_a.T @ b.asnumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_rsp_dense_grad(self):
        from mxnet_tpu.ndarray import sparse as sp
        rng = np.random.RandomState(5)
        a, data = self._rsp((5, 3), [1, 3], rng)
        b = mx.nd.array(rng.randn(3, 2).astype(np.float32))
        b.attach_grad()
        with mx.autograd.record():
            out = sp.dot(a, b)
            loss = (out * out).sum()
        loss.backward()
        dense_a = a.todense().asnumpy()
        expect = 2 * dense_a.T @ (dense_a @ b.asnumpy())
        np.testing.assert_allclose(b.grad.asnumpy(), expect,
                                   rtol=1e-4, atol=1e-5)

    def test_1d_operands_fall_back(self):
        """1-D dense operands use the densify path (pre-existing
        behavior) instead of crashing in the 2-D fast paths."""
        from mxnet_tpu.ndarray import sparse as sp
        rng = np.random.RandomState(8)
        a, _ = self._rsp((6, 4), [1, 4], rng)
        v = mx.nd.array(rng.randn(4).astype(np.float32))
        out = sp.dot(a, v)
        np.testing.assert_allclose(
            out.asnumpy(), a.todense().asnumpy() @ v.asnumpy(),
            rtol=1e-5, atol=1e-6)
        dense_b = ((rng.rand(4, 5) > 0.5) * rng.randn(4, 5)).astype(np.float32)
        b = sp.csr_matrix(mx.nd.array(dense_b))
        u = mx.nd.array(rng.randn(4).astype(np.float32))
        out2 = sp.dot(u, b)
        np.testing.assert_allclose(out2.asnumpy(), u.asnumpy() @ dense_b,
                                   rtol=1e-5, atol=1e-6)

    def test_dense_csr(self):
        from mxnet_tpu.ndarray import sparse as sp
        rng = np.random.RandomState(6)
        dense_b = (rng.rand(4, 5) > 0.6) * rng.randn(4, 5)
        b = sp.csr_matrix(mx.nd.array(dense_b.astype(np.float32)))
        a = mx.nd.array(rng.randn(3, 4).astype(np.float32))
        out = sp.dot(a, b)
        np.testing.assert_allclose(
            out.asnumpy(), a.asnumpy() @ dense_b.astype(np.float32),
            rtol=1e-5, atol=1e-6)

    def test_dense_csr_transpose_b_and_grad(self):
        from mxnet_tpu.ndarray import sparse as sp
        rng = np.random.RandomState(7)
        dense_b = ((rng.rand(6, 4) > 0.5) * rng.randn(6, 4)).astype(np.float32)
        b = sp.csr_matrix(mx.nd.array(dense_b))
        a = mx.nd.array(rng.randn(3, 4).astype(np.float32))
        a.attach_grad()
        with mx.autograd.record():
            out = sp.dot(a, b, transpose_b=True)
            loss = (out * out).sum()
        loss.backward()
        np.testing.assert_allclose(out.asnumpy(),
                                   a.asnumpy() @ dense_b.T,
                                   rtol=1e-5, atol=1e-6)
        expect = 2 * (a.asnumpy() @ dense_b.T) @ dense_b
        np.testing.assert_allclose(a.grad.asnumpy(), expect,
                                   rtol=1e-4, atol=1e-5)
