"""2-bit gradient compression (reference:
src/kvstore/gradient_compression.h kTwoBit + error feedback;
tests/nightly/dist_sync_kvstore.py compressed push assertions)."""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kvstore
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gradient_compression import GradientCompression, create


def test_quantize_codes_and_threshold():
    gc = GradientCompression(threshold=0.5)
    grad = np.array([0.7, -0.6, 0.1, -0.1, 0.0], np.float32)
    deq = gc.dequantize(gc.quantize("k", grad), grad.shape)
    np.testing.assert_allclose(deq, [0.5, -0.5, 0.0, 0.0, 0.0])


def test_error_feedback_residual_accumulates():
    gc = GradientCompression(threshold=0.5)
    grad = np.full((8,), 0.2, np.float32)
    # 0.2 < 0.5: first two pushes emit nothing, residual reaches 0.6
    d1 = gc.dequantize(gc.quantize("k", grad), grad.shape)
    d2 = gc.dequantize(gc.quantize("k", grad), grad.shape)
    d3 = gc.dequantize(gc.quantize("k", grad), grad.shape)
    np.testing.assert_allclose(d1, 0.0)
    np.testing.assert_allclose(d2, 0.0)
    np.testing.assert_allclose(d3, 0.5)  # residual 0.6 >= threshold
    # long-run mean approaches the true gradient (unbiased-ish drift)
    total = d1 + d2 + d3
    for _ in range(17):
        total = total + gc.dequantize(gc.quantize("k", grad), grad.shape)
    np.testing.assert_allclose(total / 20.0, 0.2, atol=0.03)


def test_packing_is_4_codes_per_byte():
    gc = GradientCompression(threshold=1.0)
    grad = np.ones((1000,), np.float32)
    packed = gc.quantize("k", grad)
    assert packed.dtype == np.uint8
    assert packed.size == 250
    np.testing.assert_allclose(gc.dequantize(packed, (1000,)), 1.0)


def test_create_validates():
    assert create({"type": "none"}) is None
    assert create({"type": "2bit", "threshold": 2.0}).threshold == 2.0
    with pytest.raises(MXNetError):
        create({"type": "1bit"})
    with pytest.raises(MXNetError):
        create({"type": "2bit", "bogus": 1})


def test_local_store_rejects_compression():
    kv = kvstore.create("local")
    with pytest.raises(MXNetError):
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})


def test_device_store_compressed_convergence():
    """Linear regression through a compressed 'device' kvstore still
    converges (error feedback recovers the small updates)."""
    rng = np.random.RandomState(0)
    w_true = rng.randn(4).astype(np.float32)
    w = nd.array(np.zeros(4, np.float32))
    kv = kvstore.create("device")
    # each step moves at most threshold*lr per coordinate, so the
    # constants must allow reaching |w_true|~1 within the step budget
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", w)
    lr = 0.1
    losses = []
    for step in range(150):
        x = rng.randn(32, 4).astype(np.float32)
        err = x @ np.asarray(w.asnumpy()) - x @ w_true
        losses.append(float((err ** 2).mean()))
        grad = nd.array((x.T @ err / 32).astype(np.float32))
        kv.push("w", grad)
        agg = nd.zeros(4)
        kv.pull("w", out=agg)   # no updater: store holds the deq grad
        w = nd.array(w.asnumpy() - lr * agg.asnumpy())
    assert np.mean(losses[-10:]) < losses[0] * 0.2, losses[::15]


_WORKER = """
import os, sys
rank, num_workers, port, out = (int(sys.argv[1]), int(sys.argv[2]),
                                int(sys.argv[3]), sys.argv[4])
os.environ["DMLC_RANK"] = str(rank)
os.environ["DMLC_NUM_WORKER"] = str(num_workers)
os.environ["DMLC_PS_ROOT_URI"] = "127.0.0.1"
os.environ["DMLC_PS_ROOT_PORT"] = str(port)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import kvstore, nd
kv = kvstore.create("dist_sync")
kv.set_gradient_compression({"type": "2bit", "threshold": 0.25})
kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0, wd=0.0))
w = nd.array(np.zeros(8, np.float32))
kv.init("w", w)
# every worker pushes the same grad pattern; with threshold .25 the
# elements 0..3 (value .3) quantize to .25 each push, elements 4..7
# (value .1) emit only when the residual crosses the threshold
grad = nd.array(np.array([0.3]*4 + [0.1]*4, np.float32))
for step in range(6):
    kv.push("w", grad)
    out_arr = nd.zeros(8)
    kv.pull("w", out=out_arr)
np.save(out, out_arr.asnumpy())
"""


def test_dist_sync_4workers_compressed(tmp_path):
    """4 workers, compressed pushes, bit-identical pulls (parity:
    tests/nightly/dist_sync_kvstore.py compressed section)."""
    import subprocess
    import sys

    from mxnet_tpu.kvstore_server import KVServer
    num_workers = 4
    port = 19261
    server = KVServer(port=port, num_workers=num_workers)
    t = threading.Thread(target=server.run, daemon=True)
    t.start()
    time.sleep(0.2)
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    outs = [str(tmp_path / f"out{r}.npy") for r in range(num_workers)]
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), str(num_workers), str(port),
         outs[r]], env=env) for r in range(num_workers)]
    for p in procs:
        assert p.wait(timeout=180) == 0
    server._stop.set()
    results = [np.load(o) for o in outs]
    # bit-exact across all 4 workers
    for r in results[1:]:
        np.testing.assert_array_equal(results[0], r)
    # server-side SGD (lr=1): w = -sum over rounds of the aggregated
    # (4-worker) dequantized gradients.  All workers emit identically, so
    # per-worker cumulative emission = -w/4, which error feedback keeps
    # within one threshold of the true cumulative gradient 6*g.
    # lag bound: one push emits at most one +-threshold level, so the
    # residual can hold up to threshold + per-push-grad
    per_worker = -results[0] / num_workers
    np.testing.assert_allclose(per_worker[:4], 6 * 0.3, atol=0.25 + 0.3)
    np.testing.assert_allclose(per_worker[4:], 6 * 0.1, atol=0.25 + 0.1)
    # and something was actually emitted (the wire path works)
    assert (per_worker[:4] > 0).all()


# -- traced collective codecs (ISSUE 11) -------------------------------------
def test_jnp_quantize_matches_numpy_reference():
    """The in-trace kTwoBit codec (quantize_2bit_flat/decode_2bit_sum)
    must emit exactly the NumPy reference's codes and keep the same
    error-feedback residual."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gradient_compression import (decode_2bit_sum,
                                                quantize_2bit_flat)

    rng = np.random.RandomState(7)
    grad = rng.randn(37).astype(np.float32)  # non-multiple-of-4 length
    res = rng.randn(37).astype(np.float32) * 0.1

    ref = GradientCompression(threshold=0.5)
    ref._residuals["k"] = res.copy()
    ref_packed = ref.quantize("k", grad)
    ref_deq = ref.dequantize(ref_packed, grad.shape)

    packed, new_res = jax.jit(
        lambda f, r: quantize_2bit_flat(f, r, 0.5))(grad, res)
    np.testing.assert_array_equal(np.asarray(packed), ref_packed)
    np.testing.assert_allclose(np.asarray(new_res),
                               ref._residuals["k"], atol=1e-6)
    # decode-sum over a fake 2-rank gather == sum of dequantized values
    gathered = jnp.stack([jnp.asarray(packed), jnp.asarray(packed)])
    summed = jax.jit(
        lambda g: decode_2bit_sum(g, 0.5, grad.shape[0]))(gathered)
    np.testing.assert_allclose(np.asarray(summed), 2 * ref_deq,
                               atol=1e-6)


def test_codec_wire_bytes_ring_math():
    from mxnet_tpu.gradient_compression import codec_wire_bytes

    B = 1 << 20
    # dense ring all-reduce: 2 * B * (R-1)/R
    assert codec_wire_bytes(B, 8, "none") == int(2 * B * 7 / 8)
    # fp16 halves it
    assert codec_wire_bytes(B, 8, "fp16") == int(B * 7 / 8)
    # 2bit: (R-1) * B/16 -> dense/2bit == 32/R
    assert codec_wire_bytes(B, 8, "2bit") == int(7 * B / 16)
    ratio = codec_wire_bytes(B, 8, "none") / codec_wire_bytes(B, 8,
                                                              "2bit")
    assert abs(ratio - 32 / 8) < 1e-9
    # R=2 (the cross-host pair): 16x
    r2 = codec_wire_bytes(B, 2, "none") / codec_wire_bytes(B, 2, "2bit")
    assert abs(r2 - 16.0) < 1e-9
