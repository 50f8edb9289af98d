"""Module API distributed training (parity: the reference's canonical
dist path — Module.fit(kvstore='dist_sync') → model.py
_update_params_on_kvstore; tests/nightly/dist_lenet.py shape)."""
import os
import threading
import time

import numpy as np

_WORKER = """
import os, sys
import numpy as np
rank = int(sys.argv[1]); num_workers = int(sys.argv[2]); port = int(sys.argv[3])
os.environ["DMLC_RANK"] = str(rank)
os.environ["DMLC_NUM_WORKER"] = str(num_workers)
os.environ["DMLC_PS_ROOT_URI"] = "127.0.0.1"
os.environ["DMLC_PS_ROOT_PORT"] = str(port)
import mxnet_tpu as mx
from mxnet_tpu import symbol as sym, io as mxio
from mxnet_tpu.module import Module

data = sym.var("data")
w = sym.var("fc_weight")
fc = sym.Symbol._create("FullyConnected", [data, w],
                        {"num_hidden": 1, "no_bias": True})
label = sym.var("lin_label")
out = sym.Symbol._create("LinearRegressionOutput", [fc, label], {})

rng = np.random.RandomState(100 + rank)  # DIFFERENT data per worker
x = rng.randn(32, 4).astype(np.float32)
y = x @ np.asarray([[1.0, -1.0, 0.5, 2.0]], np.float32).T
it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=16,
                      label_name="lin_label")
mod = Module(out, data_names=("data",), label_names=("lin_label",))
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
mod.init_params(mx.initializer.Constant(0.0))
mod.init_optimizer(kvstore="dist_sync", optimizer="sgd",
                   optimizer_params=(("learning_rate", 0.006),))
assert mod._kvstore is not None and mod._update_on_kvstore
for epoch in range(3):
    it.reset()
    for batch in it:
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
weights = mod._exec.arg_dict["fc_weight"].asnumpy()
np.save(sys.argv[4], weights)
"""


def test_module_dist_sync_two_workers(tmp_path):
    import subprocess
    import sys

    from mxnet_tpu.kvstore_server import KVServer
    num_workers = 2
    port = 19441
    server = KVServer(port=port, num_workers=num_workers)
    t = threading.Thread(target=server.run, daemon=True)
    t.start()
    time.sleep(0.2)
    script = str(tmp_path / "mworker.py")
    with open(script, "w") as f:
        f.write(_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    outs = [str(tmp_path / f"w{r}.npy") for r in range(num_workers)]
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), str(num_workers), str(port),
         outs[r]], env=env) for r in range(num_workers)]
    try:
        for p in procs:
            assert p.wait(timeout=180) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server._stop.set()
    w0, w1 = np.load(outs[0]), np.load(outs[1])
    # server-side optimizer: every worker pulls the SAME weights
    np.testing.assert_array_equal(w0, w1)
    # and training actually moved toward the shared target
    target = np.asarray([[1.0, -1.0, 0.5, 2.0]], np.float32)
    assert np.abs(w0 - target).mean() < np.abs(target).mean(), w0
