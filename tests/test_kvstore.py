"""KVStore tests (parity: reference tests/python/unittest/test_kvstore.py +
tests/nightly/dist_sync_kvstore.py strategy: real multi-process localhost
transport, bit-exact weight agreement)."""
import multiprocessing
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kvstore


def test_single_kv_pair():
    kv = kvstore.create("local")
    kv.init(3, mx.nd.ones((3, 3)))
    out = mx.nd.zeros((3, 3))
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), 1)
    kv.push(3, mx.nd.ones((3, 3)) * 4)
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), 4)


def test_list_kv_pairs():
    kv = kvstore.create("device")
    keys = [5, 7, 9]
    kv.init(keys, [mx.nd.ones((2, 2))] * 3)
    outs = [mx.nd.zeros((2, 2)) for _ in keys]
    kv.pull(keys, out=outs)
    for o in outs:
        np.testing.assert_allclose(o.asnumpy(), 1)


def test_aggregation():
    """Push from multiple 'devices' sums (parity: comm Reduce)."""
    kv = kvstore.create("local")
    kv.init("a", mx.nd.zeros((4,)))
    vals = [mx.nd.ones((4,)), mx.nd.ones((4,)) * 2, mx.nd.ones((4,)) * 3]
    kv.push("a", vals)
    out = mx.nd.zeros((4,))
    kv.pull("a", out=out)
    np.testing.assert_allclose(out.asnumpy(), 6)


def test_updater():
    """In-store optimizer (parity: update_on_kvstore)."""
    kv = kvstore.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1))
    w = mx.nd.ones((2, 2))
    kv.init(0, w)
    kv.push(0, mx.nd.ones((2, 2)))  # grad=1 -> w -= 0.1*1
    out = mx.nd.zeros((2, 2))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.9, rtol=1e-5)


def test_str_keys():
    kv = kvstore.create("local")
    kv.init("weight", mx.nd.ones((2,)))
    out = mx.nd.zeros((2,))
    kv.pull("weight", out=out)
    np.testing.assert_allclose(out.asnumpy(), 1)


def test_save_load_optimizer_states(tmp_path):
    kv = kvstore.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                         momentum=0.9))
    kv.init(0, mx.nd.ones((2,)))
    kv.push(0, mx.nd.ones((2,)))
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname)
    kv.load_optimizer_states(fname)


_WORKER_SCRIPT = """
import os, sys
import numpy as np
rank = int(sys.argv[1]); num_workers = int(sys.argv[2]); port = int(sys.argv[3])
os.environ["DMLC_RANK"] = str(rank)
os.environ["DMLC_NUM_WORKER"] = str(num_workers)
os.environ["DMLC_PS_ROOT_URI"] = "127.0.0.1"
os.environ["DMLC_PS_ROOT_PORT"] = str(port)
import mxnet_tpu as mx
from mxnet_tpu import kvstore as kvs
kv = kvs.create("dist_sync")
assert kv.rank == rank and kv.num_workers == num_workers
kv.init("w", mx.nd.ones((4,)))
kv.push("w", mx.nd.ones((4,)) * (rank + 1))
kv.barrier()
out = mx.nd.zeros((4,))
kv.pull("w", out=out)
np.save(sys.argv[4], out.asnumpy())
"""


def test_dist_sync_localhost(tmp_path):
    """Real multi-process dist kvstore on localhost — separate interpreter
    per worker, real TCP transport (parity:
    tests/nightly/dist_sync_kvstore.py via launcher local mode)."""
    import subprocess
    import sys

    from mxnet_tpu.kvstore_server import KVServer
    num_workers = 2
    port = 19123
    server = KVServer(port=port, num_workers=num_workers)
    t = threading.Thread(target=server.run, daemon=True)
    t.start()
    time.sleep(0.2)
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_WORKER_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    outs = [str(tmp_path / f"out{r}.npy") for r in range(num_workers)]
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), str(num_workers), str(port), outs[r]],
        env=env) for r in range(num_workers)]
    for p in procs:
        assert p.wait(timeout=90) == 0
    server._stop.set()
    # no updater installed: store = sum of pushes = 1+2 = 3
    results = [np.load(o) for o in outs]
    for r in results:
        np.testing.assert_allclose(r, 3.0)
    # bit-exact across workers (parity: dist_sync_kvstore.py assertion)
    np.testing.assert_array_equal(results[0], results[1])


def test_heartbeat_dead_node_detection():
    """PS failure detection: a worker that stops heartbeating is
    reported by get_num_dead_node (parity: ps-lite heartbeats,
    include/mxnet/kvstore.h:353)."""
    from mxnet_tpu.kvstore_server import KVClient, KVServer
    port = 19557
    server = KVServer(port=port, num_workers=2)
    t = threading.Thread(target=server.run, daemon=True)
    t.start()
    time.sleep(0.2)
    try:
        # manual heartbeats so the test controls time precisely
        c0 = KVClient("127.0.0.1", port, rank=0, num_workers=2,
                      heartbeat_interval=0)
        c1 = KVClient("127.0.0.1", port, rank=1, num_workers=2,
                      heartbeat_interval=0)
        c0.heartbeat()
        c1.heartbeat()
        assert c0.num_dead_node(timeout=5) == 0
        # rank 1 goes silent; rank 0 keeps beating
        time.sleep(1.2)
        c0.heartbeat()
        assert c0.num_dead_node(timeout=1.0) == 1
        # rank 1 recovers
        c1.heartbeat()
        assert c0.num_dead_node(timeout=1.0) == 0
    finally:
        server._stop.set()


_ASYNC_WORKER = """
import os, sys
import numpy as np
rank = int(sys.argv[1]); num_workers = int(sys.argv[2]); port = int(sys.argv[3])
os.environ["DMLC_RANK"] = str(rank)
os.environ["DMLC_NUM_WORKER"] = str(num_workers)
os.environ["DMLC_PS_ROOT_URI"] = "127.0.0.1"
os.environ["DMLC_PS_ROOT_PORT"] = str(port)
import mxnet_tpu as mx
from mxnet_tpu import kvstore as kvs
from mxnet_tpu import optimizer as opt
kv = kvs.create("dist_async")
assert kv.type == "dist_async"
kv.init("w", mx.nd.ones((4,)))
kv.set_optimizer(opt.SGD(learning_rate=0.1))
# async: every push applies the update server-side immediately
kv.push("w", mx.nd.ones((4,)))
kv.push("w", mx.nd.ones((4,)))
kv.barrier()
out = mx.nd.zeros((4,))
kv.pull("w", out=out)
np.save(sys.argv[4], out.asnumpy())
"""


def test_dist_async_localhost(tmp_path):
    """dist_async: per-push server-side updates, no sync barrier between
    pushes (parity: kvstore_dist_server.h async DataHandle;
    tests/nightly/dist_async_kvstore.py)."""
    import subprocess
    import sys

    from mxnet_tpu.kvstore_server import KVServer
    num_workers = 2
    port = 19231
    server = KVServer(port=port, num_workers=num_workers)
    t = threading.Thread(target=server.run, daemon=True)
    t.start()
    time.sleep(0.2)
    script = str(tmp_path / "aworker.py")
    with open(script, "w") as f:
        f.write(_ASYNC_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    outs = [str(tmp_path / f"aout{r}.npy") for r in range(num_workers)]
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), str(num_workers), str(port),
         outs[r]], env=env) for r in range(num_workers)]
    try:
        for p in procs:
            assert p.wait(timeout=120) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server._stop.set()
    # 4 pushes total (2 per worker), each applying w -= 0.1 * 1
    results = [np.load(o) for o in outs]
    for r in results:
        np.testing.assert_allclose(r, 1.0 - 0.4, rtol=1e-5)
    np.testing.assert_array_equal(results[0], results[1])


_PROFILED_WORKER = """
import os, sys
rank = int(sys.argv[1]); port = int(sys.argv[2])
os.environ["DMLC_RANK"] = str(rank)
os.environ["DMLC_NUM_WORKER"] = "2"
os.environ["DMLC_PS_ROOT_URI"] = "127.0.0.1"
os.environ["DMLC_PS_ROOT_PORT"] = str(port)
import mxnet_tpu as mx
from mxnet_tpu import kvstore as kvs
kv = kvs.create("dist_sync")
if rank == 0:
    # only rank 0 drives the server profiler (reference contract:
    # commands come from one worker)
    mx.profiler.set_kvstore_handle(kv)
    mx.profiler.set_config(filename=sys.argv[3], aggregate_stats=True)
    mx.profiler.start()
kv.init("w", mx.nd.ones((4,)))
kv.push("w", mx.nd.ones((4,)))
kv.barrier()
out = mx.nd.zeros((4,))
kv.pull("w", out=out)
if rank == 0:
    mx.profiler.stop()
    mx.profiler.dump()
"""


def test_server_side_profiling(tmp_path):
    """Worker profiler commands reach the PS (parity: reference
    KVStoreServerProfilerCommand, include/mxnet/kvstore.h:49 +
    tests/nightly/test_server_profiling.py): set_kvstore_handle routes
    set_config/start/stop/dump to the server, which writes its own
    *_server.json trace."""
    import subprocess
    import sys

    from mxnet_tpu import profiler
    from mxnet_tpu.kvstore_server import KVServer
    port = 19677  # unique repo-wide: 19671 is test_failure_recovery's
    server = KVServer(port=port, num_workers=2)
    t = threading.Thread(target=server.run, daemon=True)
    t.start()
    time.sleep(0.2)
    fname = str(tmp_path / "prof.json")
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_PROFILED_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    saved = dict(profiler._config)
    try:
        procs = [subprocess.Popen(
            [sys.executable, script, str(r), str(port), fname],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            for r in range(2)]
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err.decode()
        # worker wrote its own trace ...
        assert os.path.exists(fname)
        # ... and the server (this process, via the command channel)
        # wrote the _server variant
        server_trace = str(tmp_path / "prof_server.json")
        assert os.path.exists(server_trace), os.listdir(tmp_path)
    finally:
        server._stop.set()
        profiler._config.update(saved)
        profiler._state["kvstore"] = None


def test_refuse_nonloopback_bind_without_token(monkeypatch):
    """Security contract: pickle-over-TCP must never listen beyond loopback
    unauthenticated (unauthenticated pickle = remote code execution)."""
    from mxnet_tpu.kvstore_server import KVServer
    monkeypatch.delenv("MXNET_KVSTORE_AUTH_TOKEN", raising=False)
    monkeypatch.delenv("MXNET_KVSTORE_ALLOW_INSECURE", raising=False)
    with pytest.raises(RuntimeError, match="non-loopback"):
        KVServer(port=0, num_workers=1, bind_addr="0.0.0.0")
    # loopback without a token stays allowed (the default deployment)
    KVServer(port=0, num_workers=1, bind_addr="127.0.0.1")
    # a token unlocks non-loopback
    KVServer(port=0, num_workers=1, bind_addr="0.0.0.0", auth_token="s3cret")
    # the documented escape hatch for trusted private networks
    monkeypatch.setenv("MXNET_KVSTORE_ALLOW_INSECURE", "1")
    KVServer(port=0, num_workers=1, bind_addr="0.0.0.0")
