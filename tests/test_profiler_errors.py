"""Profiler device-time capture + async-error-at-sync-point contract
(reference: src/profiler/profiler.h:260 engine-integrated profiling;
threaded_engine.cc:422-451 exception rethrow at WaitToRead/WaitForAll,
tests/python/unittest/test_exc_handling.py)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError


def test_profiler_records_imperative_and_jit():
    from mxnet_tpu.gluon import nn
    profiler.set_config(profile_imperative=True, aggregate_stats=True)
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.Dense(4))
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.randn(2, 16).astype(np.float32))
    net(x)  # build the jit cache outside the profiled region
    profiler.start()
    y = nd.dot(x, x.T)
    y.wait_to_read()
    net(x)
    profiler.stop()
    table = profiler.dumps()
    assert "dot" in table
    assert "CachedOp" in table          # jit path captured
    # device-time capture: recorded durations are nonzero
    stats = [l for l in table.splitlines() if "dot" in l]
    assert stats and float(stats[0].split()[-1]) >= 0.0


def test_profiler_chrome_trace_dump(tmp_path):
    profiler.set_config(filename=str(tmp_path / "profile.json"))
    profiler.start()
    nd.ones((4, 4)).wait_to_read()
    (nd.ones((4, 4)) * 2).wait_to_read()
    profiler.stop()
    profiler.dump()
    import json
    doc = json.load(open(tmp_path / "profile.json"))
    assert "traceEvents" in doc and len(doc["traceEvents"]) >= 1
    ev = doc["traceEvents"][0]
    assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(ev)


def test_async_error_surfaces_as_mxnet_error_at_sync_point():
    """A device-side failure (host callback raising inside the async
    dispatch) must raise MXNetError at an MXNet-defined sync point —
    never a raw XLA error (reference async-exception contract)."""
    import mxnet_tpu.operator as op_mod

    class Boom(op_mod.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            raise RuntimeError("deliberate device-side failure")

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            pass

    @op_mod.register("boom_op")
    class BoomProp(op_mod.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def list_outputs(self):
            return ["out"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            return Boom()

    x = nd.ones((2, 2))
    with pytest.raises(MXNetError):
        out = nd.Custom(x, op_type="boom_op")
        out.asnumpy()   # the sync point


def test_waitall_raises_mxnet_error():
    import mxnet_tpu.operator as op_mod

    class Boom2(op_mod.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            raise RuntimeError("deliberate failure 2")

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            pass

    @op_mod.register("boom_op2")
    class Boom2Prop(op_mod.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def list_outputs(self):
            return ["out"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            return Boom2()

    x = nd.ones((2, 2))
    with pytest.raises(MXNetError):
        out = nd.Custom(x, op_type="boom_op2")
        nd.waitall()


def test_healthy_path_unaffected():
    x = nd.ones((3, 3))
    y = (x * 2 + 1)
    np.testing.assert_allclose(y.asnumpy(), 3.0)
    nd.waitall()


def test_profiler_api_events_and_json_dumps():
    """profile_api records sync-point events (reference c_api_profile.cc);
    dumps(format='json') returns the aggregate dict."""
    import mxnet_tpu as mx
    mx.profiler.set_config(profile_api=True, aggregate_stats=True)
    mx.profiler.start()
    try:
        x = mx.nd.ones((4, 4))
        (x * 2).asnumpy()
        mx.nd.waitall()
    finally:
        mx.profiler.stop()
    agg = mx.profiler.dumps(format="json", reset=True)
    names = set(agg)
    assert "MXNDArraySyncCopyToCPU" in names, names
    assert "MXNDArrayWaitAll" in names, names
    for v in agg.values():
        assert v["count"] >= 1 and v["total_ms"] >= 0
    mx.profiler.set_config(profile_api=False)


def test_profiler_counter_and_marker_events(tmp_path):
    """Counters emit chrome-trace 'C' samples; aggregate table ignores
    them (they have no duration)."""
    import json
    import mxnet_tpu as mx
    fname = str(tmp_path / "trace.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.start()
    try:
        dom = mx.profiler.Domain("test")
        ctr = dom.new_counter("queue_depth", 0)
        ctr.set_value(5)
        ctr += 3
        dom.new_marker("epoch_end").mark()
    finally:
        mx.profiler.stop()
    mx.profiler.dump()
    events = json.load(open(fname))["traceEvents"]
    cs = [e for e in events if e.get("ph") == "C"
          and e["name"] == "test:queue_depth"]
    assert [e["args"]["value"] for e in cs] == [5, 8]
    table = mx.profiler.dumps(reset=True)
    assert "queue_depth" not in table  # counters aren't duration rows


def test_profiler_continuous_dump(tmp_path):
    import json
    import time as _t
    import mxnet_tpu as mx
    fname = str(tmp_path / "cont.json")
    mx.profiler.set_config(filename=fname, continuous_dump=True,
                           dump_period=0.05)
    mx.profiler.start()
    try:
        x = mx.nd.ones((2, 2))
        (x + 1).asnumpy()
        deadline = _t.time() + 5
        while not os.path.exists(fname) and _t.time() < deadline:
            _t.sleep(0.02)
    finally:
        mx.profiler.stop()
        mx.profiler.set_config(continuous_dump=False)
    assert os.path.exists(fname), "periodic dump never fired"
    json.load(open(fname))  # valid JSON
    mx.profiler.dumps(reset=True)


def test_profiler_autostart_env(tmp_path):
    """MXNET_PROFILER_AUTOSTART starts profiling at import
    (reference env_var.md:193-197)."""
    import subprocess
    import sys
    code = (
        "import mxnet_tpu as mx\n"
        "assert mx.profiler.is_running()\n"
        "x = mx.nd.ones((2,2)); (x+1).asnumpy()\n"
        "mx.profiler.stop()\n"
        "assert 'broadcast' in mx.profiler.dumps() or "
        "'_plus_scalar' in mx.profiler.dumps()\n"
        "print('AUTOSTART-OK')\n")
    env = dict(os.environ)
    env["MXNET_PROFILER_AUTOSTART"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert "AUTOSTART-OK" in r.stdout
