"""mxnet_tpu.serving — dynamic-batching inference serving.

Covers the ISSUE-1 acceptance criteria: batched == unbatched to 1e-6
through the padding/unpadding path, DynamicBatcher(max_batch_size=32)
sustains >= 3x sequential Predictor.forward throughput on the same
model, saturated queues shed with a structured MXNetError instead of
hanging — plus the batcher edge cases (deadline flush, micro-batch
splits, per-request timeouts, hot reload mid-traffic, graceful drain)
and the c_predict executor-cache regression (counter assert).
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (DynamicBatcher, ExecutorCache,
                               ModelRepository, ModelServer,
                               RequestTimeoutError, ServingClosedError,
                               ServingOverloadError, bucket_batch, pad_to)


def _mlp(hidden=8, out=3, in_dim=4):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, activation="relu"),
            gluon.nn.Dense(out))
    net.initialize()
    net(mx.nd.zeros((1, in_dim)))  # materialize deferred-init params
    return net


# -- bucketing / padding primitives -----------------------------------------
def test_bucket_batch():
    assert [bucket_batch(n) for n in (1, 2, 3, 5, 8, 9, 17)] == \
        [1, 2, 4, 8, 8, 16, 32]
    assert bucket_batch(5, max_batch=6) == 6  # cap wins, even non-pow2
    assert bucket_batch(32, max_batch=32) == 32
    with pytest.raises(MXNetError):
        bucket_batch(33, max_batch=32)
    with pytest.raises(MXNetError):
        bucket_batch(0)


def test_pad_to():
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    p = pad_to(a, 4)
    assert p.shape == (4, 3)
    np.testing.assert_array_equal(p[:2], a)
    np.testing.assert_array_equal(p[2:], 0)
    assert pad_to(a, 2) is a  # no copy when already sized
    with pytest.raises(MXNetError):
        pad_to(a, 1)


# -- numerics: batched+padded vs unbatched oracle ---------------------------
def test_padding_numerics_vs_unbatched_oracle():
    net = _mlp()
    xs = np.random.randn(5, 4).astype(np.float32)
    oracle = net(mx.nd.array(xs)).asnumpy()
    with ModelServer(max_batch_size=8, max_latency_ms=3.0,
                     name="t-numerics") as server:
        server.load("mlp", block=net)
        # 5 concurrent requests coalesce into one padded bucket-8 batch
        futs = [server.predict_async("mlp", {"data": xs[i]})
                for i in range(5)]
        outs = [f.result(60) for f in futs]
    for i, out in enumerate(outs):
        assert out[0].shape == (3,)
        np.testing.assert_allclose(out[0], oracle[i], atol=1e-6)


# -- batcher edge cases ------------------------------------------------------
def test_deadline_flush_partial_batch():
    sizes = []

    def runner(feed, n):
        sizes.append(n)
        return [feed["x"] * 2.0]

    b = DynamicBatcher(runner, max_batch_size=32, max_latency_ms=40.0,
                       name="t-deadline")
    t0 = time.perf_counter()
    futs = [b.submit({"x": np.full((2,), float(i), np.float32)})
            for i in range(3)]
    outs = [f.result(10) for f in futs]
    elapsed = time.perf_counter() - t0
    b.close()
    # 3 < max_batch_size: only the deadline can have flushed this batch
    assert sum(sizes) == 3 and max(sizes) <= 3
    assert elapsed < 5.0
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o[0], 2.0 * i)


def test_micro_batch_split_on_burst():
    sizes = []

    def runner(feed, n):
        sizes.append(n)
        return [feed["x"] + 1.0]

    b = DynamicBatcher(runner, max_batch_size=4, max_latency_ms=20.0,
                       max_queue_depth=64, name="t-burst")
    futs = [b.submit({"x": np.float32(i)}) for i in range(10)]
    outs = [f.result(10) for f in futs]
    b.close()
    assert sum(sizes) == 10
    assert max(sizes) <= 4  # burst split into micro-batches
    for i, o in enumerate(outs):
        assert o[0] == pytest.approx(i + 1.0)


def test_load_shed_error_shape():
    gate = threading.Event()
    entered = threading.Event()

    def runner(feed, n):
        entered.set()
        gate.wait(30)
        return [feed["x"]]

    b = DynamicBatcher(runner, max_batch_size=1, max_latency_ms=1.0,
                       max_queue_depth=4, shed_watermark=4,
                       num_workers=1, name="t-shed")
    # worker grabs the first request and blocks on the gate; the next 4
    # fill the queue to the watermark
    accepted = [b.submit({"x": np.float32(0)})]
    assert entered.wait(10)  # request 0 is in flight, queue is empty
    accepted += [b.submit({"x": np.float32(i)}) for i in range(1, 5)]
    with pytest.raises(ServingOverloadError) as ei:
        b.submit({"x": np.float32(99)})
    err = ei.value
    assert isinstance(err, MXNetError)  # structured MXNetError subclass
    assert err.watermark == 4 and err.queue_depth >= 4
    assert err.batcher == "t-shed"
    assert "shed" in str(err) and "watermark" in str(err)
    assert b.metrics.get("shed_total") == 1
    gate.set()  # nothing hangs: every accepted request completes
    for f in accepted:
        f.result(10)
    b.close()


def test_per_request_timeout():
    gate = threading.Event()

    def runner(feed, n):
        gate.wait(30)
        return [feed["x"]]

    b = DynamicBatcher(runner, max_batch_size=1, max_latency_ms=1.0,
                       num_workers=1, name="t-timeout")
    slow = b.submit({"x": np.float32(0)})       # occupies the worker
    doomed = b.submit({"x": np.float32(1)}, timeout_ms=50)
    time.sleep(0.2)
    gate.set()
    slow.result(10)
    with pytest.raises(RequestTimeoutError) as ei:
        doomed.result(10)
    assert ei.value.timeout_ms == pytest.approx(50, abs=1)
    assert ei.value.waited_ms >= 50
    assert b.metrics.get("timeouts_total") == 1
    b.close()


def test_hot_reload_mid_traffic_returns_new_version():
    net = _mlp()
    sym = net._cached_graph[1] if net._cached_graph else \
        net._build_sym_graph()[1]
    params_v1 = {k: p._reduce() for k, p in net.collect_params().items()}
    params_v2 = {k: v * 2.0 for k, v in params_v1.items()}
    x = np.random.randn(4).astype(np.float32)
    oracle_v1 = net(mx.nd.array(x[None])).asnumpy()[0]

    server = ModelServer(max_batch_size=4, max_latency_ms=2.0,
                         name="t-reload")
    assert server.load("m", symbol=sym, params=params_v1) == 1
    np.testing.assert_allclose(
        server.predict("m", {"data": x})[0], oracle_v1, atol=1e-6)

    stop = threading.Event()
    seen, bad = [], []

    def traffic():
        while not stop.is_set():
            try:
                seen.append(server.predict("m", {"data": x})[0])
            except MXNetError as e:  # pragma: no cover - contract breach
                bad.append(e)
                return

    t = threading.Thread(target=traffic)
    t.start()
    time.sleep(0.15)
    assert server.load("m", symbol=sym, params=params_v2) == 2  # hot reload
    # biases are zero at init, so doubling every param scales the ReLU
    # MLP output by exactly 2*2 = 4x — a clean v2 fingerprint
    oracle_v2 = 4.0 * oracle_v1
    deadline = time.perf_counter() + 20
    while time.perf_counter() < deadline:
        if seen and np.allclose(seen[-1], oracle_v2, atol=1e-5):
            break
        time.sleep(0.02)
    stop.set()
    t.join(30)
    server.shutdown()
    assert not bad, f"traffic failed during reload: {bad[0]}"
    assert seen, "no traffic completed"
    # the new version was picked up mid-traffic
    np.testing.assert_allclose(seen[-1], oracle_v2, atol=1e-5)
    # every response was EITHER v1 or v2 — never a torn mixture
    for out in seen:
        assert (np.allclose(out, oracle_v1, atol=1e-5)
                or np.allclose(out, oracle_v2, atol=1e-5))
    assert server.repository.latest_version("m") == 2


def test_shutdown_drains_in_flight():
    def runner(feed, n):
        time.sleep(0.05)
        return [feed["x"] * 3.0]

    b = DynamicBatcher(runner, max_batch_size=2, max_latency_ms=1.0,
                       num_workers=1, name="t-drain")
    futs = [b.submit({"x": np.float32(i)}) for i in range(6)]
    b.close(drain=True)  # returns only after the queue is drained
    for i, f in enumerate(futs):
        assert f.done()
        assert f.result(0.1)[0] == pytest.approx(3.0 * i)
    with pytest.raises(ServingClosedError):
        b.submit({"x": np.float32(0)})


def test_shutdown_no_drain_fails_queued_fast():
    gate = threading.Event()

    def runner(feed, n):
        gate.wait(30)
        return [feed["x"]]

    b = DynamicBatcher(runner, max_batch_size=1, max_latency_ms=1.0,
                       num_workers=1, name="t-nodrain")
    futs = [b.submit({"x": np.float32(i)}) for i in range(4)]
    time.sleep(0.1)  # worker holds request 0 at the gate
    gate.set()
    b.close(drain=False)
    outcomes = []
    for f in futs:
        try:
            f.result(10)
            outcomes.append("ok")
        except ServingClosedError:
            outcomes.append("closed")
    # the in-flight request may finish; everything still queued fails
    # fast with the structured shutdown error — nothing hangs
    assert "closed" in outcomes
    assert all(o in ("ok", "closed") for o in outcomes)


# -- executor cache ----------------------------------------------------------
def test_executor_cache_lru_eviction():
    cache = ExecutorCache(capacity=2)
    built = []

    def builder(tag):
        def b():
            built.append(tag)
            return tag
        return b

    cache.get(("a",), builder("a"))
    cache.get(("b",), builder("b"))
    cache.get(("a",), builder("a"))       # hit, refreshes LRU order
    cache.get(("c",), builder("c"))       # evicts b
    cache.get(("b",), builder("b"))       # miss again
    st = cache.stats()
    assert built == ["a", "b", "c", "b"]
    assert st["hits"] == 1 and st["misses"] == 4
    assert st["evictions"] == 2 and st["size"] == 2


def test_predictor_routes_through_executor_cache(tmp_path):
    """c_predict regression: two same-shape binds = one compile-bind,
    second is a cache hit (counter assert)."""
    from mxnet_tpu.c_predict import Predictor
    from mxnet_tpu.serving.executor_cache import shared_cache
    # distinctive dims so the content hash can't collide with models
    # built by other tests (the cache is process-wide)
    net = _mlp(hidden=11, out=7)
    x = np.random.randn(2, 4).astype(np.float32)
    ref = net(mx.nd.array(x)).asnumpy()
    prefix = str(tmp_path / "mlp")
    net.export(prefix)
    sym_json = open(prefix + "-symbol.json").read()
    params = open(prefix + "-0000.params", "rb").read()

    before = shared_cache().stats()
    outs = []
    for _ in range(2):  # fresh Predictor per request: the reference shape
        p = Predictor(sym_json, params, {"data": (2, 4)})
        p.set_input("data", x.tobytes())
        p.forward()
        outs.append(np.frombuffer(p.output_bytes(0),
                                  np.float32).reshape(2, 7))
    after = shared_cache().stats()
    assert after["misses"] == before["misses"] + 1  # bound exactly once
    assert after["hits"] >= before["hits"] + 1      # second call: cache hit
    for o in outs:
        np.testing.assert_allclose(o, ref, rtol=1e-5, atol=1e-6)


def test_concurrent_predictors_do_not_clobber_shared_executor(tmp_path):
    """Two live Predictors share one CachedExecutor; interleaved and
    concurrent set_input/forward/output_bytes must stay isolated."""
    from mxnet_tpu.c_predict import Predictor
    net = _mlp(hidden=13, out=6)
    xs = np.random.randn(8, 1, 4).astype(np.float32)
    ref = [net(mx.nd.array(x)).asnumpy() for x in xs]
    prefix = str(tmp_path / "mlp")
    net.export(prefix)
    sym_json = open(prefix + "-symbol.json").read()
    params = open(prefix + "-0000.params", "rb").read()

    p1 = Predictor(sym_json, params, {"data": (1, 4)})
    p2 = Predictor(sym_json, params, {"data": (1, 4)})
    assert p1._cached is p2._cached  # genuinely shared

    # single-threaded interleaving: p1.set_input, p2.set_input,
    # p1.forward, p2.forward — the exact clobber pattern from REVIEW
    p1.set_input("data", xs[0].tobytes())
    p2.set_input("data", xs[1].tobytes())
    p1.forward()
    p2.forward()
    o1 = np.frombuffer(p1.output_bytes(0), np.float32).reshape(1, 6)
    o2 = np.frombuffer(p2.output_bytes(0), np.float32).reshape(1, 6)
    np.testing.assert_allclose(o1, ref[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o2, ref[1], rtol=1e-5, atol=1e-6)

    # p2 forwarding again must not invalidate p1's already-read outputs
    p2.set_input("data", xs[2].tobytes())
    p2.forward()
    o1_again = np.frombuffer(p1.output_bytes(0), np.float32).reshape(1, 6)
    np.testing.assert_allclose(o1_again, ref[0], rtol=1e-5, atol=1e-6)

    # concurrent threads hammering their own Predictor
    bad = []

    def worker(p, idx):
        for _ in range(25):
            p.set_input("data", xs[idx].tobytes())
            p.forward()
            out = np.frombuffer(p.output_bytes(0),
                                np.float32).reshape(1, 6)
            if not np.allclose(out, ref[idx], rtol=1e-5, atol=1e-6):
                bad.append(idx)
                return

    threads = [threading.Thread(target=worker, args=(p, i))
               for i, p in enumerate((p1, p2,
                                      Predictor(sym_json, params,
                                                {"data": (1, 4)})))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not bad, f"cross-Predictor clobber on indices {bad}"


# -- request validation / batch isolation ------------------------------------
def test_malformed_request_rejected_individually():
    """A bad request fails at submit() with a structured error and never
    poisons the micro-batch its well-formed neighbours ride in."""
    net = _mlp()
    xs = np.random.randn(6, 4).astype(np.float32)
    oracle = net(mx.nd.array(xs)).asnumpy()
    with ModelServer(max_batch_size=8, max_latency_ms=20.0,
                     name="t-malformed") as server:
        server.load("m", block=net)
        futs = [server.predict_async("m", {"data": xs[i]})
                for i in range(3)]
        # wrong per-sample shape: rejected synchronously, alone
        with pytest.raises(MXNetError, match="incompatible"):
            server.predict_async("m", {"data": np.zeros(7, np.float32)})
        # missing input key: rejected synchronously, alone
        with pytest.raises(MXNetError, match="do not match"):
            server.predict_async("m", {"wrong": xs[0]})
        # unexpected extra key: rejected synchronously, alone
        with pytest.raises(MXNetError, match="unexpected"):
            server.predict_async("m", {"data": xs[0], "extra": xs[0]})
        futs += [server.predict_async("m", {"data": xs[i]})
                 for i in range(3, 6)]
        outs = [f.result(60) for f in futs]
        assert server.metrics.get("invalid_total") == 3
    for i, out in enumerate(outs):  # the innocents all answered correctly
        np.testing.assert_allclose(out[0], oracle[i], atol=1e-6)


def test_batcher_signature_cohorts_isolate_mismatched_shapes():
    """Raw DynamicBatcher (no validator): requests with different input
    signatures execute in separate cohorts instead of one np.stack that
    throws for everyone."""
    ran = []

    def runner(feed, n):
        ran.append((feed["x"].shape, n))
        return [feed["x"] * 2.0]

    b = DynamicBatcher(runner, max_batch_size=8, max_latency_ms=30.0,
                       num_workers=1, name="t-cohort")
    f_a = [b.submit({"x": np.full((3,), float(i), np.float32)})
           for i in range(2)]
    f_b = b.submit({"x": np.zeros((5,), np.float32)})  # mismatched shape
    for i, f in enumerate(f_a):
        np.testing.assert_allclose(f.result(10)[0], 2.0 * i)
    np.testing.assert_allclose(f_b.result(10)[0], np.zeros(5))
    b.close()
    assert {shape[1:] for shape, _ in ran} == {(3,), (5,)}


def test_integer_inputs_preserve_dtype():
    """Int inputs (token ids / indices) must not be cast to float32 —
    16777217 is the first integer float32 cannot represent."""
    data = mx.sym.var("data")
    out = data + 1
    with ModelServer(max_batch_size=4, max_latency_ms=2.0,
                     name="t-dtype") as server:
        server.load("ids", symbol=out, params={})
        big = np.array([16777217, 3], dtype=np.int32)
        res = server.predict("ids", {"data": big})[0]
        assert res.dtype == np.int32, f"int32 in, {res.dtype} out"
        np.testing.assert_array_equal(res, big + 1)
        # float traffic on the same model binds its own program
        fres = server.predict(
            "ids", {"data": np.array([0.5, 1.5], np.float32)})[0]
        assert fres.dtype == np.float32
        np.testing.assert_allclose(fres, [1.5, 2.5])


# -- repository --------------------------------------------------------------
def test_repository_versioning_and_errors(tmp_path):
    net = _mlp()
    prefix = str(tmp_path / "m")
    net.export(prefix)
    repo = ModelRepository()
    assert repo.load("m", prefix=prefix) == 1
    assert repo.load("m", prefix=prefix) == 2        # auto-increment
    assert repo.get("m").version == 2                # latest by default
    assert repo.get("m", version=1).version == 1
    assert repo.get("m").input_names == ["data"]
    assert repo.models() == {"m": [1, 2]}
    repo.unload("m", version=2)
    assert repo.latest_version("m") == 1             # latest recomputed
    with pytest.raises(MXNetError, match="unknown model"):
        repo.get("nope")
    with pytest.raises(MXNetError, match="no version"):
        repo.get("m", version=9)
    with pytest.raises(MXNetError, match="already loaded"):
        repo.load("m", prefix=prefix, version=1)
    with pytest.raises(MXNetError, match="exactly one"):
        repo.load("m2")


# -- acceptance: 3x throughput + saturation sheds ----------------------------
def test_dynamic_batcher_3x_sequential_predictor(tmp_path):
    """ISSUE-1 acceptance: DynamicBatcher(max_batch_size=32) >= 3x the
    throughput of one-request-at-a-time Predictor.forward on the SAME
    model, outputs matching the unbatched oracle to 1e-6."""
    from mxnet_tpu.c_predict import Predictor
    net = _mlp(hidden=64, out=8)
    prefix = str(tmp_path / "m")
    net.export(prefix)
    sym_json = open(prefix + "-symbol.json").read()
    params = open(prefix + "-0000.params", "rb").read()
    n_req = 256
    xs = np.random.randn(n_req, 4).astype(np.float32)
    oracle = net(mx.nd.array(xs)).asnumpy()

    # sequential baseline: one request at a time through the Predictor
    pred = Predictor(sym_json, params, {"data": (1, 4)})
    pred.set_input("data", xs[0:1].tobytes())
    pred.forward()  # warm (compile outside the timed window)
    t0 = time.perf_counter()
    seq_out = np.empty((n_req, 8), np.float32)
    for i in range(n_req):
        pred.set_input("data", xs[i:i + 1].tobytes())
        pred.forward()
        seq_out[i] = np.frombuffer(pred.output_bytes(0),
                                   np.float32).reshape(1, 8)[0]
    seq_rps = n_req / (time.perf_counter() - t0)
    np.testing.assert_allclose(seq_out, oracle, atol=1e-5)

    with ModelServer(max_batch_size=32, max_latency_ms=4.0,
                     max_queue_depth=2 * n_req, name="t-accept") as server:
        server.load("m", block=net)
        # warm every bucket a closed-loop burst can hit
        warm = [server.predict_async("m", {"data": xs[i]})
                for i in range(64)]
        for f in warm:
            f.result(60)
        t0 = time.perf_counter()
        futs = [server.predict_async("m", {"data": xs[i]})
                for i in range(n_req)]
        outs = [f.result(60) for f in futs]
        batched_rps = n_req / (time.perf_counter() - t0)
        snap = server.stats()

    for i, o in enumerate(outs):
        np.testing.assert_allclose(o[0], oracle[i], atol=1e-6)
    assert snap["batches_total"] >= 1
    assert batched_rps >= 3.0 * seq_rps, (
        f"batched {batched_rps:.0f} req/s vs sequential {seq_rps:.0f} "
        f"req/s — expected >= 3x")


def test_saturated_server_sheds_instead_of_hanging():
    net = _mlp()
    server = ModelServer(max_batch_size=4, max_latency_ms=2.0,
                         max_queue_depth=8, shed_watermark=8,
                         name="t-saturate")
    server.load("m", block=net)
    server.predict("m", {"data": np.zeros(4, np.float32)})  # warm
    futs, sheds = [], 0
    for i in range(400):
        try:
            futs.append(server.predict_async(
                "m", {"data": np.random.randn(4).astype(np.float32)}))
        except ServingOverloadError as e:
            assert isinstance(e, MXNetError)
            assert e.watermark == 8
            sheds += 1
    for f in futs:
        f.result(60)  # every accepted request completes — no hangs
    server.shutdown()
    assert sheds > 0, "queue never saturated: shed path untested"
    assert server.metrics.get("shed_total") == sheds


# -- observability / config ---------------------------------------------------
def test_stats_snapshot_and_config_knobs():
    net = _mlp()
    with ModelServer(max_batch_size=8, max_latency_ms=2.0,
                     name="t-stats") as server:
        server.load("m", block=net)
        for _ in range(10):
            server.predict("m", {"data": np.random.randn(4).astype(
                np.float32)})
        snap = server.stats()
    assert snap["responses_total"] == 10
    assert snap["requests_total"] == 10
    lat = snap["latency_ms"]
    assert lat["samples"] == 10 and lat["p50"] <= lat["p99"]
    assert snap["throughput_rps"] > 0
    assert 0 < snap["batch_occupancy"] <= 1.0
    assert snap["executor_cache"]["misses"] >= 1
    assert snap["models"] == {"m": [1]}
    # module-level aggregate includes this server by name
    assert "t-stats" in serving.stats()
    # knobs are registered and discoverable
    desc = mx.config.describe()
    for knob in ("MXNET_SERVING_MAX_BATCH", "MXNET_SERVING_MAX_LATENCY_MS",
                 "MXNET_SERVING_QUEUE_DEPTH", "MXNET_SERVING_SHED_WATERMARK",
                 "MXNET_SERVING_EXECUTOR_CACHE"):
        assert knob in desc


def test_serving_counters_reach_profiler_trace(tmp_path):
    from mxnet_tpu import profiler
    net = _mlp()
    fname = str(tmp_path / "serve_profile.json")
    profiler.set_config(filename=fname)
    profiler.start()
    try:
        with ModelServer(max_batch_size=4, max_latency_ms=2.0,
                         name="t-prof") as server:
            server.load("m", block=net)
            server.predict("m", {"data": np.zeros(4, np.float32)})
    finally:
        profiler.stop()
    profiler.dump()
    import json
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    lanes = {e["name"] for e in events if e.get("ph") == "C"}
    assert any(name.startswith("serving:t-prof:") for name in lanes), lanes


# -- module predict-path bucketing -------------------------------------------
def test_module_partial_batch_pads_instead_of_rebinding():
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=5, name="fc")
    out = mx.sym.softmax(fc, name="sm")
    mod = mx.mod.Module(out, data_names=("data",), label_names=None)
    mod.bind(data_shapes=[("data", (8, 6))], for_training=False)
    mod.init_params()
    bound_exec = mod._exec
    from collections import namedtuple
    Batch = namedtuple("Batch", ["data", "label", "pad"])
    xfull = np.random.randn(8, 6).astype(np.float32)
    mod.forward(Batch([mx.nd.array(xfull)], None, 0), is_train=False)
    full_out = mod.get_outputs()[0].asnumpy()
    # partial final batch: padded up to the bound batch, NOT rebound
    mod.forward(Batch([mx.nd.array(xfull[:3])], None, 0), is_train=False)
    part_out = mod.get_outputs()[0].asnumpy()
    assert mod._exec is bound_exec, "partial predict batch rebound the " \
        "executor instead of padding"
    assert part_out.shape == (3, 5)
    np.testing.assert_allclose(part_out, full_out[:3], rtol=1e-5, atol=1e-6)
    # growing back to the full batch reuses the same executor too
    mod.forward(Batch([mx.nd.array(xfull)], None, 0), is_train=False)
    assert mod._exec is bound_exec
    np.testing.assert_allclose(mod.get_outputs()[0].asnumpy(), full_out,
                               rtol=1e-5, atol=1e-6)


def test_partial_batch_slices_only_batch_carrying_outputs():
    """An output whose leading dim COINCIDENTALLY equals the bound batch
    (here a (6,6) gram matrix under a batch of 6) must not be pad-sliced
    after a padded partial-batch forward."""
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=5, name="fc")
    gram = mx.sym.dot(mx.sym.transpose(data), data)  # (in, in) = (6, 6)
    out = mx.symbol.Group([fc, gram])
    mod = mx.mod.Module(out, data_names=("data",), label_names=None)
    mod.bind(data_shapes=[("data", (6, 6))], for_training=False)
    mod.init_params()
    from collections import namedtuple
    Batch = namedtuple("Batch", ["data", "label", "pad"])
    x = np.random.randn(6, 6).astype(np.float32)
    # partial batch of 2 -> padded to the bound 6; zero pad rows do not
    # change X^T X, so the unsliced gram output must come back (6, 6)
    mod.forward(Batch([mx.nd.array(x[:2])], None, 0), is_train=False)
    fc_out, gram_out = mod.get_outputs()
    assert mod._forward_pad == 4  # the pad path actually ran
    assert fc_out.shape == (2, 5)          # batch output: sliced
    assert gram_out.shape == (6, 6)        # non-batch output: untouched
    np.testing.assert_allclose(gram_out.asnumpy(), x[:2].T @ x[:2],
                               rtol=1e-4, atol=1e-5)


# -- continuous batching (ISSUE 10 tentpole) ---------------------------------
def test_continuous_admission_joins_forming_batch_on_oldest_anchor():
    """A same-signature request arriving while a batch forms JOINS it,
    and the flush deadline stays anchored at the OLDEST member — the
    late joiner does not extend the wait."""
    calls = []  # (n_real, t)

    def runner(feed, n):
        calls.append((n, time.perf_counter()))
        return [feed["x"] * 2.0]

    b = DynamicBatcher(runner, max_batch_size=8, max_latency_ms=80.0,
                       num_workers=1, name="t-joins")
    try:
        t0 = time.perf_counter()
        f1 = b.submit({"x": np.float32(1.0)})
        time.sleep(0.03)  # the batch is already forming
        f2 = b.submit({"x": np.float32(2.0)})
        assert f1.result(10)[0] == pytest.approx(2.0)
        assert f2.result(10)[0] == pytest.approx(4.0)
        # one runner call: the late arrival rode the forming batch
        assert [n for n, _ in calls] == [2]
        # flush anchored at f1's enqueue (80ms), NOT f2's (would be 110)
        elapsed_ms = (calls[0][1] - t0) * 1e3
        assert 60.0 <= elapsed_ms <= 105.0, elapsed_ms
    finally:
        b.close()


def test_admitted_request_still_honors_its_own_timeout():
    """Satellite: a request admitted into a staged batch that expires
    before dispatch resolves as typed RequestTimeoutError, and its row
    is re-stacked OUT of the feed (a dead request never occupies a
    batch slot)."""
    gate = threading.Event()
    entered = threading.Event()
    sizes = []

    def runner(feed, n):
        sizes.append(n)
        if not entered.is_set():
            entered.set()
            gate.wait(30)
        return [feed["x"] * 2.0]

    b = DynamicBatcher(runner, max_batch_size=2, max_latency_ms=5.0,
                       num_workers=1, name="t-own-timeout")
    try:
        blocker = b.submit({"x": np.float32(0.0)})
        assert entered.wait(10)  # dispatch thread is now occupied
        ok = b.submit({"x": np.float32(1.0)})
        doomed = b.submit({"x": np.float32(2.0)}, timeout_ms=50)
        time.sleep(0.25)  # doomed expires while staged
        gate.set()
        assert blocker.result(10)[0] == pytest.approx(0.0)
        assert ok.result(10)[0] == pytest.approx(2.0)
        with pytest.raises(RequestTimeoutError):
            doomed.result(10)
        # the batch behind the blocker re-stacked to ONE live row
        assert sizes == [1, 1]
        assert b.metrics.get("timeouts_total") == 1
    finally:
        gate.set()
        b.close()


def test_mismatched_signature_dispatches_concurrently_not_serialized():
    """Continuous batching: a mismatched-signature arrival goes to the
    NEXT micro-batch and a sibling worker runs it WHILE the first
    cohort is still in flight — it is never serialized behind it."""
    gate = threading.Event()
    entered = threading.Event()

    def runner(feed, n):
        if feed["x"].shape[1:] == (3,):
            entered.set()
            gate.wait(30)
        return [feed["x"] * 2.0]

    b = DynamicBatcher(runner, max_batch_size=8, max_latency_ms=10.0,
                       num_workers=2, name="t-cohort-conc")
    try:
        fa = b.submit({"x": np.ones((3,), np.float32)})
        assert entered.wait(10)  # cohort A is wedged in its runner
        fb = b.submit({"x": np.ones((5,), np.float32)})
        # cohort B answers while A is STILL in flight
        np.testing.assert_allclose(fb.result(5)[0], 2.0 * np.ones(5))
        assert not fa.done()
        gate.set()
        np.testing.assert_allclose(fa.result(10)[0], 2.0 * np.ones(3))
    finally:
        gate.set()
        b.close()


# -- replica pools (ISSUE 10 tentpole) ----------------------------------------
def test_replica_pool_routes_around_busy_replica():
    """Load-aware routing: with replica 0 occupied, traffic flows to
    replica 1 instead of queueing behind the busy one."""
    from mxnet_tpu.serving import ReplicaPool
    gates = {0: threading.Event(), 1: threading.Event()}
    entered = {0: threading.Event(), 1: threading.Event()}

    def factory(rid):
        def run(feed, n):
            entered[rid].set()
            gates[rid].wait(30)
            return [feed["x"] * 2.0]
        return run

    pool = ReplicaPool(factory, num_replicas=2, name="t-route",
                       model="t-route", max_batch_size=4,
                       max_latency_ms=1.0, num_workers=1)
    try:
        f0 = pool.submit({"x": np.float32(1.0)})
        assert entered[0].wait(10)  # ties break by id: replica 0 first
        gates[1].set()  # replica 1 answers immediately
        f1 = pool.submit({"x": np.float32(2.0)})
        assert f1.result(5)[0] == pytest.approx(4.0)
        assert not f0.done()  # replica 0 still busy — it was bypassed
        gates[0].set()
        assert f0.result(10)[0] == pytest.approx(2.0)
    finally:
        for g in gates.values():
            g.set()
        pool.close()


def test_replica_pool_remove_replica_drains_no_drops():
    """Drain-on-removal: everything the removed replica admitted
    completes; the pool keeps serving on the survivors."""
    from mxnet_tpu.serving import ReplicaPool

    def factory(rid):
        def run(feed, n):
            time.sleep(0.01)
            return [feed["x"] + 1.0]
        return run

    pool = ReplicaPool(factory, num_replicas=2, name="t-drain-rm",
                       model="t-drain-rm", max_batch_size=2,
                       max_latency_ms=1.0, num_workers=1)
    try:
        futs = [pool.submit({"x": np.float32(i)}) for i in range(12)]
        victim_rid = pool.replica_ids()[0]
        victim = pool.remove_replica(victim_rid, drain=True)
        assert victim.occupancy() == 0  # drained, not dropped
        for i, f in enumerate(futs):
            assert f.result(10)[0] == pytest.approx(i + 1.0)
        assert pool.replica_ids() == [1]
        assert pool.submit({"x": np.float32(9)}).result(10)[0] == \
            pytest.approx(10.0)
    finally:
        pool.close()


def test_slo_admission_sheds_on_predicted_p99():
    """SLO admission control: once the service-rate EWMA x occupancy
    predicts a p99 above the SLO, submits shed synchronously as typed
    ServingOverloadError carrying the prediction — and the shed point
    moved with the measured rate, not a hand-set queue depth."""
    from mxnet_tpu.serving import ReplicaPool

    def factory(rid):
        def run(feed, n):
            time.sleep(0.005)
            return [feed["x"]]
        return run

    pool = ReplicaPool(factory, num_replicas=1, name="t-slo",
                       model="t-slo", slo_p99_ms=20.0, max_batch_size=4,
                       max_latency_ms=1.0, num_workers=1,
                       max_queue_depth=10_000, shed_watermark=10_000)
    try:
        sheds, futs = [], []
        for i in range(400):
            try:
                futs.append(pool.submit({"x": np.float32(i)}))
            except ServingOverloadError as e:
                sheds.append(e)
            time.sleep(0.0005)
        assert sheds, "prediction never crossed the SLO"
        e = sheds[0]
        assert e.predicted_p99_ms is not None
        assert e.predicted_p99_ms > e.slo_ms == 20.0
        assert pool.metrics.get("slo_shed_total") == len(sheds)
        # the watermark never entered into it — admission was purely
        # prediction-driven (the queue knobs are effectively unbounded)
        for f in futs:
            f.result(30)  # everything admitted completes
    finally:
        pool.close()


def test_wedged_replica_requests_resolve_typed_under_router():
    """Satellite: a replica wedged mid-dispatch under the ROUTER path
    behaves exactly like the single-batcher case — its claimed requests
    resolve as typed RequestTimeoutError via the in-flight sweep while
    siblings keep serving."""
    import mxnet_tpu.chaos as chaos
    from mxnet_tpu.serving import ReplicaPool

    def factory(rid):
        def run(feed, n):
            return [feed["x"] * 2.0]
        return run

    chaos.reset()
    chaos.arm("serving/batcher/worker", "wedge", hits=1, count=1)
    pool = ReplicaPool(factory, num_replicas=2, name="t-pool-wedge",
                       model="t-pool-wedge", max_batch_size=4,
                       max_latency_ms=1.0, num_workers=1)
    try:
        doomed = pool.submit({"x": np.float32(1.0)}, timeout_ms=200)
        time.sleep(0.1)  # a replica claims it and wedges
        for i in range(10):  # siblings keep serving and sweeping
            pool.submit({"x": np.float32(i)}).result(10)
        with pytest.raises(RequestTimeoutError):
            doomed.result(10)
    finally:
        chaos.release("serving/batcher/worker")
        chaos.reset()
        pool.close(timeout=5)


def test_replica_pool_throughput_scales_vs_single_batcher():
    """Replica pools exist to scale throughput: 3 replicas must beat
    one batcher by a clear margin on a service-time-dominated runner
    (a soft bar, to stay timing-robust)."""
    from mxnet_tpu.serving import ReplicaPool

    def factory(rid):
        def run(feed, n):
            time.sleep(0.002 * n + 0.001)
            return [feed["x"]]
        return run

    def saturate(pool, seconds=0.6, n_clients=12):
        done = [0]
        lock = threading.Lock()
        stop = time.perf_counter() + seconds

        def client():
            while time.perf_counter() < stop:
                try:
                    pool.submit({"x": np.float32(0)}).result(10)
                    with lock:
                        done[0] += 1
                except ServingOverloadError:
                    time.sleep(0.001)

        threads = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return done[0] / seconds

    kw = dict(max_batch_size=4, max_latency_ms=2.0, num_workers=1,
              max_queue_depth=128)
    single = ReplicaPool(factory, num_replicas=1, name="t-scale1",
                         model="t-scale1", **kw)
    try:
        saturate(single, 0.2)  # warm
        single_rps = saturate(single)
    finally:
        single.close()
    pool = ReplicaPool(factory, num_replicas=3, name="t-scale3",
                       model="t-scale3", **kw)
    try:
        pool_rps = saturate(pool)
    finally:
        pool.close()
    assert pool_rps >= 1.5 * single_rps, (
        f"pool {pool_rps:.0f} req/s vs single {single_rps:.0f} req/s")


def test_router_telemetry_families_exact_counts():
    """Satellite: the three router families land in the registry and
    the Prometheus dump with exact values — occupancy per replica,
    one spill for one rescued request, and a predicted p99 once the
    rate EWMA has samples."""
    import mxnet_tpu.chaos as chaos
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import ReplicaPool

    def factory(rid):
        def run(feed, n):
            return [feed["x"] * 2.0]
        return run

    occ_g = telemetry.REGISTRY.gauge("mxnet_serving_replica_occupancy")
    spill_c = telemetry.REGISTRY.counter(
        "mxnet_serving_router_spill_total")
    pred_g = telemetry.REGISTRY.gauge("mxnet_serving_predicted_p99_ms")
    spills0 = spill_c.value(labels={"model": "t-families"})

    pool = ReplicaPool(factory, num_replicas=2, name="t-families",
                       model="t-families", slo_p99_ms=10_000.0,
                       max_batch_size=4, max_latency_ms=1.0)
    try:
        pool.submit({"x": np.float32(1.0)}).result(10)
        # the first routing decision exported one occupancy sample per
        # replica (idle pool: 0 at sample time)
        for rid in ("0", "1"):
            assert occ_g.value(labels={"model": "t-families",
                                       "replica": rid}) == 0.0
        # exactly one injected dispatch fault -> exactly one spill
        chaos.arm("serving/router/dispatch", "raise", hits=1, count=1)
        pool.submit({"x": np.float32(2.0)}).result(10)
        assert spill_c.value(
            labels={"model": "t-families"}) == spills0 + 1
        # enough traffic spaced past the EWMA's minimum sample window
        # -> the predicted-p99 gauge carries a real prediction
        for _ in range(3):
            time.sleep(0.03)
            pool.submit({"x": np.float32(0.0)}).result(10)
        assert pred_g.value(labels={"model": "t-families"}) > 0.0
        dump = telemetry.prometheus_dump()
        for family in ("mxnet_serving_replica_occupancy",
                       "mxnet_serving_router_spill_total",
                       "mxnet_serving_predicted_p99_ms"):
            assert f"# TYPE {family}" in dump, family
        assert ('mxnet_serving_router_spill_total{model="t-families"}'
                in dump)
    finally:
        chaos.reset()
        pool.close()


def test_server_pools_resize_and_flip_hook():
    """ModelServer fronts each model with a pool: resize() scales it;
    a hot reload's flip hook retires stale-version executors (keeping
    {new, previous}) and resets the admission EWMA."""
    net = _mlp()
    sym = net._cached_graph[1] if net._cached_graph else \
        net._build_sym_graph()[1]
    params = {k: p._reduce() for k, p in net.collect_params().items()}
    x = np.random.randn(4).astype(np.float32)

    server = ModelServer(max_batch_size=4, max_latency_ms=2.0,
                         num_replicas=2, name="t-pools")
    try:
        assert server.load("m", symbol=sym, params=params) == 1
        server.predict("m", {"data": x})
        snap = server.stats()
        assert snap["pools"]["m"]["replicas"] == 2
        server.resize("m", 3)
        assert server.stats()["pools"]["m"]["replicas"] == 3
        server.predict("m", {"data": x})

        # learn a service rate, then hot reload twice: v1's executors
        # must retire from the cache after the v3 flip ({v3, v2} kept)
        pool = server._get_pool("m")
        for _ in range(3):
            time.sleep(0.03)
            server.predict("m", {"data": x})
        assert pool.admission.service_rate() is not None
        assert server.load("m", symbol=sym, params=params) == 2
        server.predict("m", {"data": x})
        assert server.load("m", symbol=sym, params=params) == 3
        assert pool.admission.service_rate() is None  # reset at flip
        versions_cached = {k[1] for k in server._cache._entries
                           if k[0] == "m"}
        assert 1 not in versions_cached
        server.predict("m", {"data": x})
    finally:
        server.shutdown()


# -- checkpoint-directory hot reload (ISSUE 2 satellite) --------------------
def test_repository_watch_serves_only_committed_checkpoints(tmp_path):
    """ModelRepository.poll_checkpoint picks up newly COMMITTED steps as
    new versions; an in-progress ``step-NNNNNN.tmp/`` is never served."""
    import os
    from mxnet_tpu.checkpoint import CheckpointManager, step_dir
    from mxnet_tpu.module import Module

    net = _mlp()
    ckdir = str(tmp_path / "ck")
    repo = ModelRepository()
    with CheckpointManager(ckdir, keep_last=0) as mgr:
        params = {f"arg:{k}": p._reduce()
                  for k, p in net.collect_params().items()}
        if not getattr(net, "_cached_graph", None):
            net._build_sym_graph()
        sym = net._cached_graph[1]
        mgr.save(1, arrays=params, symbol=sym, block=True)

        # first poll loads step 1 as version 1
        assert repo.poll_checkpoint("mlp", ckdir) == 1
        assert repo.latest_version("mlp") == 1
        # nothing new: no-op
        assert repo.poll_checkpoint("mlp", ckdir) is None

        # an in-progress step-2 tmp dir must NEVER be served
        tmp2 = step_dir(ckdir, 2) + ".tmp"
        os.makedirs(tmp2)
        with open(os.path.join(tmp2, "data-00000-of-00001.bin"), "wb") as f:
            f.write(b"torn")
        assert repo.poll_checkpoint("mlp", ckdir) is None
        assert repo.latest_version("mlp") == 1

        # commit step 2 for real -> hot reload as version 2
        mgr.save(2, arrays=params, symbol=sym, block=True)
        assert repo.poll_checkpoint("mlp", ckdir) == 2
        assert repo.latest_version("mlp") == 2
        # the loaded version actually serves: bind + forward
        mv = repo.get("mlp")
        assert mv.version == 2 and mv.input_names == ["data"]


def test_repository_watch_thread_hot_reloads(tmp_path):
    """The background watcher picks up a commit within its poll period."""
    import time as _time
    from mxnet_tpu.checkpoint import CheckpointManager

    net = _mlp()
    if not getattr(net, "_cached_graph", None):
        net._build_sym_graph()
    sym = net._cached_graph[1]
    params = {f"arg:{k}": p._reduce()
              for k, p in net.collect_params().items()}
    ckdir = str(tmp_path / "ck")
    repo = ModelRepository()
    with CheckpointManager(ckdir, keep_last=0) as mgr:
        mgr.save(1, arrays=params, symbol=sym, block=True)
        repo.watch("mlp", ckdir, interval=0.05)
        try:
            deadline = _time.time() + 10
            while _time.time() < deadline:
                try:
                    if repo.latest_version("mlp") == 1:
                        break
                except MXNetError:
                    pass
                _time.sleep(0.02)
            assert repo.latest_version("mlp") == 1
            mgr.save(7, arrays=params, symbol=sym, block=True)
            deadline = _time.time() + 10
            while repo.latest_version("mlp") != 7:
                assert _time.time() < deadline, \
                    "watcher never picked up the committed step"
                _time.sleep(0.02)
        finally:
            repo.unwatch("mlp")


def test_watch_warms_ladder_before_flip(tmp_path):
    """ISSUE 7 satellite: a checkpoint hot-reload warms the new
    version's full bucket ladder BEFORE the served-version pointer
    flips, so a version swap under load never serves a cold-compile
    request (zero executor-cache misses post-flip)."""
    from mxnet_tpu import compile as mxc
    from mxnet_tpu.checkpoint import CheckpointManager

    net = _mlp(in_dim=6)
    if not getattr(net, "_cached_graph", None):
        net._build_sym_graph()
    sym = net._cached_graph[1]
    params = {f"arg:{k}": p._reduce()
              for k, p in net.collect_params().items()}
    ckdir = str(tmp_path / "ck")
    server = ModelServer(max_batch_size=4, max_latency_ms=2.0,
                         name="flip")
    repo = server.repository
    at_hook = []  # (latest-at-hook-time, warmed sigs registered?)

    def probe_hook(name, mv):
        # registered AFTER the server's warm hook, so by the time this
        # runs the ladder must already be warmed — and the pointer must
        # not have flipped yet
        try:
            latest = repo.latest_version(name)
        except MXNetError:
            latest = 0
        at_hook.append((mv.version, latest,
                        mxc.warmed_signatures(name, mv.version)))

    repo.add_warm_hook(probe_hook)
    try:
        with CheckpointManager(ckdir, keep_last=0) as mgr:
            mgr.save(1, arrays=params, symbol=sym, block=True)
            assert repo.poll_checkpoint("flipm", ckdir) == 1
            # v1 had no traffic history: warmup skipped, recorded as such
            assert at_hook[0][0] == 1 and at_hook[0][2] is None

            # serve traffic on v1 so the shape census knows the model
            x = np.random.randn(6).astype(np.float32)
            for _ in range(4):
                server.predict("flipm", {"data": x}, wait_s=30.0)
            misses_v1 = server._cache.stats()["misses"]

            mgr.save(2, arrays=params, symbol=sym, block=True)
            assert repo.poll_checkpoint("flipm", ckdir) == 2
            # the probe ran after warmup, before the flip
            assert at_hook[1][0] == 2
            assert at_hook[1][1] == 1, \
                "version pointer flipped before the warm hooks ran"
            assert at_hook[1][2], "v2 ladder was not warmed pre-flip"
            misses_warm = server._cache.stats()["misses"]
            assert misses_warm > misses_v1  # the warmup itself compiled

            # post-flip traffic is all executor-cache hits on v2
            traces0 = mxc.LEDGER.trace_count(
                callsite="serving.executor_cache")
            for _ in range(6):
                out = server.predict("flipm", {"data": x}, wait_s=30.0)
            assert out[0].shape == (3,)
            assert repo.get("flipm").version == 2
            assert server._cache.stats()["misses"] == misses_warm, \
                "a post-flip request paid a compile"
            assert mxc.LEDGER.trace_count(
                callsite="serving.executor_cache") == traces0
    finally:
        server.shutdown()
        mxc.clear_ladders()
        mxc.clear_warmed()
        mxc.STATS.reset()


# -- trace lifecycle hardening ------------------------------------------------
def test_rejected_predict_finishes_trace_even_when_event_raises():
    """Regression (graftlint resource-leak-on-raise): predict_async's
    rejection handler recorded the shed event BEFORE finishing the
    span — an event() that raised (exporter lock poisoned, snapshot
    bug) leaked the span into the tracer's active set.  finish() now
    runs under finally."""
    from mxnet_tpu.telemetry import trace as mxtrace

    mxtrace.enable()
    mxtrace.reset_exemplars()
    orig_event = mxtrace.Trace.event

    def exploding_event(self, name, **fields):
        raise RuntimeError("exporter wedged")

    mxtrace.Trace.event = exploding_event
    try:
        with ModelServer(name="t-trace-reject") as server:
            with pytest.raises(RuntimeError, match="exporter wedged"):
                server.predict_async("no-such-model",
                                     {"data": np.zeros(4, np.float32)})
        docs = mxtrace.exemplars().get("serving", {})
        last = docs.get("last")
        assert last is not None and last["status"] == "rejected", \
            f"span leaked despite the failing event(): {docs}"
    finally:
        mxtrace.Trace.event = orig_event
        mxtrace.disable()
        mxtrace.reset_exemplars()
