"""ISSUE 12 observability plane: end-to-end tracing, the crash flight
recorder, cross-rank fleet aggregation, and the satellite fixes.

Covers: disabled-path overhead of the trace/flight hooks (< 1 us, the
chaos-failpoint bar), stage decomposition + the head/tail exemplar
store, a served request's stage spans covering >= 95% of its measured
e2e latency, the ONE-trace contract under a spill to a sibling replica,
the scanned-fit window trace, flight ring mechanics + atomic dumps +
the shared MXNET_WATCHDOG_KEEP retention, the first-anomaly reader,
the /snapshot.json numpy-coercion regression, and the kvstore-backed
fleet merge (lost rank tagged, never dropped) + /fleet.json endpoint.
"""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import fleet, flight, trace
from mxnet_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture
def traced():
    trace.enable()
    trace.reset_exemplars()
    yield
    trace.disable()
    trace.reset_exemplars()


@pytest.fixture
def ring():
    flight.enable()
    flight.clear()
    yield
    flight.configure()
    flight.clear()


def _mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _linear_server(**kw):
    from mxnet_tpu.serving import ModelServer
    d = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(d, num_hidden=4, name="fc")
    rng = np.random.RandomState(0)
    params = {"fc_weight": mx.nd.array(rng.randn(4, 8).astype(np.float32)),
              "fc_bias": mx.nd.zeros((4,))}
    srv = ModelServer(**kw)
    srv.load("m", symbol=net, params=params)
    return srv


# -- disabled-path overhead ---------------------------------------------------
def test_trace_and_flight_disabled_overhead_under_1us():
    trace.disable()
    flight.disable()
    n = 20000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            tr = trace.start("bench")
            with tr.stage("noop"):
                pass
            flight.record("bench", "noop", value=1)
        best = min(best, (time.perf_counter() - t0) / (3 * n))
    flight.configure()
    assert best < 1e-6, f"disabled trace/flight hook costs {best * 1e9:.0f}ns"


def test_disabled_trace_records_nothing(ring):
    trace.disable()
    tr = trace.start("serving", "m")
    assert tr is trace.NULL_TRACE
    with tr.stage("submit"):
        pass
    tr.finish()
    assert trace.exemplars() == {}


# -- stage decomposition + exemplars -----------------------------------------
def test_trace_stage_decomposition(traced):
    tr = trace.start("serving", "m")
    # explicit intervals over a start moved one second back: coverage is
    # 1 / (1 + the few microseconds until finish()), arithmetic and not
    # how six test workers happen to be scheduled around two sleeps
    tr.t0 -= 1.0
    tr.add_stage("submit", tr.t0, tr.t0 + 0.4)
    tr.add_stage("queue_wait", tr.t0 + 0.4, tr.t0 + 1.0)
    tr.event("route", replica=0, hop=0)
    tr.finish()
    doc = trace.exemplars()["serving"]["last"]
    assert doc["status"] == "ok"
    assert [s["stage"] for s in doc["stages"]] == ["submit", "queue_wait"]
    assert [s["dur_ms"] for s in doc["stages"]] == [400.0, 600.0]
    assert doc["stage_total_ms"] == 1000.0
    assert 0.9 <= doc["coverage"] <= 1.0
    assert doc["events"][0]["event"] == "route"
    # stage durations fanned out to the registry histogram
    hist = telemetry.REGISTRY.get("mxnet_trace_stage_seconds")
    assert hist.stats(labels={"kind": "serving", "stage": "submit"}
                      )["count"] >= 1


def test_exemplar_head_tail_sampling(traced, monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "head=2,tail=2")
    trace.reset_exemplars()  # re-reads the policy on next add
    durations = [0.001, 0.002, 0.003, 0.030, 0.004, 0.020]
    for i, dur in enumerate(durations):
        tr = trace.start("k", f"t{i}")
        with tr.stage("s"):
            time.sleep(dur)
        tr.finish()
    ex = trace.exemplars()["k"]
    assert ex["count"] == 6
    assert [d["name"] for d in ex["head"]] == ["t0", "t1"]
    # the two slowest of the post-head traces, slowest first
    assert [d["name"] for d in ex["slowest"]] == ["t3", "t5"]


# -- serving end-to-end -------------------------------------------------------
def test_served_request_stages_cover_95pct_of_e2e(traced):
    srv = _linear_server(max_latency_ms=2.0, name="t-trace")
    try:
        x = np.random.randn(8).astype(np.float32)
        for _ in range(3):
            srv.predict("m", {"data": x})
        ex = trace.exemplars()["serving"]
        assert ex["count"] == 3
        last = ex["last"]
        assert last["status"] == "ok"
        stages = {s["stage"] for s in last["stages"]}
        assert {"submit", "queue_wait", "stage", "staged_wait",
                "dispatch", "resolve"} <= stages
        assert last["coverage"] >= 0.95, last
    finally:
        srv.shutdown()


def test_spilled_request_is_one_trace_resolved_on_sibling(traced):
    from mxnet_tpu.chaos import failpoints as chaos
    srv = _linear_server(max_latency_ms=2.0, num_replicas=2,
                         name="t-spill")
    try:
        x = np.random.randn(8).astype(np.float32)
        # the chosen replica takes an injected dispatch fault on the
        # FIRST submit: the router spills to the sibling, which resolves
        # — the journey must read as ONE trace with its hop recorded
        chaos.arm("serving/router/dispatch", "raise", hits=1, count=1)
        try:
            out = srv.predict("m", {"data": x})
        finally:
            chaos.reset()
        assert out is not None
        ex = trace.exemplars()["serving"]
        assert ex["count"] == 1, "a spilled request must stay ONE trace"
        doc = ex["last"]
        assert doc["status"] == "ok"
        events = [e["event"] for e in doc["events"]]
        assert "spill" in events, events
        assert doc["coverage"] >= 0.95, doc
    finally:
        srv.shutdown()


def test_shed_trace_finishes_typed(traced):
    from mxnet_tpu.serving.batcher import (DynamicBatcher,
                                           ServingOverloadError)
    gate = threading.Event()

    def runner(feed, n):
        gate.wait(10)
        return [feed["x"]]

    b = DynamicBatcher(runner, max_batch_size=1, max_latency_ms=1.0,
                       num_workers=1, max_queue_depth=1, shed_watermark=1,
                       name="t-shed-trace")
    try:
        tr1 = trace.start("serving", "m")
        b.submit({"x": np.float32(0)}, trace=tr1)  # occupies the worker
        time.sleep(0.1)
        b.submit({"x": np.float32(1)})             # queued/staged: depth 1
        tr2 = trace.start("serving", "m")
        with pytest.raises(ServingOverloadError):
            b.submit({"x": np.float32(2)}, trace=tr2)
        tr2.finish(status="shed")  # what the router/front-end does
        assert any(e[1] == "shed" for e in tr2.events)
    finally:
        gate.set()
        b.close()


# -- train window trace -------------------------------------------------------
def test_scanned_fit_window_trace(traced, monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_SCAN_STEPS", "2")
    rng = np.random.RandomState(0)
    x = rng.randn(128, 20).astype(np.float32)
    y = rng.randint(0, 10, 128).astype(np.float32)
    it = mx.io.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=32,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05})
    ex = trace.exemplars().get("train")
    assert ex is not None, "no train-window traces recorded"
    assert ex["count"] == 2  # 4 batches / K=2 windows
    doc = ex["last"]
    stages = {s["stage"] for s in doc["stages"]}
    assert {"collect", "stage", "dispatch", "boundary_flush"} <= stages
    assert doc["status"] == "ok"


# -- flight recorder ----------------------------------------------------------
def test_flight_ring_bounded_and_ordered(ring):
    flight.configure(enabled=True, ring=16)
    for i in range(40):
        flight.record("t", f"e{i}", idx=i)
    evs = flight.events()
    assert len(evs) == 16
    assert evs[0]["event"] == "e24" and evs[-1]["event"] == "e39"
    assert evs[-1]["fields"]["idx"] == 39
    assert evs[0]["seq"] < evs[-1]["seq"]


def test_flight_disabled_is_noop(ring):
    flight.disable()
    flight.record("t", "never")
    assert flight.events() == []


def test_flight_dump_atomic_and_json(ring, tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    flight.record("serving", "shed", severity="warn", depth=3)
    flight.record("chaos", "inject", severity="error",
                  site="multihost/peer_loss", action="kill")
    path = flight.dump(reason="test")
    assert os.path.basename(path).startswith("mxnet-flight-")
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "test"
    assert [e["event"] for e in doc["events"]] == ["shed", "inject"]
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def test_dump_retention_keep_newest(ring, tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_WATCHDOG_KEEP", "3")
    flight.record("t", "e")
    paths = [flight.dump(reason=f"d{i}") for i in range(6)]
    left = sorted(p for p in os.listdir(tmp_path)
                  if p.startswith("mxnet-flight-"))
    assert len(left) == 3
    assert os.path.basename(paths[-1]) in left  # newest survived
    # the same retention applies to watchdog stall dumps
    for i in range(5):
        p = tmp_path / f"mxnet-watchdog-1-{i}.txt"
        p.write_text("dump")
        os.utime(p, (i + 1, i + 1))
    flight.prune(str(tmp_path), "mxnet-watchdog-")
    wd = sorted(p for p in os.listdir(tmp_path)
                if p.startswith("mxnet-watchdog-"))
    assert wd == ["mxnet-watchdog-1-2.txt", "mxnet-watchdog-1-3.txt",
                  "mxnet-watchdog-1-4.txt"]


def test_first_anomaly_orders_by_wall_time(ring):
    rings = [
        {"events": [
            {"t": 10.0, "severity": "info", "event": "start"},
            {"t": 30.0, "severity": "error", "event": "peer_lost"}]},
        {"events": [
            {"t": 20.0, "severity": "error", "event": "inject",
             "fields": {"site": "multihost/peer_loss"}}]},
    ]
    anomaly = flight.first_anomaly(rings)
    assert anomaly["event"] == "inject"
    assert anomaly["fields"]["site"] == "multihost/peer_loss"
    assert flight.first_anomaly([{"events": []}]) is None


# -- /snapshot.json numpy coercion (satellite regression) ---------------------
def test_snapshot_json_roundtrips_numpy_families():
    reg = MetricsRegistry()
    reg.counter("np_counter", "d").inc(np.int64(3),
                                       labels={"k": "a"})
    reg.gauge("np_gauge", "d").set(np.float32(1.5))
    reg.histogram("np_hist", "d").observe(np.float64(0.25))
    reg.register_collector(
        "np_source",
        lambda: {"value": np.float32(2.5), "count": np.int64(7),
                 "nested": {"arr": np.arange(3), "ok": np.bool_(True)}})
    snap = reg.snapshot()
    # NO default= escape hatch: every leaf must already be native
    text = json.dumps(snap)
    back = json.loads(text)
    assert back["np_source"]["value"] == 2.5
    assert back["np_source"]["nested"]["arr"] == [0, 1, 2]
    # every registered family individually round-trips
    for family, doc in snap["metrics"].items():
        json.dumps({family: doc})
    assert back["metrics"]["np_counter"]["values"][0]["value"] == 3
    # the process-wide registry (with every subsystem collector) too
    json.dumps(telemetry.snapshot())


def test_sample_families_flatten(ring):
    reg = MetricsRegistry()
    reg.counter("c_total", "d").inc(2, labels={"op": "x"})
    reg.histogram("h_seconds", "d").observe(0.1)
    fams = reg.sample_families()
    assert fams["c_total"]["type"] == "counter"
    assert fams["c_total"]["values"][0] == {"labels": {"op": "x"},
                                            "value": 2}
    assert "h_seconds_bucket" in fams and "h_seconds_count" in fams
    json.dumps(fams)


# -- fleet aggregation --------------------------------------------------------
def _start_server(num_workers=2, peer_timeout_s=0.4):
    from mxnet_tpu.kvstore_server import KVServer
    server = KVServer(port=0, num_workers=num_workers,
                      peer_timeout_s=peer_timeout_s)
    t = threading.Thread(target=server.run, daemon=True)
    t.start()
    assert server.started.wait(10)
    return server


def test_fleet_merge_tags_lost_rank_with_last_snapshot():
    from mxnet_tpu.kvstore_server import KVClient
    server = _start_server()
    try:
        c0 = KVClient("127.0.0.1", server.bound_port, rank=0,
                      num_workers=2, timeout=10, heartbeat_interval=0)
        c1 = KVClient("127.0.0.1", server.bound_port, rank=1,
                      num_workers=2, timeout=10, heartbeat_interval=0)
        c0.heartbeat()
        c1.heartbeat()
        c0.push_telemetry(fleet.local_payload())
        c1.push_telemetry({"time": time.time(),
                           "families": {"mxnet_fake_total": {
                               "type": "counter",
                               "values": [{"labels": {}, "value": 5}]}}})
        # rank 1 goes silent past the peer timeout -> marked lost;
        # rank 0 keeps heartbeating throughout (alive is sticky-false:
        # once in the dead set a rank stays lost for the generation)
        c1.close()
        deadline = time.time() + 10
        while 1 not in server.dead_ranks() and time.time() < deadline:
            c0.heartbeat()
            time.sleep(0.05)
        c0.heartbeat()  # rank 0 stays alive
        c0.push_telemetry(fleet.local_payload())  # ...and fresh
        snap = fleet.merge_server(server)
        assert snap["ranks"]["0"]["state"] == "alive"
        assert snap["ranks"]["1"]["state"] == "lost"
        # the lost rank keeps its LAST pushed families, tagged — never
        # silently dropped
        assert "mxnet_fake_total" in snap["ranks"]["1"]["families"]
        # the same view is one bounded RPC away for any client
        rpc_snap = c0.fleet_state()
        assert rpc_snap["ranks"]["1"]["state"] == "lost"
        c0.close()
    finally:
        server._stop.set()


def test_fleet_json_endpoint_and_prometheus_rank_labels():
    server = _start_server(num_workers=1)
    try:
        from mxnet_tpu.kvstore_server import KVClient
        c0 = KVClient("127.0.0.1", server.bound_port, rank=0,
                      num_workers=1, timeout=10, heartbeat_interval=0)
        c0.heartbeat()
        c0.push_telemetry(fleet.local_payload())
        fleet.set_provider(lambda: fleet.merge_server(server))
        try:
            port = telemetry.start_exporter(0)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/fleet.json",
                    timeout=10) as r:
                doc = json.loads(r.read().decode("utf-8"))
            assert doc["ranks"]["0"]["state"] == "alive"
            assert doc["ranks"]["0"]["families"]
            # the Prometheus dump re-emits rank-labelled families
            text = telemetry.prometheus_dump()
            assert 'mxnet_fleet_rank_state{rank="0",state="alive"} 1' \
                in text
            assert 'rank="0"' in text
        finally:
            telemetry.stop_exporter()
            fleet.set_provider(None)
        c0.close()
    finally:
        server._stop.set()


def test_fleet_json_without_provider_is_local_view():
    fleet.set_provider(None)
    doc = fleet.fleet_json()
    rank = os.environ.get("MXNET_MULTIHOST_PROC_ID", "0")
    assert doc["ranks"][rank]["state"] == "alive"
    assert doc["ranks"][rank]["families"]
    json.dumps(doc)
