"""The program's spans on the profiler's clock (ISSUE 25): what a span's
in-memory record holds, that a ``StepTimer`` lane is a span, where the
step id advances, the bounded ring, the disabled path, that an enabled
span is an event of a ``jax.profiler`` trace nobody configured through
``MXNET_PROFILER_XPLANE_DIR``, and the helper that counts a step call's
host arguments.  All on the CPU: names, structure and counts, no speed."""
import glob
import os
import sys
import time
import tracemalloc

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.telemetry import spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def enabled():
    telemetry.enable()
    telemetry.reset_span_records()
    yield
    telemetry.disable()
    telemetry.reset_span_records()


def _named(name):
    return [r for r in telemetry.span_records() if r["name"] == name]


def test_record_holds_name_times_parent_thread_and_step(enabled):
    import threading
    telemetry.next_step()
    step = telemetry.current_step()
    with telemetry.span("t/clock/outer"):
        with telemetry.span("t/clock/inner"):
            time.sleep(0.002)
        time.sleep(0.001)
    (outer,), (inner,) = _named("t/clock/outer"), _named("t/clock/inner")
    assert outer["parent"] is None and inner["parent"] == "t/clock/outer"
    for rec in (outer, inner):
        assert rec["start_ns"] < rec["end_ns"]
        assert rec["step"] == step
        assert rec["thread"] == threading.get_ident()
    assert outer["start_ns"] <= inner["start_ns"]
    assert inner["end_ns"] <= outer["end_ns"]
    # self time: the duration less what the child covers
    inner_ns = inner["end_ns"] - inner["start_ns"]
    assert inner["self_ns"] == inner_ns >= 2_000_000
    assert outer["self_ns"] == \
        outer["end_ns"] - outer["start_ns"] - inner_ns
    assert outer["self_ns"] >= 1_000_000


def test_counts_land_in_the_innermost_open_span(enabled):
    counter = telemetry.REGISTRY.get("mxnet_io_stage_bytes_total")
    before = counter.value()
    with telemetry.span("t/clock/counting"):
        telemetry.record_io_stage_bytes(100)
        with telemetry.span("t/clock/counting/child"):
            telemetry.record_io_stage_bytes(7)
        telemetry.record_io_stage_bytes(20)
    telemetry.record_io_stage_bytes(3)      # no span open: registry only
    assert counter.value() - before == 130
    assert _named("t/clock/counting")[0]["counts"] == \
        {"mxnet_io_stage_bytes_total": 120}
    assert _named("t/clock/counting/child")[0]["counts"] == \
        {"mxnet_io_stage_bytes_total": 7}


def test_a_lane_is_a_span_and_the_breakdown_still_sums(enabled):
    telemetry.reset_step_stats()
    timer = telemetry.step_timer()
    try:
        timer.begin_step()
        for _ in range(3):
            with timer.lane("data_wait"):
                time.sleep(0.002)
            with timer.lane("step_dispatch"):
                with telemetry.span("t/clock/in_lane"):
                    time.sleep(0.003)
            timer.end_step()
    finally:
        timer.close()
    lanes = _named("fit/lane/step_dispatch")
    assert len(lanes) == 3 and len(_named("fit/lane/data_wait")) == 3
    assert all(r["parent"] == "fit/lane/step_dispatch"
               for r in _named("t/clock/in_lane"))
    bd = telemetry.step_breakdown()
    assert bd["steps"] == 3
    # the lane's total is the sum of its spans, to the nanosecond's float
    for lane in ("data_wait", "step_dispatch"):
        spans_s = sum(r["end_ns"] - r["start_ns"]
                      for r in _named("fit/lane/" + lane)) / 1e9
        assert bd["lanes"][lane] == pytest.approx(spans_s, rel=1e-9)
    total = sum(bd["lanes"].values()) + bd["other_s"]
    assert total == pytest.approx(bd["wall_s"], rel=1e-6)
    assert bd["lanes"]["step_dispatch"] >= 0.009
    # begin_step and every end_step closed a step: three ids, one a step
    assert len({r["step"] for r in lanes}) == 3
    for lane, inner in zip(lanes, _named("t/clock/in_lane")):
        assert lane["step"] == inner["step"]


def test_step_timer_lane_holds_no_clock_of_its_own():
    from mxnet_tpu.telemetry import steps
    assert not hasattr(steps, "_Lane")
    telemetry.enable()
    try:
        timer = telemetry.step_timer()
        lane = timer.lane("h2d_stage")
        timer.close()
    finally:
        telemetry.disable()
    assert type(lane) is spans._Span and lane.name == "fit/lane/h2d_stage"


def test_ring_is_bounded(enabled, monkeypatch):
    import collections
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=16))
    for i in range(40):
        with telemetry.span(f"t/clock/ring/{i}"):
            pass
    recs = telemetry.span_records()
    assert len(recs) == 16
    assert recs[0]["name"] == "t/clock/ring/24"      # oldest dropped first
    assert recs[-1]["name"] == "t/clock/ring/39"
    assert spans._ring.maxlen == 16 and spans.RING_SIZE >= 4096
    telemetry.reset_span_records()
    assert telemetry.span_records() == []


def test_disabled_span_allocates_nothing_and_records_nothing():
    telemetry.disable()
    telemetry.reset_span_records()
    assert telemetry.span("t/clock/off") is telemetry.span("t/clock/off2")
    for _ in range(100):                      # warm every code path
        with telemetry.span("t/clock/off"):
            pass
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            with telemetry.span("t/clock/off"):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    here = [s for s in after.compare_to(before, "filename")
            if s.traceback[0].filename.endswith("spans.py")]
    assert sum(s.size_diff for s in here) == 0
    assert telemetry.span_records() == []


def test_span_is_on_the_profilers_clock_without_any_env(enabled, tmp_path,
                                                        monkeypatch):
    """Whoever starts the profiler gets the spans: no
    ``MXNET_PROFILER_XPLANE_DIR``, no ``profiler.start``."""
    monkeypatch.delenv("MXNET_PROFILER_XPLANE_DIR", raising=False)
    sys.path.insert(0, os.path.join(REPO, "benchmark", "trace"))
    try:
        import reduce as trace_reduce
    finally:
        sys.path.pop(0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with telemetry.span("t/clock/traced"):
            with telemetry.span("t/clock/traced/child"):
                time.sleep(0.005)
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    trace = trace_reduce.load_xplane(path)
    for name in ("t/clock/traced", "t/clock/traced/child"):
        events = [(plane["name"], dur) for plane in trace["planes"]
                  for line in plane["lines"]
                  for ev_name, _start, dur in line["events"]
                  if ev_name == name]
        assert len(events) == 1, (name, events)
        plane, dur = events[0]
        assert plane.startswith("/host:")
        (rec,) = _named(name)
        want = rec["end_ns"] - rec["start_ns"]
        assert abs(dur - want) <= max(0.2 * want, 50_000), (dur, want)


def test_host_arg_stats_counts_what_the_call_has_to_copy():
    step_dev, other = jax.devices()[1], jax.devices()[0]
    on_device = jax.device_put(np.ones((4, 4), np.float32), step_dev)
    elsewhere = jax.device_put(np.ones((2, 3), np.float32), other)
    args = (on_device, (0.1, np.float32(1.0)), [elsewhere],
            {"w": np.zeros((5,), np.float32), "d": on_device})
    leaves, nbytes = telemetry.host_arg_stats(args, {step_dev})
    # the Python float, the numpy scalar, the array on another device and
    # the numpy array; not the two references to the array on the device
    assert leaves == 4
    assert nbytes == 8 + 4 + 24 + 20
    assert telemetry.host_arg_stats((on_device,), {step_dev}) == (0, 0)
    # a mesh: every device of the array has to be one of the step's
    assert telemetry.host_arg_stats(
        (on_device, elsewhere), {step_dev, other}) == (0, 0)


def _tiny_net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net.hybridize()
    return net


def test_gluon_step_spans_share_one_step_id(enabled):
    net = _tiny_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.nd.array(np.random.randn(4, 5).astype(np.float32))
    y = mx.nd.array(np.array([0, 1, 2, 0], np.float32))
    calls = telemetry.REGISTRY.get("mxnet_trainer_update_calls_total")
    before = calls.value()
    for _ in range(3):
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(4)
    want = ("gluon/cached_op/prepare", "gluon/cached_op/dispatch",
            "autograd/backward/walk", "autograd/backward/dispatch",
            "gluon/trainer/allreduce", "gluon/trainer/update")
    steps = [{r["step"] for r in _named(name)} for name in want]
    assert all(len(ids) == 3 for ids in steps), steps
    assert all(ids == steps[0] for ids in steps)
    assert all(r["parent"] == "autograd/backward/walk"
               for r in _named("autograd/backward/dispatch"))
    # 2 Dense layers x (weight, bias) = four tensors in one program a
    # step: in the registry and in the update span's record
    assert calls.value() - before == 3
    assert [r["counts"] for r in _named("gluon/trainer/update")] == \
        [{"mxnet_trainer_update_calls_total": 1}] * 3


def test_spmd_step_spans_and_counters(enabled):
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import TrainStep
    net = _tiny_net()
    x = np.random.randn(8, 5).astype(np.float32)
    y = np.array([0, 1, 2, 0, 1, 2, 0, 1], np.float32)
    net(mx.nd.array(x))
    mesh = make_mesh(devices=jax.devices()[:4], dp=4)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, mesh,
                     example_batch=(mx.nd.array(x), mx.nd.array(y)))
    telemetry.reset_span_records()
    for _ in range(2):
        float(step(x, y))
    names = ("spmd/step/shard_batch", "spmd/step/prepare",
             "spmd/step/dispatch")
    ids = [{r["step"] for r in _named(name)} for name in names]
    assert all(len(i) == 2 for i in ids) and ids[0] == ids[1] == ids[2]
    # one span over the whole call, so that a trace names the call even
    # where the caller annotates nothing; the three parts nest under it
    assert len(_named("spmd/step")) == 2
    assert all(r["parent"] == "spmd/step" for n in names for r in _named(n))
    assert [r["counts"] for r in _named("spmd/step/shard_batch")] == \
        [{"mxnet_io_stage_bytes_total": x.nbytes + y.nbytes,
          "mxnet_spmd_batch_arrays_total": 2}] * 2
    for rec in _named("spmd/step/dispatch"):
        assert rec["counts"]["mxnet_step_host_arg_leaves"] == 0
        assert rec["counts"]["mxnet_step_host_arg_bytes"] == 0


def test_fit_spans_split_the_step_and_count_its_host_arguments(enabled):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(h, name="softmax")
    rng = np.random.RandomState(0)
    x = rng.randn(96, 20).astype(np.float32)
    y = rng.randint(0, 10, 96).astype(np.float32)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=32), num_epoch=2,
            optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    steps = 6
    for name in ("fit/step/prepare", "fit/step/fused_dispatch",
                 "fit/step/writeback"):
        recs = _named(name)
        assert len(recs) == steps, name
        assert len({r["step"] for r in recs}) == steps
        assert all(r["parent"] == "fit/lane/step_dispatch" for r in recs)
    # the lr vector, the wd vector and the poison scalar, however many
    # parameter tensors (four here)
    assert {r["counts"]["mxnet_step_host_arg_leaves"]
            for r in _named("fit/step/fused_dispatch")} == {3}
    assert all(r["parent"] == "fit/lane/h2d_stage"
               for r in _named("io/stage_batch"))
