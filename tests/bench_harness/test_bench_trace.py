"""The reduction from a trace to numbers (``benchmark/trace/reduce.py``):
on a trace small enough to work out by hand, and on the fixtures recorded
on the chip (``record_fixture.py``), whose numbers are written beside
them and are worked out here a second way, by rasterising every event
onto a nanosecond grid instead of merging intervals."""
import glob
import json
import os

import numpy as np
import pytest

from bench_dry import REPO, harness

TRACE_DIR = os.path.join(REPO, "benchmark", "trace")


def _reduce():
    C, _run = harness()
    return C.load_py(os.path.join(TRACE_DIR, "reduce.py"), "bench_reduce")


def _trace(device_ops, host, extra_planes=()):
    """ops: {device: [(name, start, dur)]}, host: [(name, start, dur)]."""
    planes = [{"name": f"/device:TPU:{d}", "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in ops]},
        {"name": "XLA Modules", "events": [
            ["jit_step(1)", s, 1] for n, s, _d in ops
            if n.startswith("%fusion.1 ")]}]}
        for d, ops in device_ops.items()]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [list(e) for e in host]}]})
    return {"planes": planes + list(extra_planes)}


def test_by_hand():
    """Two steps of 1000 us.  Device 0: a 300 us fusion, an all-reduce of
    400 us that a 100 us fusion overlaps, so 300 us of it are exposed;
    idle 200 us before the first op and 100 us after the last.  Device 1
    is busy 500 us of each step."""
    us = 1000
    ops0, ops1, host = [], [], [("bench/sync", 0, 1)]
    for step in range(2):
        t = step * 1000 * us
        ops0 += [("%fusion.1 = f32[8,4]{1,0} fusion(f32[8,4] %p)",
                  t + 200 * us, 300 * us),
                 ("%all-reduce.2 = f32[4]{0} all-reduce(f32[4] %g)",
                  t + 500 * us, 400 * us),
                 ("%fusion.3 = f32[4]{0} fusion(f32[4] %q)",
                  t + 600 * us, 100 * us)]
        ops1 += [("%fusion.1 = f32[8,4]{1,0} fusion(f32[8,4] %p)",
                  t + 100 * us, 500 * us)]
        host += [("bench/step_call", t + 10 * us, 150 * us),
                 ("PjitFunction(step)", t + 12 * us, 146 * us),
                 ("bench/loss_read", t + 880 * us, 115 * us),
                 ("bench/sync", t + 1000 * us, 1)]
    red = _reduce()
    out = red.reduce(_trace({0: ops0, 1: ops1}, host), n_devices=2)
    assert out["syncs"] == 3 and out["steps"] == 2 and out["devices"] == 2
    assert out["window_s"] == pytest.approx(2000e-6)
    # device 0 busy 700 us a step, device 1 500 us: the mean
    assert out["busy_s"] == pytest.approx((1400e-6 + 1000e-6) / 2)
    ops = dict(out["device_ops"])
    assert ops["%fusion.1 fusion f32[8,4]"] == pytest.approx(
        (600e-6 + 1000e-6) / 2)
    assert ops["%all-reduce.2 all-reduce f32[4]"] == pytest.approx(400e-6)
    assert out["collective_s"] == pytest.approx(800e-6)
    assert out["collective_exposed_s"] == pytest.approx(600e-6)
    assert out["program_runs"] == 2          # device 0's module events
    # idle [0, 200) and [900, 1200) us: bench/step_call covers 150 us of
    # each and PjitFunction(step) inside it 146 us, nearly as much, so
    # the innermost names the gap; idle [1900, 2000): the loss read
    gaps = dict(out["idle_gaps"])
    assert gaps["PjitFunction(step)"] == pytest.approx(500e-6)
    assert gaps["bench/loss_read"] == pytest.approx(100e-6)
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - 1400e-6)
    # a while op around a step's ops adds nothing to busy time and is
    # not listed: its body is
    loop = [("%while.5 = (s32[], f32[8,4]) while((s32[], f32[8,4]) %t)",
             150 * us, 800 * us)]
    wrapped = red.reduce(_trace({0: ops0 + loop}, host), n_devices=1)
    assert wrapped["busy_s"] == pytest.approx((800 + 700) * 1e-6)
    assert not any("while" in name for name, _s in wrapped["device_ops"])
    # one chip of the two: only device 0 counts
    one = red.reduce(_trace({0: ops0, 1: ops1}, host), n_devices=1)
    assert one["busy_s"] == pytest.approx(1400e-6)
    # several steps to a sync
    assert red.reduce(_trace({0: ops0}, host), 1, steps_per_sync=8)[
        "steps"] == 16


def test_a_trace_without_a_device_or_syncs_gives_nothing():
    red = _reduce()
    out = red.reduce(_trace({}, [("bench/sync", 0, 1),
                                 ("bench/sync", 5000, 1)]), n_devices=1)
    assert out["busy_s"] == 0 and out["devices"] == 0
    assert out["window_s"] == pytest.approx(5e-6)
    assert red.reduce(_trace({0: []}, []), 1)["steps"] == 0


def test_interval_arithmetic():
    red = _reduce()
    assert red.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == [
        [1, 4], [5, 8]]
    assert red.subtract([[0, 10], [20, 30]], [[2, 3], [8, 22], [29, 40]]) \
        == [[0, 2], [3, 8], [22, 29]]
    assert red.gaps([[2, 3], [5, 9]], 0, 10) == [[0, 2], [3, 5], [9, 10]]


def _raster(trace, n_devices):
    """The same quantities on a nanosecond grid."""
    red = _reduce()
    host = [e for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for e in line["events"]]
    syncs = sorted(s for n, s, _d in host if n == red.SYNC)
    lo, hi = syncs[0], syncs[-1]
    busy, coll_s, exposed_s, by_name = [], 0, 0, {}
    planes = sorted((p for p in trace["planes"]
                     if red.DEVICE_PLANE.match(p["name"])),
                    key=lambda p: p["name"])[:n_devices]
    for i, plane in enumerate(planes):
        ops = next(line["events"] for line in plane["lines"]
                   if line["name"] == red.OPS_LINE)
        grid = np.zeros(hi - lo, bool)
        coll = np.zeros(hi - lo, bool)
        rest = np.zeros(hi - lo, bool)
        for name, s, d in ops:
            a, b = max(s, lo) - lo, min(s + d, hi) - lo
            if b <= a:
                continue
            grid[a:b] = True
            (coll if red.COLLECTIVE.match(name) else rest)[a:b] = True
            if not red.CONTAINER.match(name):
                by_name[red.label(name)] = by_name.get(
                    red.label(name), 0) + (b - a)
        busy.append(int(grid.sum()))
        if i == 0:
            coll_s, exposed_s = int(coll.sum()), int((coll & ~rest).sum())
    n = len(planes)
    return {"steps": len(syncs) - 1, "window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / n / 1e9, "collective_s": coll_s / 1e9,
            "collective_exposed_s": exposed_s / 1e9,
            "top_op": max(by_name.items(), key=lambda kv: kv[1])[0],
            "top_op_s": max(by_name.values()) / n / 1e9}


FIXTURES = sorted(glob.glob(os.path.join(TRACE_DIR, "fixture_*.xplane.pb")))


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_recorded_fixture(path):
    red = _reduce()
    with open(os.path.join(TRACE_DIR, "fixture_expected.json")) as f:
        want = json.load(f)[os.path.basename(path)]
    trace = red.load_xplane(path)
    out = red.reduce(trace, n_devices=want["devices"])
    again = _raster(trace, want["devices"])
    for key in ("steps", "window_s", "busy_s", "collective_s",
                "collective_exposed_s"):
        assert out[key] == pytest.approx(want[key], rel=1e-9), key
        assert out[key] == pytest.approx(again[key], rel=1e-9), key
    assert out["device_ops"][0][0] == want["top_op"] == again["top_op"]
    assert out["device_ops"][0][1] == pytest.approx(want["top_op_s"])
    assert out["device_ops"][0][1] == pytest.approx(again["top_op_s"])
    assert out["program_runs"] == want["program_runs"]
    # the annotated sleep between steps is what the device waited for
    assert out["idle_gaps"][0][0] == want["top_gap"]
    assert out["idle_gaps"][0][1] == pytest.approx(want["top_gap_s"])
    assert sum(s for _n, s in out["idle_gaps"]) == pytest.approx(
        want["idle_s_device0"])
    if want["devices"] > 1:
        assert out["collective_s"] > 0


def test_fixtures_are_there_and_small():
    assert FIXTURES, "no recorded fixture beside reduce.py"
    for path in FIXTURES:
        assert os.path.getsize(path) < 300_000
