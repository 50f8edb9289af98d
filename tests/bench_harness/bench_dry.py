"""Shared by the benchmark's CPU tests: the harness loaded from its
files, the thumbnail sizes of a dry drive, and the contract's keys.

A dry drive calls ``run.run_cell`` — the function ``main`` calls on the
chip — with a ``dry`` overlay of sizes on ``mx.cpu()``.  ``main`` itself
has no such mode.  What a dry drive prints under a metric's name is a CPU
number: it shows that the arithmetic runs and the line parses, never a
speed.
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HARNESS = os.path.join(REPO, "benchmark", "harness")

DRY_CONFIG = {
    "resnet50_v1": {"units": [1, 1, 1, 1], "filters": [16, 32, 64, 128],
                    "stem_filters": 8, "num_classes": 10,
                    "image": [3, 32, 32], "thumbnail": True},
    "mobilenetv2_1.0": {"width_multiplier": 0.25, "num_classes": 10,
                        "image": [3, 32, 32]},
}

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def harness():
    """(benchcore, run) imported from ``benchmark/harness``."""
    if HARNESS not in sys.path:
        sys.path.insert(0, HARNESS)
    import benchcore
    import run
    return benchcore, run


def dry_overlay(cell, batch=8):
    return {"config": DRY_CONFIG[cell.row["config"]],
            # one batch repeated: a thumbnail learns it within a second
            "job": {"batch": batch, "trace_seconds": 0.6, "pool_batches": 1}}


def check_line(cell, result, trace):
    """The last line of a run against the contract: exactly the keys the
    driver reads, metrics of the right group with value and unit."""
    result = json.loads(json.dumps(result))        # it must serialise
    keys = set(result)
    assert keys - {"breakdown"} == RESULT_KEYS, keys
    assert ("breakdown" in keys) == bool(trace)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    device = result["device"]
    want = DEVICE_KEYS | ({"busy_s", "window_s"} if trace else set())
    assert set(device) == want, device
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m["unit"] for m in cell.metrics(group)}
    assert result["metrics"], "no metric at all"
    for name, cellv in result["metrics"].items():
        assert set(cellv) == {"value", "unit"}
        assert cellv["unit"] == listed[name]
        assert isinstance(cellv["value"], (int, float))
    if not trace:
        assert set(result["metrics"]) == set(listed)
        assert all(v["value"] > 0 for v in result["metrics"].values())
    else:
        for part in ("device_ops", "idle_gaps"):
            assert len(result["breakdown"][part]) <= 10
    return result
