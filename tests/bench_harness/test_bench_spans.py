"""The per-layer metrics that read the program's own spans and counters
(``benchmark/harness/spanread.py``), dry-driven traced at thumbnail size
on the CPU through each of the three drivers: every new metric
``BENCHMARK.json`` lists for the cell gets a number, and the counts read
the same in a second drive.  The numbers are a CPU's: they show that the
spans are where the readers look, never a speed."""
import json

import jax
import pytest

import mxnet_tpu as mx

from bench_dry import REPO, check_line, dry_overlay, harness

NEW = {"step_call_host_ms", "step_prepare_host_ms", "stage_host_ms_per_step",
       "stage_stack_share_pct", "h2d_mb_per_step", "step_host_args",
       "backward_walk_host_ms", "trainer_update_calls"}
COUNTS = ("h2d_mb_per_step", "step_host_args", "trainer_update_calls")


def _drive(name, monkeypatch, first_device, seed, seconds):
    C, run = harness()
    cell = C.Cell(name)
    for key, value in cell.job.get("env", {}).items():
        monkeypatch.setenv(key, value)
    devices = jax.devices()[first_device:first_device + cell.chips]
    result = run.run_cell(cell, seed=seed, seconds=seconds, trace=1,
                          devices=devices, ctx=mx.cpu(first_device),
                          dry=dry_overlay(cell))
    got = check_line(cell, result, 1)["metrics"]
    listed = {m["name"] for m in cell.metrics("per_layer")} & NEW
    assert listed and listed <= set(got), listed - set(got)
    return {name: got[name]["value"] for name in listed}


# seeds and seconds are those of the cells' own dry-drive tests.  The step
# cell runs on the second CPU device: the iterator's batches live on the
# first, so that staging has a copy to make, as it has on the chip; it is
# driven twice, and its two counts have to repeat
@pytest.mark.parametrize("name,first_device,seed,seconds,drives", [
    ("resnet50-fit-step-bs64", 1, 3, 1.2, 2),
    ("resnet50-fit-scan-bs128", 0, 3, 1.2, 1),
    ("mobilenetv2-gluon-bs32", 0, 0, 1.5, 1),
    ("resnet50-spmd-dp4-bs256", 0, 3, 1.0, 1),
])
def test_new_metrics_have_numbers_and_counts_repeat(
        name, first_device, seed, seconds, drives, monkeypatch):
    runs = [_drive(name, monkeypatch, first_device, seed, seconds)
            for _ in range(drives)]
    batch_bytes = 8 * (3 * 32 * 32 + 1) * 4   # images and labels, float32
    for got in runs:
        for metric, value in got.items():
            assert value >= 0, (metric, value)
        if "h2d_mb_per_step" in got:
            assert got["h2d_mb_per_step"] * 1e6 == batch_bytes
        if "stage_stack_share_pct" in got:
            assert 0 < got["stage_stack_share_pct"] < 100
    for metric in COUNTS:
        if metric in runs[0]:
            assert len({got[metric] for got in runs}) == 1, metric
    if name == "resnet50-fit-step-bs64":
        # one lr and one wd per parameter tensor, the poison scalar and
        # the key, which sits on the first device
        assert runs[0]["step_host_args"] > 100
    if name == "mobilenetv2-gluon-bs32":
        assert runs[0]["trainer_update_calls"] > 10


def test_new_entries_are_appended_with_their_workloads():
    with open(f"{REPO}/BENCHMARK.json") as f:
        per_layer = json.load(f)["per_layer"]
    assert {m["name"] for m in per_layer[-len(NEW):]} == NEW
    for m in per_layer[-len(NEW):]:
        assert m["workloads"] and m["moves"] == "images_per_s"
        assert m["source"] in ("program_span", "program_counter")


def test_readers_return_none_without_the_spans(monkeypatch):
    """A program from before the spans (the parent of this change) has no
    ``span_records``: every reader leaves its metric out, none raises."""
    C, _run = harness()
    from mxnet_tpu import telemetry
    monkeypatch.delattr(telemetry, "span_records")
    cell = C.Cell("resnet50-fit-scan-bs128")
    data = {"trace": {"steps": 16}, "cell": {"steps_per_sync": 8}}
    for name in NEW:
        assert cell.reader(name)(data) is None


def test_readers_take_the_last_profiled_steps_only():
    C, _run = harness()
    from mxnet_tpu import telemetry
    telemetry.enable()
    telemetry.reset_span_records()
    counter = telemetry.REGISTRY.get("mxnet_step_host_arg_leaves")
    for leaves in (9, 9, 5, 5, 5):          # five steps, three profiled
        telemetry.next_step()
        with telemetry.span("spmd/step/dispatch"):
            telemetry.count_in_span(counter, leaves, {"step": "spmd"})
    cell = C.Cell("resnet50-spmd-dp4-bs256")
    data = {"trace": {"steps": 3}, "cell": {"steps_per_sync": 1}}
    assert cell.reader("step_host_args")(data) == 5
    assert cell.reader("step_call_host_ms")(data) > 0
    assert cell.reader("stage_host_ms_per_step")(data) is None
