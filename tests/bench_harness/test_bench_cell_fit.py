"""The two ``Module.fit`` cells dry-driven at thumbnail size on the CPU:
the default fused step untraced, the K=8 scanned window traced (the
profiler, the lanes and every reader run; a CPU trace has no device
plane, so the device metrics are left out of the line)."""
import jax
import pytest

import mxnet_tpu as mx

from bench_dry import check_line, dry_overlay, harness


@pytest.mark.parametrize("name,trace", [("resnet50-fit-step-bs64", 0),
                                        ("resnet50-fit-scan-bs128", 1)])
def test_fit_cell_dry_drive(name, trace, monkeypatch):
    C, run = harness()
    cell = C.Cell(name)
    for key, value in cell.job["env"].items():
        monkeypatch.setenv(key, value)
    result = run.run_cell(cell, seed=3, seconds=1.2, trace=trace,
                          devices=jax.devices()[:1], ctx=mx.cpu(),
                          dry=dry_overlay(cell))
    result = check_line(cell, result, trace)
    if trace:
        got = result["metrics"]
        # the lanes of telemetry.steps cover the fit loop's wall time
        assert 0 < got["fit_host_share_pct"]["value"] < 100
        assert 0 < got["fit_device_block_pct"]["value"] < 100
        assert got["compiles_in_window"]["value"] == 0
        assert got["setup_backend_compiles"]["value"] > 0
        # no accelerator: no device time under a device metric's name
        for device_metric in ("step_device_ms", "device_idle_pct",
                              "mfu_pct", "conv_device_share_pct"):
            assert device_metric not in got
        assert result["device"]["busy_s"] == 0
