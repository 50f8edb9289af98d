"""The Gluon cell dry-driven at thumbnail size on the CPU."""
import jax

import mxnet_tpu as mx

from bench_dry import check_line, dry_overlay, harness


def test_gluon_cell_dry_drive():
    C, run = harness()
    cell = C.Cell("mobilenetv2-gluon-bs32")
    result = run.run_cell(cell, seed=0, seconds=1.5, trace=1,
                          devices=jax.devices()[:1], ctx=mx.cpu(),
                          dry=dry_overlay(cell))
    got = check_line(cell, result, 1)["metrics"]
    assert got["gluon_update_host_ms"]["value"] > 0
    assert got["compiles_in_window"]["value"] == 0
