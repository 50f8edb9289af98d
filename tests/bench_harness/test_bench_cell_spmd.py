"""The four-chip cell on four virtual CPU devices, in a child process
that sets ``XLA_FLAGS`` itself (the test process has eight)."""
import json
import os
import subprocess
import sys

from bench_dry import HARNESS, REPO, check_line, harness

_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{repo!r}, {harness!r}, {here!r}]
from bench_dry import dry_overlay, harness
C, run = harness()
import jax, mxnet_tpu as mx
assert len(jax.devices()) == 4
cell = C.Cell("resnet50-spmd-dp4-bs256")
result = run.run_cell(cell, seed=3, seconds=1.0, trace=0,
                      devices=jax.devices(), ctx=mx.cpu(),
                      dry=dry_overlay(cell))
print(json.dumps(result))
"""


def test_spmd_dp4_cell_dry_drive():
    here = os.path.dirname(os.path.abspath(__file__))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c",
         _CHILD.format(repo=REPO, harness=HARNESS, here=here)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    C, _run = harness()
    cell = C.Cell("resnet50-spmd-dp4-bs256")
    result = check_line(cell, json.loads(proc.stdout.splitlines()[-1]), 0)
    assert result["device"]["count"] == 4
    # step_engaged held: four distinct devices, a quarter of the batch each
    assert "step_engaged=ok" in proc.stdout
