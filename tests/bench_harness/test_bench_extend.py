"""The harness is driven by data: a later PR adds a cell, a
configuration and a per-layer metric as new files and appended entries,
and edits no file that is there."""
import hashlib
import json
import os
import shutil

import jax

import mxnet_tpu as mx

from bench_dry import DRY_CONFIG, REPO, check_line, harness

NEW_METRIC = '''"""train step: steps the traced window held."""


def read(data):
    return data["trace"].get("syncs")
'''


def _digests(root):
    out = {}
    for d, _sub, names in os.walk(root):
        if "__pycache__" in d:
            continue
        for n in names:
            path = os.path.join(d, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_new_cell_config_and_metric_are_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)

    bdir = os.path.join(root, "benchmark")
    # a configuration: its sizes and, beside it, its build and reference
    with open(os.path.join(bdir, "configs", "resnet50_v1.json")) as f:
        cfg = dict(json.load(f), name="resnet14_thumb",
                   **DRY_CONFIG["resnet50_v1"])
    with open(os.path.join(bdir, "configs", "resnet14_thumb.json"),
              "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(bdir, "configs", "resnet50_v1.py"),
                os.path.join(bdir, "configs", "resnet14_thumb.py"))
    # a traffic mix: parameters for a driver that is there
    with open(os.path.join(bdir, "jobs", "fit-step-bs64.json")) as f:
        job = dict(json.load(f), batch=8, trace_seconds=0.6)
    with open(os.path.join(bdir, "jobs", "fit-step-bs8.json"), "w") as f:
        json.dump(job, f)
    # a per-layer metric: a reader of its own
    with open(os.path.join(bdir, "layer_metrics", "syncs_traced.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "resnet14_thumb", "source": cfg["source"],
        "file": "benchmark/configs/resnet14_thumb.json",
        "reduced": ["units"], "why": "a test's configuration"})
    bench["workloads"].append({
        "name": "resnet14-fit-step-bs8", "config": "resnet14_thumb",
        "traffic": "fit-step-bs8", "chips": 1, "why": "a test's cell"})
    bench["per_layer"].append({
        "name": "syncs_traced", "unit": "count", "better": "higher",
        "source": "device_trace", "layer": "train step",
        "moves": "images_per_s", "workloads": ["resnet14-fit-step-bs8"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    C, run = harness()
    cell = C.Cell("resnet14-fit-step-bs8", root=root)
    # no overlay of sizes: the new files carry them
    result = run.run_cell(cell, seed=5, seconds=1.2, trace=1,
                          devices=jax.devices()[:1], ctx=mx.cpu(), dry={})
    got = check_line(cell, result, 1)["metrics"]
    assert got["syncs_traced"]["value"] >= 2

    after = _digests(root)
    added = set(after) - set(before)
    assert added == {"benchmark/configs/resnet14_thumb.json",
                     "benchmark/configs/resnet14_thumb.py",
                     "benchmark/jobs/fit-step-bs8.json",
                     "benchmark/layer_metrics/syncs_traced.py"}
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}
