"""``BENCHMARK.json`` against the contract it was written to, and the
runner's refusals: no TPU, or a directory with the benchmark's files and
no program, is a non-zero exit with no result line."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_dry import REPO, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    assert "\t" not in text and "\n" not in text and 1 <= len(text) <= 200


def test_benchmark_json_meets_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= len(b["paths"]) <= 16 and 1 <= len(b["command"]) <= 32
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    for word in b["command"]:
        _line(word)
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in b["paths"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51

    names = [c["name"] for c in b["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        _line(c["source"]), _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            assert isinstance(json.load(f), dict)
        assert len(c["reduced"]) <= 16

    cells = b["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == set(names)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        _line(w["why"])
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)

    cell_names = {w["name"] for w in cells}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert e2e["setup_s"]["bound"] == 0.1
    assert 1 <= len(b["per_layer"]) <= 128
    every = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        _line(m["layer"])
        assert m["moves"] in e2e and m["source"] in SOURCES
        # reported only where the metric it moves is
        where = set(m.get("workloads", cell_names))
        assert where <= set(e2e[m["moves"]].get("workloads", cell_names))
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cell_names
    for w in cell_names:   # setup_s, another end-to-end, a per-layer
        mine = [m for m in b["end_to_end"]
                if w in m.get("workloads", cell_names)]
        assert len(mine) >= 2
        assert any(w in m.get("workloads", cell_names)
                   for m in b["per_layer"])


def test_every_named_file_is_there():
    C, _run = harness()
    b = _bench()
    for w in b["workloads"]:
        cell = C.Cell(w["name"])
        assert callable(cell.driver_module().run)
        cfgmod = cell.config_module()
        assert cell.job["build"] in cell.config["builds"]
        assert callable(cfgmod.reference) and callable(cfgmod.build)
        for m in cell.metrics("per_layer"):
            assert callable(cell.reader(m["name"]))
    # files under ``paths`` are named from the characters of a name
    for p in b["paths"]:
        for _dir, _subdirs, names in os.walk(os.path.join(REPO, p)):
            for n in names:
                if "__pycache__" not in _dir:
                    assert re.match(r"^[A-Za-z0-9_.\-]+$", n), n


def _run_main(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, script, "--workload", "resnet50-fit-step-bs64",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=240)


def test_no_tpu_is_a_nonzero_exit_and_no_result_line():
    proc = _run_main(REPO, os.path.join("benchmark", "harness", "run.py"))
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout
    assert "platform=cpu" in proc.stdout      # it names what it found


def test_the_benchmark_alone_is_a_nonzero_exit(tmp_path):
    """Only ``BENCHMARK.json`` and the files under ``paths``: there is no
    program to measure."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in _bench()["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_main(str(tmp_path),
                     os.path.join("benchmark", "harness", "run.py"))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("script", ["benchmark/harness/run.py",
                                    "benchmark/harness/benchcore.py",
                                    "benchmark/trace/reduce.py"])
def test_nothing_imports_bench_or_chip_smoke(script):
    with open(os.path.join(REPO, script)) as f:
        text = f.read()
    assert not re.search(r"^\s*(import|from)\s+(bench|chip_smoke)\b", text,
                         re.M)
