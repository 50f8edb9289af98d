"""tools/: im2rec packer + local dist launcher + packaging metadata
(reference: tools/im2rec.py, tools/launch.py:128 local mode)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scrubbed_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _make_images(root, n_per_class=3):
    from PIL import Image
    rng = np.random.RandomState(0)
    for cls in ("cats", "dogs"):
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = rng.randint(0, 255, (40, 48, 3), np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"{i}.jpg"))


def test_im2rec_list_and_pack(tmp_path):
    root = str(tmp_path / "imgs")
    _make_images(root)
    prefix = str(tmp_path / "data")
    env = _scrubbed_env()
    r = subprocess.run([sys.executable, os.path.join(_REPO, "tools",
                                                     "im2rec.py"),
                        "--list", "--recursive", prefix, root],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    lst = open(prefix + ".lst").read().strip().splitlines()
    assert len(lst) == 6
    r = subprocess.run([sys.executable, os.path.join(_REPO, "tools",
                                                     "im2rec.py"),
                        "--num-thread", "2", prefix, root],
                       env=env, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr
    assert os.path.exists(prefix + ".rec")
    assert os.path.exists(prefix + ".idx")

    # read back through the framework's reader
    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    keys = sorted(rec.keys)
    assert len(keys) == 6
    header, img = recordio.unpack(rec.read_idx(keys[0]))
    assert len(img) > 100           # an encoded JPEG payload
    labels = set()
    for k in keys:
        h, _ = recordio.unpack(rec.read_idx(k))
        labels.add(float(h.label))
    assert labels == {0.0, 1.0}     # two classes from --recursive


def test_im2rec_feeds_image_iter(tmp_path):
    root = str(tmp_path / "imgs")
    _make_images(root)
    prefix = str(tmp_path / "data")
    env = _scrubbed_env()
    subprocess.run([sys.executable, os.path.join(_REPO, "tools",
                                                 "im2rec.py"),
                    "--list", "--recursive", prefix, root], env=env,
                   check=True, timeout=120)
    subprocess.run([sys.executable, os.path.join(_REPO, "tools",
                                                 "im2rec.py"),
                    prefix, root], env=env, check=True, timeout=180)
    from mxnet_tpu import image
    it = image.ImageIter(batch_size=2, data_shape=(3, 32, 32),
                         path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx", shuffle=False)
    batch = next(iter(it))
    assert batch.data[0].shape == (2, 3, 32, 32)


_TRAIN = """
import os
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import kvstore, nd
kv = kvstore.create("dist_sync")
kv.init("w", nd.zeros(4))
kv.push("w", nd.ones(4) * (kv.rank + 1))
out = nd.zeros(4)
kv.pull("w", out=out)
# sum over ranks 1..n
expect = sum(range(1, kv.num_workers + 1))
np.testing.assert_allclose(out.asnumpy(), expect)
print("worker", kv.rank, "ok")
"""


def test_launch_local_cluster(tmp_path):
    script = str(tmp_path / "train.py")
    with open(script, "w") as f:
        f.write(_TRAIN)
    env = _scrubbed_env()
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
         "-n", "3", "-p", "19431", sys.executable, script],
        env=env, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert r.stdout.count("ok") == 3


def test_pyproject_metadata():
    import tomllib
    with open(os.path.join(_REPO, "pyproject.toml"), "rb") as f:
        meta = tomllib.load(f)
    assert meta["project"]["name"] == "mxnet-tpu"
    assert "jax>=0.9" in meta["project"]["dependencies"]


def test_config_registry():
    import mxnet_tpu as mx
    cfg = mx.config
    assert cfg.get("DMLC_PS_ROOT_PORT") == 9091
    os.environ["MXNET_KVSTORE_HEARTBEAT_INTERVAL"] = "2.5"
    try:
        assert cfg.get("MXNET_KVSTORE_HEARTBEAT_INTERVAL") == 2.5
    finally:
        del os.environ["MXNET_KVSTORE_HEARTBEAT_INTERVAL"]
    table = cfg.describe()
    assert "MXNET_ENGINE_TYPE" in table
    assert len(cfg.list_vars()) >= 20
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError):
        cfg.get("MXNET_NO_SUCH_VAR")


def test_graftlint_json_schema_round_trips(tmp_path):
    """--json is a machine interface (schema v2): findings + parse
    errors + call_graph stats must survive a loads->dumps->loads round
    trip, and the stats must reflect the analyzed tree."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "def top():\n"
        "    return helper()\n\n"
        "def helper():\n"
        "    return unknown_dynamic.call()\n\n"
        "def save(path, doc):\n"
        "    with open(path, 'w') as f:\n"
        "        f.write(doc)\n")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "graftlint.py"),
         str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=120)
    doc = json.loads(r.stdout)
    assert doc["schema_version"] == 2
    cg = doc["call_graph"]
    assert set(cg) == {"functions", "edges", "unresolved_calls"}
    assert cg["functions"] >= 3 and cg["edges"] >= 1
    assert cg["unresolved_calls"] >= 1
    assert any(f["rule"] == "torn-write" for f in doc["findings"])
    # byte-level round trip: the schema holds nothing json can't carry
    assert json.loads(json.dumps(doc)) == doc


def test_graftlint_sarif_round_trips(tmp_path):
    """--sarif emits SARIF 2.1.0: every registered rule in
    tool.driver.rules, results carrying graftlint fingerprints as
    partialFingerprints, severities mapped to SARIF levels — and the
    document survives a loads->dumps->loads round trip."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "def serve(pool):\n"
        "    slot = pool.acquire('s', 4)\n"
        "    risky()\n"
        "    pool.release(slot)\n\n"
        "def save(path, doc):\n"
        "    with open(path, 'w') as f:\n"
        "        f.write(doc)\n")
    out = tmp_path / "lint.sarif"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "graftlint.py"),
         str(tmp_path), "--sarif", str(out)],
        capture_output=True, text=True, timeout=120)
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "graftlint"
    rule_ids = [rd["id"] for rd in driver["rules"]]
    assert rule_ids == sorted(rule_ids)          # stable ruleIndex order
    assert "torn-write" in rule_ids
    assert "resource-leak-on-raise" in rule_ids  # ALL rules, fired or not
    by_rule = {res["ruleId"]: res for res in run["results"]}
    assert {"torn-write", "resource-leak-on-raise"} <= set(by_rule)
    for res in run["results"]:
        # ruleIndex must resolve to the matching descriptor
        assert driver["rules"][res["ruleIndex"]]["id"] == res["ruleId"]
        fp = res["partialFingerprints"]["graftlintFingerprint/v1"]
        assert fp.startswith(res["ruleId"] + "|")
        region = res["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] >= 1 and region["startColumn"] >= 1
    assert by_rule["torn-write"]["level"] == "error"
    leak = by_rule["resource-leak-on-raise"]
    assert leak["locations"][0]["physicalLocation"][
        "artifactLocation"]["uri"].endswith("m.py")
    # byte-level round trip
    assert json.loads(json.dumps(doc)) == doc
