"""Device time by the program's own scopes, on a trace recorded on the chip
(``benchmark/trace/scopes_fixture_1chip.xplane.pb``, made by
``record_scopes_fixture.py``): ``profiler.device_ops`` against the file
decoded a second way (protobuf's own decoder over a descriptor written
here), every reader of ``benchmark/trace/scopes.py`` against arithmetic
done here over that decoding, the device section of
``profiler.dumps()``, and None from every reader where a trace has none of
the program's scopes: the two older fixtures and a CPU trace."""
import json
import os
import re
import shutil
import tempfile

import pytest

from bench_dry import REPO, harness

TRACE_DIR = os.path.join(REPO, "benchmark", "trace")
FIXTURE = os.path.join(TRACE_DIR, "scopes_fixture_1chip.xplane.pb")
# metric: (how its number is worked out below, what it reads)
READERS = {
    "unscoped_device_share_pct": ("class_share", "unscoped"),
    "optimizer_device_ms_per_step": ("class_ms", "optimizer"),
    "recompute_device_ms_per_step": ("phase_ms", "recompute"),
    "attention_device_ms_per_step": ("class_ms", "attention"),
    "scan_device_ms_per_step": ("class_ms", "scan"),
    "moe_device_ms_per_step": ("class_ms", "moe"),
    "head_device_ms_per_step": ("class_ms", "head"),
    "conv_device_ms_per_step": ("class_ms", "conv"),
    "batchnorm_device_ms_per_step": ("class_ms", "batchnorm"),
    "pool_device_ms_per_step": ("class_ms", "pool"),
    "backward_device_share_pct": ("phase_share", "backward"),
}
DATA = {"cell": {"chips": 1, "steps_per_sync": 1}, "trace": {}}


# -- the file decoded a second way --------------------------------------------
def _xspace(path):
    """``[(plane name, {line name: [(start_ns, duration_ns, event name,
    tf_op)]})]`` by protobuf's decoder; the fields are xplane.proto's."""
    pytest.importorskip("google.protobuf")
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="xplane_as_the_test_reads_it.proto", package="xt",
        syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, kind in fields:
            repeated = kind.startswith("*")
            kind = kind.lstrip("*")
            fd = m.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if kind in ("int64", "uint64", "string"):
                fd.type = getattr(F, "TYPE_" + kind.upper())
            else:
                fd.type, fd.type_name = F.TYPE_MESSAGE, ".xt." + kind
    message("XStat", ("metadata_id", 1, "int64"), ("str_value", 5, "string"),
            ("ref_value", 7, "uint64"))
    message("XEvent", ("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
            ("duration_ps", 3, "int64"))
    message("XLine", ("name", 2, "string"), ("timestamp_ns", 3, "int64"),
            ("events", 4, "*XEvent"))
    message("XEventMetadata", ("id", 1, "int64"), ("name", 2, "string"),
            ("stats", 5, "*XStat"))
    message("XStatMetadata", ("id", 1, "int64"), ("name", 2, "string"))
    # a map field is a repeated entry of key = 1, value = 2 on the wire
    message("EventEntry", ("key", 1, "int64"), ("value", 2, "XEventMetadata"))
    message("StatEntry", ("key", 1, "int64"), ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, "string"), ("lines", 3, "*XLine"),
            ("event_metadata", 4, "*EventEntry"),
            ("stat_metadata", 5, "*StatEntry"))
    message("XSpace", ("planes", 1, "*XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    space = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xt.XSpace"))()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = []
    for plane in space.planes:
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            tf_op = ""
            for st in e.value.stats:
                if stat_name.get(st.metadata_id) == "tf_op":
                    tf_op = st.str_value or stat_name.get(st.ref_value, "")
            meta[e.key] = (e.value.name, tf_op)
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (int(line.timestamp_ns + ev.offset_ps / 1000),
                 int(ev.duration_ps / 1000)) + meta.get(ev.metadata_id,
                                                        ("", ""))
                for ev in line.events]
        out.append((plane.name, lines))
    return out


@pytest.fixture(scope="module")
def decoded():
    return _xspace(FIXTURE)


def _device_rows(decoded):
    return [(int(name.rsplit(":", 1)[1]), lines) for name, lines in decoded
            if re.match(r"^/device:TPU:\d+$", name)]


# -- the classes, worked out here ----------------------------------------------
def _class(path):
    """A scope path's class, by its components (``scopes.py`` matches
    patterns; the order is the issue's)."""
    parts = path.split("/")
    pairs = {"/".join(parts[i:i + 2]) for i in range(len(parts) - 1)}
    if "step/optimizer" in pairs or any(
            p.startswith("op/") and p.endswith("_update") for p in pairs):
        return "optimizer"
    if "moe" in parts:
        return "moe"
    if pairs & {"mamba/ssd", "kda/scan"}:
        return "scan"
    if "attention" in parts:
        return "attention"
    if "head" in parts or "step/loss" in pairs:
        return "head"
    for op, name in (("op/Convolution", "conv"), ("op/BatchNorm", "batchnorm"),
                     ("op/Pooling", "pool")):
        if op in pairs:
            return name
    return "other"


def _by_hand(decoded):
    """ms a step by class and by phase, and of all ops, of the fixture."""
    from mxnet_tpu import profiler
    syncs = sorted(s for name, lines in decoded if name.startswith("/host:")
                   for events in lines.values()
                   for s, _d, ev, _t in events if ev == "bench/sync")
    lo, hi, steps = syncs[0], syncs[-1], len(syncs) - 1
    (_dev, lines), = _device_rows(decoded)
    classes, phases, whole = {}, {}, 0
    for start, dur, name, tf_op in lines["XLA Ops"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a or re.match(r"^%?(while|conditional|call)[.\d]* = ", name):
            continue
        scopes, phs = profiler.parse_op_name(tf_op)
        cs = sorted({_class(s) for s in scopes if s}) or ["unscoped"]
        for c in cs:
            classes[c] = classes.get(c, 0.0) + (b - a) / len(cs)
        ps = sorted(set(phs)) or ["other"]
        for p in ps:
            phases[p] = phases.get(p, 0.0) + (b - a) / len(ps)
        whole += b - a
    ms = 1e-6 / steps
    return ({k: v * ms for k, v in classes.items()},
            {k: v * ms for k, v in phases.items()}, whole * ms, steps)


def _expected(decoded, how, what):
    classes, phases, whole, _steps = _by_hand(decoded)
    return {"class_ms": lambda: classes.get(what, 0.0),
            "phase_ms": lambda: phases.get(what, 0.0),
            "class_share": lambda: 100 * classes.get(what, 0.0) / whole,
            "phase_share": lambda: 100 * phases.get(what, 0.0) / whole}[how]()


def _as_this_runs_trace(monkeypatch, tmp_path, xplane):
    """Lay ``xplane`` where ``run.py`` would have just written it."""
    where = tmp_path / "bench-trace-test" / "plugins" / "profile" / "now"
    where.mkdir(parents=True)
    shutil.copy(xplane, where / "host.xplane.pb")
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))


def _reader(metric):
    C, _run = harness()
    return C.Cell("resnet50-fit-step-bs64").reader(metric)


# -- device_ops -----------------------------------------------------------------
def test_device_ops_are_the_files_own_events(decoded):
    from mxnet_tpu import profiler
    ops = profiler.device_ops(FIXTURE)
    (dev, lines), = _device_rows(decoded)
    want = sorted((start, dur, name) + profiler.parse_op_name(tf_op)
                  for start, dur, name, tf_op in lines["XLA Ops"])
    got = sorted((op.start_ns, op.duration_ns, op.name, op.scopes, op.phases)
                 for op in ops)
    assert got == want and {op.device for op in ops} == {dev}
    runs = sorted(lines["XLA Modules"])
    for op in ops:
        inside = [name for start, dur, name, _t in runs
                  if start <= op.start_ns < start + dur]
        assert op.program == (inside[-1].partition("(")[0] if inside else "")
    assert {op.program for op in ops} >= {"jit_step"}


def test_the_fixture_holds_every_class_and_phase(decoded):
    classes, phases, whole, steps = _by_hand(decoded)
    assert steps == 4
    assert set(classes) >= {"optimizer", "moe", "scan", "attention", "head",
                            "conv", "batchnorm", "pool", "other"}
    assert set(phases) == {"forward", "backward", "recompute", "other"}
    assert all(v > 0 for v in classes.values())
    # a partition of the op time, read twice
    assert sum(classes.values()) == pytest.approx(whole, rel=1e-9)
    assert sum(phases.values()) == pytest.approx(whole, rel=1e-9)
    # most of it runs under a scope of the program's
    assert classes.get("unscoped", 0.0) < 0.25 * whole


def test_window_and_clock_are_reduce_pys(decoded):
    """The readers clip to the window ``reduce.py`` reports."""
    C, _run = harness()
    red = C.load_py(os.path.join(TRACE_DIR, "reduce.py"), "bench_reduce")
    out = red.reduce(red.load_xplane(FIXTURE), n_devices=1)
    scopes = C.load_py(os.path.join(TRACE_DIR, "scopes.py"), "bench_scopes")
    syncs = scopes.syncs_of(FIXTURE)
    assert len(syncs) == out["syncs"] == 5
    assert (syncs[-1] - syncs[0]) / 1e9 == pytest.approx(out["window_s"])
    _classes, _phases, whole, steps = _by_hand(decoded)
    # ops of one core do not overlap: their sum is the busy time, less
    # the ops that only hold others
    assert whole * steps / 1e3 <= out["busy_s"] * (1 + 1e-9)
    assert whole * steps / 1e3 >= 0.98 * out["busy_s"]


# -- the readers ----------------------------------------------------------------
@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_against_numbers_worked_out_here(monkeypatch, tmp_path,
                                                decoded, metric):
    _as_this_runs_trace(monkeypatch, tmp_path, FIXTURE)
    got = _reader(metric)(DATA)
    want = _expected(decoded, *READERS[metric])
    assert want > 0, "the fixture exercises every reader"
    assert got == pytest.approx(want, rel=1e-9)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    where = tmp_path_factory.mktemp("cpu-trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(where), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/sync"):
            pass
        with jax.named_scope("op/tanh"):
            jax.jit(jnp.tanh)(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = (where / "plugins" / "profile").glob("*/*.xplane.pb")
    return str(path)


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("trace", ["fixture_1chip", "fixture_4chip", "cpu",
                                   "none"])
def test_reader_gives_none_without_the_programs_scopes(
        monkeypatch, tmp_path, cpu_trace, capsys, trace, metric):
    if trace == "none":     # no traced run wrote anything
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    else:
        _as_this_runs_trace(monkeypatch, tmp_path, cpu_trace if trace == "cpu"
                            else os.path.join(TRACE_DIR,
                                              trace + ".xplane.pb"))
    assert _reader(metric)(DATA) is None
    said = capsys.readouterr().out
    if trace.startswith("fixture"):
        assert "no op/ scope in the trace" in said
    elif trace == "cpu":
        assert "no TPU plane" in said


def test_one_parse_and_one_line_for_all_readers(monkeypatch, tmp_path,
                                                capsys):
    from mxnet_tpu import profiler
    _as_this_runs_trace(monkeypatch, tmp_path, FIXTURE)
    calls = []
    real = profiler.device_ops
    monkeypatch.setattr(profiler, "device_ops",
                        lambda path: calls.append(path) or real(path))
    values = {m: _reader(m)(DATA) for m in READERS}
    assert len(calls) == 1 and None not in values.values()
    said = [line for line in capsys.readouterr().out.splitlines()
            if "device time by scope" in line]
    assert len(said) == 1 and "ms a step over 4 steps" in said[0]


def test_a_program_from_before_device_ops_reads_as_nothing(
        monkeypatch, tmp_path):
    """The driver lays these readers over the parent's checkout too."""
    from mxnet_tpu import profiler
    _as_this_runs_trace(monkeypatch, tmp_path, FIXTURE)
    monkeypatch.delattr(profiler, "device_ops")
    assert _reader("conv_device_ms_per_step")(DATA) is None


# -- BENCHMARK.json ---------------------------------------------------------------
def test_the_eleven_entries_and_their_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in bench["per_layer"][-len(mine):]] == \
        [m["name"] for m in mine] and len(mine) == len(READERS)
    cells = {w["name"]: w["config"] for w in bench["workloads"]}
    image = {c for c, cfg in cells.items()
             if cfg in ("resnet50_v1", "mobilenetv2_1.0")}
    token = set(cells) - image
    for m in mine:
        assert (m["source"], m["better"], m["moves"]) == \
            ("device_trace", "lower", "images_per_s")
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
        listed = set(m.get("workloads", cells))
        if m["name"] in ("conv_device_ms_per_step", "pool_device_ms_per_step",
                         "batchnorm_device_ms_per_step"):
            assert listed == image
        elif m["name"] == "moe_device_ms_per_step":
            assert listed == {c for c in token if "granite" not in c}
        elif "workloads" in m:
            assert listed == token
        else:
            assert listed == set(cells)


# -- the program's own table ------------------------------------------------------
def test_profiler_dumps_ends_in_the_devices_time_by_scope(
        monkeypatch, tmp_path, decoded):
    from mxnet_tpu import profiler
    where = tmp_path / "plugins" / "profile" / "now"
    where.mkdir(parents=True)
    shutil.copy(FIXTURE, where / "host.xplane.pb")
    monkeypatch.setitem(profiler._state, "xplane_dir", str(tmp_path))
    table = profiler.dumps()
    assert "Profile Statistics:" in table          # the host table stays
    head, _, device = table.partition("Device time by scope")
    rows = {line.split()[0]: line.split()[1:]
            for line in device.splitlines()[2:] if line.strip()}
    assert {"op/Convolution", "op/BatchNorm", "op/Pooling", "step/optimizer",
            "(unscoped)"} <= set(rows)
    assert any(r.startswith("toy/attention/op/FullyConnected") for r in rows)
    as_json = profiler.dumps(format="json")["device_time_by_scope"]
    (_dev, lines), = _device_rows(decoded)
    whole = sum(dur for _s, dur, name, _t in lines["XLA Ops"]
                if not re.match(r"^%?(while|conditional|call)[.\d]* = ",
                                name))
    assert sum(r["total_ms"] for r in as_json.values()) == \
        pytest.approx(whole / 1e6, rel=1e-9)
    assert float(rows["op/Convolution"][1]) == \
        pytest.approx(as_json["op/Convolution"]["total_ms"], abs=1e-4)
    # while a trace is running there is nothing to read yet
    monkeypatch.setitem(profiler._state, "jax_trace_dir", str(tmp_path))
    assert "Device time by scope" not in profiler.dumps()
