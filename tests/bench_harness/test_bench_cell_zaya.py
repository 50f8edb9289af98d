"""The token cell ``zaya1-8b-spmd-seq8192-bs1`` dry-driven on the CPU
through ``run.run_cell``, untraced and traced, as
``test_bench_cell_nemotron.py`` drives Nemotron's: the unedited
``spmd_lm_moe`` driver's reference checks (first loss, the first step's
top-1 routing over s + β, the logits of the tokens whose routing cannot
flip), AdamW, the five remat boundaries with two arrays across each, the
cell's three per-layer metrics; the readers on a registry with and without
the gauge and on a run without a trace; the configuration's counts from its
shapes alone; the published keys against the catalog's; the reference alone
at thumbnail size.  The overlay is this file's own."""
import json
import os

import jax
import numpy as np
import pytest

import mxnet_tpu as mx

from bench_dry import check_line, harness

CELL = "zaya1-8b-spmd-seq8192-bs1"
METRICS = {"cca_mix_device_ms_per_step": "ms",
           "router_device_ms_per_step": "ms", "moe_tokens_held_pct": "%"}
# the timed five layers at thumbnail widths: 4 query heads over 2 key/value
# heads of 16 (8 channels turned), 4 of 8 experts held, one a token, a
# router of width 16, tiles of 4 rows, a length that is not a multiple of
# the tile; one batch repeated so that the thumbnail learns it
DRY = {"config": {"hidden_size": 64, "head_dim": 16,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "moe_intermediate_size": 32, "router_hidden_size": 16,
                  "num_experts": 4, "published": {"num_experts": 8},
                  "first_routed_expert": 2, "expert_tile_rows": 4,
                  "vocab_size": 64, "num_classes": 64, "image": [30]},
       "job": {"batch": 2, "trace_seconds": 0.6, "pool_batches": 1,
               "optimizer_params": {"learning_rate": 0.01, "beta1": 0.9,
                                    "beta2": 0.95, "epsilon": 1e-8,
                                    "wd": 1e-5},
               # float32 on the CPU against float32: rounding only, so
               # no routing flips and every token is compared
               "tolerances": {"routing_margin": 0.0,
                              "logits_median_rel": 1e-4,
                              "logits_p99_over_median": 3.0,
                              "loss_rel": 1e-5, "expert_load_rel": 0.0}}}


def _drive(trace, **job):
    C, run = harness()
    cell = C.Cell(CELL)
    dry = {"config": DRY["config"], "job": dict(DRY["job"], **job)}
    return cell, run.run_cell(cell, seed=2**31 + 5, seconds=1.2, trace=trace,
                              devices=jax.devices()[:1], ctx=mx.cpu(),
                              dry=dry)


@pytest.mark.parametrize("trace", [0, 1])
def test_zaya_cell_dry_drive(trace, capsys):
    from mxnet_tpu import telemetry
    cell, result = _drive(trace)
    result = check_line(cell, result, trace)
    out = capsys.readouterr().out
    assert "remat boundaries in the step program: 5 of 5 layers" in out
    assert "step_engaged=ok" in out and "logits=ok" in out
    assert "first_loss=ok" in out and "expert_load=ok" in out
    assert "over the 100.0% of tokens" in out
    got = result["metrics"]
    if not trace:
        assert set(got) == {"setup_s", "images_per_s"}
        return
    # a CPU trace has no device plane: the two device readers leave their
    # metrics out, the counts are what it can give
    assert got["compiles_in_window"]["value"] == 0
    assert "cca_mix_device_ms_per_step" not in got
    assert "router_device_ms_per_step" not in got
    held = telemetry.REGISTRY.get("mxnet_moe_assignments_held").value()
    # 5 layers x 58 tokens, one expert each, at most
    assert 0 < held <= 5 * 58
    assert got["moe_tokens_held_pct"]["unit"] == "%"
    assert got["moe_tokens_held_pct"]["value"] == pytest.approx(
        100 * held / (2 * 8192 * 5))    # the reader counts the CELL's slots
    latent = telemetry.REGISTRY.get("mxnet_cca_latent_channels")
    assert (latent.value({"part": "q"}), latent.value({"part": "kv"})) \
        == (64, 32)
    assert telemetry.REGISTRY.get(
        "mxnet_router_eda_gamma_abs_mean").value() > 0     # AdamW moved it


def test_a_limit_the_routing_breaks_fails_its_check(capsys):
    tol = dict(DRY["job"]["tolerances"], expert_load_rel=-1.0)
    _cell, result = _drive(0, tolerances=tol)
    out = capsys.readouterr().out
    assert "expert_load=FAILED" in out and "logits=ok" in out
    assert result["correct"] is False


def test_the_held_share_reads_the_gauge_and_nothing_else(monkeypatch):
    C, _run = harness()
    from mxnet_tpu import telemetry
    read = C.Cell(CELL).reader("moe_tokens_held_pct")
    data = {"cell": {"name": CELL, "batch": 1}}
    load = np.full((5, 8), 512.0)
    telemetry.record_moe_load(2 * load, np.full(5, 8192.0), steps=2)
    assert read(data) == 50.0
    telemetry.record_moe_load(0 * load, np.zeros(5))
    assert read(data) == 0.0
    monkeypatch.setattr(telemetry.REGISTRY, "get", lambda name: None)
    assert read(data) is None


@pytest.mark.parametrize("metric", ["cca_mix_device_ms_per_step",
                                    "router_device_ms_per_step"])
def test_the_scope_readers_give_nothing_where_there_is_no_trace(metric,
                                                                monkeypatch):
    """No trace of this process, a trace ``scopes.read`` cannot use, or a
    program whose profiler fails: None, never an exception."""
    C, _run = harness()
    import scoperead
    read = C.Cell(CELL).reader(metric)
    data = {"cell": {"name": CELL, "chips": 1, "steps_per_sync": 1}}
    scopes = scoperead.scopes()
    monkeypatch.setattr(scopes, "newest_trace", lambda: None)
    assert read(data) is None
    monkeypatch.setattr(scopes, "read", lambda d: {"steps": 3})
    monkeypatch.setattr(scopes, "newest_trace", lambda: "/nonexistent.pb")
    assert read(data) is None


def test_the_scope_readers_divide_a_fused_op_among_its_scopes(monkeypatch):
    """Rows as ``profiler.device_ops`` gives them: clipped to the window,
    containers dropped, an op of two scopes half to each."""
    C, _run = harness()
    import scopepath
    import scoperead
    from mxnet_tpu.profiler import DeviceOp
    scopes = scoperead.scopes()
    rows = [
        DeviceOp(0, 1_000, 4_000, "%fusion.1 = f32[] fusion()", "jit_step",
                 ("zaya/attention/mix/op/_contrib_causal_conv1d",), ("",)),
        DeviceOp(0, 6_000, 2_000, "%fusion.2 = f32[] fusion()", "jit_step",
                 ("zaya/attention/rope/op/x", "zaya/attention/proj/op/y"),
                 ("",)),
        DeviceOp(0, 9_000, 3_000, "%fusion.3 = f32[] fusion()", "jit_step",
                 ("zaya/moe/router/op/FullyConnected",), ("",)),
        DeviceOp(0, 9_000, 3_000, "%while.4 = () while()", "jit_step",
                 ("zaya/moe/router",), ("",)),
        DeviceOp(0, 19_000, 5_000, "%fusion.5 = f32[] fusion()", "jit_step",
                 ("zaya/moe/router/op/z",), ("",)),      # 1 000 ns inside
        DeviceOp(1, 1_000, 9_000, "%fusion.6 = f32[] fusion()", "jit_step",
                 ("zaya/attention/mix",), ("",)),        # another chip
    ]
    monkeypatch.setattr(scopes, "read", lambda d: {"steps": 2})
    monkeypatch.setattr(scopes, "newest_trace", lambda: "a.pb")
    monkeypatch.setattr(scopes, "syncs_of", lambda p: [0, 10_000, 20_000])
    monkeypatch.setattr(scopepath, "_ops", lambda p: rows)
    data = {"cell": {"name": CELL, "chips": 1, "steps_per_sync": 1}}
    cell = C.Cell(CELL)
    assert cell.reader("cca_mix_device_ms_per_step")(data) \
        == pytest.approx((4_000 + 1_000) * 1e-6 / 2)
    assert cell.reader("router_device_ms_per_step")(data) \
        == pytest.approx((3_000 + 1_000) * 1e-6 / 2)


def test_published_widths_give_the_issue_counts():
    C, _run = harness()
    cell = C.Cell(CELL)
    cfg, mod = cell.config, cell.config_module()
    shapes = mod.param_shapes(cfg, "gluon")
    count = {k: int(np.prod(s)) for k, s in shapes.items()}
    # auxiliary state: the two counts and a bias of 16 a layer
    assert sorted(set(shapes) - set(mod.trained(shapes))) == sorted(
        ["expert_load", "expert_rows"]
        + [f"layers.{i}.moe.bias" for i in range(5)])
    assert shapes["layers.0.moe.bias"] == (16,)
    part = lambda at: sum(count[k] for k in mod.trained(shapes)  # noqa: E731
                          if k.startswith(at))
    # the issue's arithmetic
    assert sum(count[f"layers.0.attn.{n}"]
               for n in ("q", "k", "v1", "v2", "o")) == 5_242_880
    assert part("layers.0.attn.conv") == 332_800
    assert part("layers.0.router.") == 660_497
    assert part("layers.0.") - part("layers.0.moe.") == 6_256_659
    assert part("layers.0.moe.") == 8 * 12_582_912
    assert count["embed"] == 67_141_632 and "head" not in shapes
    assert sum(count[k] for k in mod.trained(shapes)) == 601_743_455
    # every published width, the 8 over 2 heads, the router's 16 and 256
    assert shapes["layers.0.attn.q"] == (1024, 2048)
    assert shapes["layers.0.attn.k"] == (256, 2048)
    assert shapes["layers.0.attn.v1"] == (128, 2048)
    assert shapes["layers.0.attn.o"] == (2048, 1024)
    assert shapes["layers.0.attn.conv0_w"] == (1280, 2)
    assert shapes["layers.0.attn.conv1_w"] == (1280, 128, 2)
    assert shapes["layers.0.router.down_w"] == (256, 2048)
    assert shapes["layers.0.router.w3"] == (16, 256)
    assert shapes["layers.0.moe.w1"] == (8, 2048, 2048)
    assert mod._rope_of(cfg) == (64, 5e6)
    # about 172 M products a token, 8.4 to 8.5 TFLOP a step
    macs = mod.macs_per_image(cfg, "gluon")
    assert 171e6 < macs / 8192 < 173e6
    assert 8.4e12 < 6 * macs < 8.5e12
    # the routed experts count at the expected half assignment a token
    dense = mod.macs_per_image(dict(cfg, published={"num_experts": 8}),
                               "gluon")
    assert dense - macs == pytest.approx(
        8192 * 5 * (0.5 * 3 * 2048 * 2048 - 8 * 256), rel=1e-9)
    # the kernels' work: 6 products over the causal pairs, 8 heads of 128
    assert mod.attention_kernel_flops(cfg) == \
        12 * (8192 * 8193 // 2) * 128 * 8 * 5
    assert mod.attention_kernel_bytes(cfg) == \
        4 * 6 * (1024 + 256) * 8192 * 5


def test_the_file_carries_every_published_key_but_the_three_reduced():
    C, _run = harness()
    cell = C.Cell(CELL)
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B"][0]
    assert cfg["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(cfg["reduced"]) == reduced
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    # the floors: five layers, eight experts, exactly an eighth of the rows
    assert cfg["num_hidden_layers"] == 5 and cfg["num_experts"] == 8
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["assumed"] and "8 chips" in cfg["deployment"]


def test_reference_runs_at_thumbnail():
    """The reference alone, from the shapes: finite logits of the right
    shape and a loss near ln(vocab) at small random weights."""
    C, _run = harness()
    cell = C.Cell(CELL)
    mod = cell.config_module()
    cfg = dict(cell.config, **DRY["config"])
    rng = np.random.default_rng(0)
    params = {k: (np.ones(s) if k.endswith(("norm", "scale", "temp"))
                  else rng.standard_normal(s) * 0.1).astype(np.float32)
              for k, s in mod.param_shapes(cfg, "gluon").items()}
    ids = rng.integers(0, 64, (2, 29)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, margin, counts = mod.reference(cfg, "gluon", routing=True)(
            params, ids)
        loss = float(mod.loss(cfg, "gluon")(params, ids, ids))
    assert logits.shape == (2, 29, 64) and margin.shape == (5, 2, 29)
    assert counts.shape == (5, 4) and 0 < int(counts.sum()) <= 5 * 58
    assert np.isfinite(logits).all() and np.abs(logits).max() > 0
    assert abs(loss - np.log(64)) < 1.5
