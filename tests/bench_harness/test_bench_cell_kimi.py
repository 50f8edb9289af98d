"""The token cell ``kimi-linear-spmd-seq8192-bs1`` dry-driven on the CPU
through ``run.run_cell``, untraced and traced, as
``test_bench_cell_zaya.py`` drives ZAYA1's: the unedited ``spmd_lm_moe``
driver's reference checks (first loss, the first step's top-k routing over
s + b, the logits of the tokens whose routing cannot flip), AdamW, the
five remat boundaries, the latent gauge; the cell's three readers on a
run without a trace, on rows of a recorded trace, and on a trace whose
kernels they price; the configuration's counts from its shapes alone; the
published keys against the published config's; the reference alone at thumbnail
size.  The overlay is this file's own."""
import json
import os

import jax
import numpy as np
import pytest

import mxnet_tpu as mx

from bench_dry import check_line, harness

CELL = "kimi-linear-spmd-seq8192-bs1"
METRICS = ("mla_device_ms_per_step", "mla_kernel_roofline_pct",
           "kda_device_ms_per_step")
# the timed five layers at thumbnail widths: 2 KDA heads of 16, 4 latent
# attention heads with keys of 16 + 8 over values of 16 from a latent of
# 24, a dense MLP of 96, 4 of 16 experts held, top-3, tiles of 4 rows, a
# length that is not a multiple of the tile; one batch repeated so that the
# thumbnail learns it
DRY = {"config": {"hidden_size": 64, "intermediate_size": 96,
                  "moe_intermediate_size": 32,
                  "linear_attn_config": {
                      "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5],
                      "head_dim": 16, "num_heads": 2,
                      "short_conv_kernel_size": 4},
                  "num_attention_heads": 4, "num_key_value_heads": 4,
                  "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                  "v_head_dim": 16, "kv_lora_rank": 24, "kda_chunk_size": 8,
                  "num_experts": 4, "published": {"num_experts": 16},
                  "first_routed_expert": 4, "num_experts_per_token": 3,
                  "expert_tile_rows": 4, "vocab_size": 64, "num_classes": 64,
                  "image": [30]},
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
    return cell, run.run_cell(cell, seed=2**31 + 11, seconds=1.2,
                              trace=trace, devices=jax.devices()[:1],
                              ctx=mx.cpu(), dry=dry)


@pytest.mark.parametrize("trace", [0, 1])
def test_kimi_cell_dry_drive(trace, capsys):
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
    # a CPU trace has no device plane and the dry drive no table peak: the
    # three readers leave their metrics out, the counts are what it gives
    assert got["compiles_in_window"]["value"] == 0
    for metric in METRICS:
        assert metric not in got
    latent = telemetry.REGISTRY.get("mxnet_mla_latent_channels")
    assert (latent.value({"part": "kv"}), latent.value({"part": "rope"})) \
        == (24, 8)
    # four expert layers, top-3 of 16 over 58 tokens, 4 held
    held = telemetry.REGISTRY.get("mxnet_moe_assignments_held").value()
    assert 0 < held <= 4 * 58 * 3


def test_a_limit_the_routing_breaks_fails_its_check(capsys):
    tol = dict(DRY["job"]["tolerances"], expert_load_rel=-1.0)
    _cell, result = _drive(0, tolerances=tol)
    out = capsys.readouterr().out
    assert "expert_load=FAILED" in out and "logits=ok" in out
    assert result["correct"] is False


@pytest.mark.parametrize("metric", METRICS)
def test_the_readers_give_nothing_where_there_is_no_trace(metric,
                                                          monkeypatch):
    """No trace of this process, a trace ``scopes.read`` cannot use, or no
    table peak: None, never an exception."""
    C, _run = harness()
    import scoperead
    read = C.Cell(CELL).reader(metric)
    data = {"cell": {"name": CELL, "chips": 1, "steps_per_sync": 1,
                     "peak_flops": None},
            "trace": {"steps": 3, "device_ops": []}}
    scopes = scoperead.scopes()
    monkeypatch.setattr(scopes, "newest_trace", lambda: None)
    assert read(data) is None
    monkeypatch.setattr(scopes, "read", lambda d: {"steps": 3})
    monkeypatch.setattr(scopes, "newest_trace", lambda: "/nonexistent.pb")
    assert read(data) is None


def test_the_scope_readers_take_their_layer_and_no_other(monkeypatch):
    """Rows as ``profiler.device_ops`` gives them: clipped to the window,
    containers dropped, an op of two scopes half to each; Solar's KDA is
    not Kimi's."""
    C, _run = harness()
    import scopepath
    import scoperead
    from mxnet_tpu.profiler import DeviceOp
    scopes = scoperead.scopes()
    rows = [
        DeviceOp(0, 1_000, 4_000, "%fusion.1 = f32[] fusion()", "jit_step",
                 ("kimi/attention/latent/op/FullyConnected",), ("",)),
        DeviceOp(0, 6_000, 2_000, "%custom-call.2 = f32[] custom-call()",
                 "jit_step", ("kimi/attention/op/_contrib_flash_attention/"
                              "mx_flash_attention_fwd",), ("",)),
        DeviceOp(0, 9_000, 3_000, "%fusion.3 = f32[] fusion()", "jit_step",
                 ("kimi/kda/scan/op/_contrib_kda_scan",
                  "kimi/moe/op/_contrib_routed_experts"), ("",)),
        DeviceOp(0, 9_000, 3_000, "%while.4 = () while()", "jit_step",
                 ("kimi/kda/scan",), ("",)),
        DeviceOp(0, 12_000, 1_000, "%fusion.5 = f32[] fusion()", "jit_step",
                 ("solar/kda/proj/op/FullyConnected",), ("",)),
        DeviceOp(0, 19_000, 5_000, "%fusion.6 = f32[] fusion()", "jit_step",
                 ("kimi/kda/out/op/z",), ("",)),          # 1 000 ns inside
    ]
    monkeypatch.setattr(scopes, "read", lambda d: {"steps": 2})
    monkeypatch.setattr(scopes, "newest_trace", lambda: "a.pb")
    monkeypatch.setattr(scopes, "syncs_of", lambda p: [0, 10_000, 20_000])
    monkeypatch.setattr(scopepath, "_ops", lambda p: rows)
    data = {"cell": {"name": CELL, "chips": 1, "steps_per_sync": 1}}
    cell = C.Cell(CELL)
    assert cell.reader("mla_device_ms_per_step")(data) \
        == pytest.approx((4_000 + 2_000) * 1e-6 / 2)
    assert cell.reader("kda_device_ms_per_step")(data) \
        == pytest.approx((3_000 / 2 + 1_000) * 1e-6 / 2)


def test_the_roofline_share_prices_the_latent_kernels():
    """The configuration's FLOPs over the table peak, against the seconds
    the ``mx_flash_attention_*`` ops took a step: compute bounds it."""
    C, _run = harness()
    cell = C.Cell(CELL)
    mod = cell.config_module()
    flops = mod.attention_kernel_flops(cell.config)
    peak = 197e12
    seconds = 4 * flops / peak
    data = {"cell": {"name": CELL, "peak_flops": peak},
            "trace": {"steps": 2, "device_ops": [
                ("mx_flash_attention_fwd", seconds),
                ("mx_flash_attention_bwd_dq", seconds / 2),
                ("mx_flash_attention_bwd_dkv", seconds / 2),
                ("fusion.9", 100.0)]}}
    assert cell.reader("mla_kernel_roofline_pct")(data) \
        == pytest.approx(25.0)
    assert mod.attention_kernel_bytes(cell.config) / 819e9 < flops / peak


def test_published_widths_give_the_counts_of_the_cut():
    C, _run = harness()
    cell = C.Cell(CELL)
    cfg, mod = cell.config, cell.config_module()
    shapes = mod.param_shapes(cfg, "gluon")
    count = {k: int(np.prod(s)) for k, s in shapes.items()}
    # auxiliary state: the two counts and a bias of 256 an expert layer
    assert sorted(set(shapes) - set(mod.trained(shapes))) == sorted(
        ["expert_load", "expert_rows"]
        + [f"layers.{i}.moe.bias" for i in range(1, 5)])
    assert shapes["layers.1.moe.bias"] == (256,)
    assert shapes["expert_load"] == (4, 8)
    assert [k for k in shapes if k.startswith("layers.3.mla.")] and not [
        k for k in shapes if k.startswith(("layers.3.kda.", "layers.4.mla."))]

    def part(at):
        return sum(count[k] for k in mod.trained(shapes) if k.startswith(at))
    # the parameters of the cut, part by part
    assert part("layers.0.kda.") == 39_514_272
    assert part("layers.3.mla.") == 29_114_880
    assert part("layers.0.mlp.") == 63_700_992
    assert part("layers.1.moe.") == 64_290_816
    assert count["embed"] == count["head"] == 47_185_920
    assert sum(count[k] for k in mod.trained(shapes)) == 602_433_408
    # every published width: 32 heads of each kind, 192 over 128, the
    # 512-channel latent, the router's 256
    assert shapes["layers.3.mla.q"] == (32 * 192, 2304)
    assert shapes["layers.3.mla.kv_a"] == (512 + 64, 2304)
    assert shapes["layers.3.mla.kv_b"] == (32 * 256, 512)
    assert shapes["layers.3.mla.o"] == (2304, 32 * 128)
    assert shapes["layers.0.kda.q"] == (4096, 2304)
    assert shapes["layers.0.kda.a_down"] == (128, 2304)
    assert shapes["layers.1.moe.router"] == (256, 2304)
    assert shapes["layers.1.moe.w1"] == (8, 1024, 2304)
    assert shapes["layers.1.moe.shared_in"] == (2048, 2304)
    assert shapes["layers.0.mlp.in"] == (2 * 9216, 2304)
    # the kernels' work: keys of 192 over values of 128, 32 heads, one layer
    pairs = 8192 * 8193 // 2
    assert mod.attention_kernel_flops(cfg) == 2 * pairs * 32 * (3 * 192
                                                               + 3 * 128)
    assert mod.attention_kernel_bytes(cfg) == 4 * 6 * (192 + 128) * 32 * 8192
    # about 388 M products a token, 19.1 TFLOP a step
    macs = mod.macs_per_image(cfg, "gluon")
    assert 385e6 < macs / 8192 < 390e6
    assert 19.0e12 < 6 * macs < 19.2e12
    # the routed experts count at the expected two assignments a token
    dense = mod.macs_per_image(dict(cfg, published={"num_experts": 8}),
                               "gluon")
    assert dense - macs == pytest.approx(
        8192 * 4 * (8 - 0.25) * 3 * 1024 * 2304
        + 8192 * 4 * (8 - 256) * 2304, rel=1e-9)


def test_the_file_carries_every_published_key_but_the_three_reduced():
    C, _run = harness()
    cell = C.Cell(CELL)
    cfg = cell.config
    # the published config.json of Kimi-Linear-48B-A3B-Instruct, as its
    # source_url gives it
    published = os.path.join(os.path.dirname(__file__),
                             "kimi_linear_published_config.json")
    with open(published) as f:
        row = json.load(f)
    assert cfg["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(cfg["reduced"]) == reduced
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    # the floors: the leading dense layer and four expert layers, a whole
    # period of 3 KDA : 1 MLA, eight experts, an eighth of the rows
    assert cfg["num_hidden_layers"] == 5 and cfg["num_experts"] == 8
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["assumed"] and "32 chips" in cfg["deployment"]


def test_reference_runs_at_thumbnail():
    """The reference alone, from the shapes: finite logits of the right
    shape and a loss near ln(vocab) at small random weights."""
    C, _run = harness()
    cell = C.Cell(CELL)
    mod = cell.config_module()
    cfg = dict(cell.config, **DRY["config"])
    rng = np.random.default_rng(0)
    params = {k: (np.ones(s) if k.endswith(("norm", "norm1", "norm2"))
                  else rng.standard_normal(s) * 0.1).astype(np.float32)
              for k, s in mod.param_shapes(cfg, "gluon").items()}
    ids = rng.integers(0, 64, (2, 29)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, margin, counts = mod.reference(cfg, "gluon", routing=True)(
            params, ids)
        loss = float(mod.loss(cfg, "gluon")(params, ids, ids))
    assert logits.shape == (2, 29, 64) and margin.shape == (4, 2, 29)
    assert counts.shape == (4, 4) and 0 < int(counts.sum()) <= 4 * 58 * 3
    assert np.isfinite(logits).all() and np.abs(logits).max() > 0
    assert abs(loss - np.log(64)) < 1.5
