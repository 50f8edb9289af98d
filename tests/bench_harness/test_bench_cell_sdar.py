"""The token cell ``sdar-30b-a3b-spmd-bd4-seq8192-bs1`` dry-driven on the CPU
through ``run.run_cell``, untraced and traced, as
``test_bench_cell_nemotron.py`` drives Nemotron's: the ``spmd_lm_diffusion``
driver's noise (a level a block, the mask id, the 1/t weights), its
reference checks (first weighted loss, the first step's routing, the logits
of the tokens whose routing cannot flip), the three batch arrays through
``TrainStep``, the five remat boundaries, the three new per-layer metrics in
the traced line; the three readers with and without what they read; the
configuration's counts from its shapes alone; the reference alone at
thumbnail size.  The overlay is this file's own."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx

from bench_dry import check_line, harness

CELL = "sdar-30b-a3b-spmd-bd4-seq8192-bs1"
METRICS = {"attention_tiles_skipped_pct": "%",
           "diffusion_masked_token_pct": "%",
           "attention_kernel_roofline_pct": "%"}
# the five layers at thumbnail widths: 4 query heads over 2 key/value heads
# of 16, 4 of 16 routed experts held, top-3, blocks of 4, 40 tokens (80
# positions: not a multiple of the kernels' tile); one batch repeated so
# that the thumbnail learns it
DRY = {"config": {"hidden_size": 64, "head_dim": 16,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "moe_intermediate_size": 32, "num_experts": 4,
                  "published": {"num_experts": 16},
                  "first_routed_expert": 4, "num_experts_per_tok": 3,
                  "expert_tile_rows": 4, "vocab_size": 64,
                  "mask_token_id": 63, "num_classes": 64, "image": [40]},
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


def _clear_tiles():
    """The gauge holds what the process's last traced calls left, other
    test files' among them: the reader sums over the masks' kinds."""
    from mxnet_tpu import telemetry
    for mask in ("none", "causal", "block_causal", "block_diffusion"):
        telemetry.record_flash_attention_tiles(
            mask, {"empty": 0, "partial": 0, "full": 0})


def _drive(trace, **job):
    _clear_tiles()
    C, run = harness()
    cell = C.Cell(CELL)
    dry = {"config": DRY["config"], "job": dict(DRY["job"], **job)}
    return cell, run.run_cell(cell, seed=3_000_000_019, seconds=1.2,
                              trace=trace, devices=jax.devices()[:1],
                              ctx=mx.cpu(), dry=dry)


@pytest.mark.parametrize("trace", [0, 1])
def test_sdar_cell_dry_drive(trace, capsys):
    cell, result = _drive(trace)
    result = check_line(cell, result, trace)
    out = capsys.readouterr().out
    assert "remat boundaries in the step program: 5 of 5 layers" in out
    assert "step_engaged=ok" in out and "logits=ok" in out
    assert "first_loss=ok" in out and "expert_load=ok" in out
    assert "over the 100.0% of tokens in whose block" in out
    # 80 positions are one tile of 128: nothing to skip at this size
    assert "under block_diffusion: empty 0, partial 1, full 0" in out
    got = result["metrics"]
    if not trace:
        assert set(got) == {"setup_s", "images_per_s"}
        return
    # a CPU trace has no device plane: the counts are what it can give
    assert got["compiles_in_window"]["value"] == 0
    assert "attention_kernel_roofline_pct" not in got
    assert got["attention_tiles_skipped_pct"] == {"value": 0.0, "unit": "%"}
    # the one pool batch's share of masked positions, as the driver drew it
    C, _run = harness()
    driver = C.Cell(CELL).driver_module()
    x0 = driver.base.token_pool(3_000_000_019, 1, 2, 40, 63, 1.0)
    _xt, w = driver.noised_pool(3_000_000_019, x0, 4, 63, 0.001)
    assert got["diffusion_masked_token_pct"]["value"] == pytest.approx(
        100 * (w > 0).mean())


def test_a_limit_the_routing_breaks_fails_its_check(capsys):
    tol = dict(DRY["job"]["tolerances"], expert_load_rel=-1.0)
    _cell, result = _drive(0, tolerances=tol)
    out = capsys.readouterr().out
    assert "expert_load=FAILED" in out and "logits=ok" in out
    assert result["correct"] is False


def _reader(metric):
    C, _run = harness()
    return C.Cell(CELL).reader(metric)


def test_the_tile_reader_reads_the_gauge_and_nothing_else(monkeypatch):
    from mxnet_tpu import telemetry
    read = _reader("attention_tiles_skipped_pct")
    _clear_tiles()                                 # what earlier tests left
    assert read({}) is None                        # no call was traced
    telemetry.record_flash_attention_tiles(
        "block_diffusion", {"empty": 736, "partial": 48, "full": 240})
    assert read({}) == 100 * 736 / 1024
    monkeypatch.setattr(telemetry.REGISTRY, "get", lambda name: None)
    assert read({}) is None                        # a program without it


def test_the_masked_share_reader_reads_the_counters_in_the_span():
    from mxnet_tpu import telemetry
    read = _reader("diffusion_masked_token_pct")
    data = {"trace": {"steps": 2}, "cell": {"steps_per_sync": 1}}
    telemetry.enable()
    try:
        for weights in (np.array([0.0, 2.0, 0.0, 4.0]),
                        np.array([1.0, 2.0, 0.0, 4.0])):
            telemetry.next_step()
            with telemetry.span("spmd/step/shard_batch"):
                telemetry.record_loss_weights(weights)
        assert read(data) == 100 * 5 / 8
        telemetry.next_step()
        with telemetry.span("spmd/step/shard_batch"):
            pass                                   # a step of two arrays
        assert read(dict(data, trace={"steps": 1})) is None
    finally:
        telemetry.disable()
    assert read({"trace": {"steps": 0}, "cell": {"steps_per_sync": 1}}) \
        is None


def test_the_roofline_reader_counts_the_work_from_the_shapes():
    """197 TFLOP/s: the step's 16.5 TFLOP of attention take 83.7 ms at
    the least; kernels that took 0.5 s a step ran at 16.7 % of that; no
    kernel in the trace, no peak (a dry drive) or no step: nothing."""
    C, _run = harness()
    read = _reader("attention_kernel_roofline_pct")
    mod, cfg = C.Cell(CELL).config_module(), C.Cell(CELL).config
    flops = mod.attention_kernel_flops(cfg)
    assert flops == 12 * (8192 ** 2 + 8192 * 4) * 128 * 32 * 5
    # compute bounds it: the bytes would take 11 ms of the 84
    assert mod.attention_kernel_bytes(cfg) / 819e9 < 0.15 * flops / 197e12
    ops = [["_mx_flash_attention_fwd.2_custom-call_f32_32_16384_128_", 0.8],
           ["_fusion.12_fusion_f32_16384_2048_", 0.7],
           ["_mx_flash_attention_bwd_dkv.1_custom-call_f32_4_16384_128_",
            0.2]]
    data = {"trace": {"steps": 2, "device_ops": ops},
            "cell": {"name": CELL, "peak_flops": 197e12}}
    assert read(data) == pytest.approx(100 * flops / 197e12 / 0.5)
    assert 16 < read(data) < 17
    assert read(dict(data, trace={"steps": 2, "device_ops": ops[1:2]})) \
        is None
    assert read(dict(data, cell={"name": CELL, "peak_flops": None})) is None
    assert read(dict(data, trace={"steps": 0, "device_ops": []})) is None


def test_published_widths_give_the_issue_counts():
    C, _run = harness()
    cell = C.Cell(CELL)
    cfg, mod = cell.config, cell.config_module()
    shapes = mod.param_shapes(cfg, "gluon")
    count = {k: int(np.prod(s)) for k, s in shapes.items()}
    assert sorted(set(shapes) - set(mod.trained(shapes))) == [
        "expert_load", "expert_rows"]
    assert shapes["expert_load"] == (5, 16)
    part = lambda at: sum(count[k] for k in mod.trained(shapes)  # noqa: E731
                          if k.startswith(at))
    # the issue's arithmetic: a layer outside its experts 19,140,864, one
    # expert 4,718,592, sixteen held; five of the stage's six layers fit
    experts = sum(count[f"layers.0.moe.{w}"] for w in ("w1", "w3", "w2"))
    assert experts == 16 * 4_718_592 == 75_497_472
    assert part("layers.0.") - experts == 19_140_864
    assert part("layers.4.") == 94_638_336 and "layers.5.attn.q" not in shapes
    assert count["embed"] + count["head"] + count["final_norm"] == 77_793_280
    assert sum(count[k] for k in mod.trained(shapes)) == 550_984_960
    # every published width, the 32/4 heads and the router's 128
    assert shapes["layers.0.attn.q"] == (32 * 128, 2048)
    assert shapes["layers.0.attn.k"] == (4 * 128, 2048)
    assert shapes["layers.0.attn.q_norm"] == (128,)
    assert shapes["layers.0.moe.router"] == (128, 2048)
    assert shapes["layers.0.moe.w1"] == (16, 768, 2048)
    assert shapes["head"] == (18992, 2048)
    published = {k: v for k, v in cfg.items() if k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "moe_intermediate_size",
        "num_experts_per_tok", "intermediate_size", "rope_theta")}
    assert published == {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "intermediate_size": 6144,
        "rope_theta": 1000000}
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    # a sequence's multiply-accumulates: both halves' projections and ONE
    # held expert a position, the pairs the mask allows, the head over T
    macs = mod.macs_per_image(cfg, "gluon")
    t = 8192
    layer = 2 * t * (19_140_864 - 4096 - 256 + 4_718_592) \
        + 2 * (t * t + 4 * t) * 32 * 128
    assert macs == 5 * layer + t * 18992 * 2048
    assert 5.0e12 < macs < 5.05e12 and 30.1e12 < 6 * macs < 30.2e12
    # attention is 58 % of a layer's products; all (2T)^2 pairs would
    # count four times its work
    assert 0.58 < 2 * (t * t + 4 * t) * 32 * 128 / layer < 0.59
    assert mod.allowed_pairs(cfg) * 4 == pytest.approx((2 * t) ** 2, rel=1e-3)


def test_reference_runs_at_thumbnail():
    """The reference alone, from the shapes: finite logits of the right
    shape, the noisy half's, and a weighted loss near ln(vocab) x the mean
    weight at small random weights; the denoising forward too."""
    C, _run = harness()
    cell = C.Cell(CELL)
    mod = cell.config_module()
    cfg = dict(cell.config, **DRY["config"])
    rng = np.random.default_rng(0)
    params = {k: (np.ones(s) if k.endswith("norm")
                  else rng.standard_normal(s) * 0.1).astype(np.float32)
              for k, s in mod.param_shapes(cfg, "gluon").items()}
    ids = rng.integers(0, 63, (2, 80)).astype(np.int32)
    weights = rng.random((2, 40)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(mod.reference(cfg, "gluon")(params, ids))
        loss = float(mod.loss(cfg, "gluon")(params, ids, ids[:, :40],
                                            weights))
        block = np.asarray(mod.denoise(cfg, "gluon")(params, ids[:, :12]))
    assert logits.shape == (2, 40, 64) and block.shape == (2, 12, 64)
    assert np.isfinite(logits).all() and np.abs(logits).max() > 0
    assert abs(loss / weights.mean() - np.log(64)) < 1.0
