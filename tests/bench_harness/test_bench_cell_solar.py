"""The token cell ``solar-open2-spmd-seq8192-bs1`` dry-driven on the CPU
through ``run.run_cell``, untraced and traced: the driver's reference
checks (whole logits and first loss against the plain reference, after the
window: first loss, the first step's routing, the logits of the tokens
whose routing cannot flip), the per-layer remat boundaries, the expert
layer's gauges and the line's contract, at thumbnail size; the configuration's counts
from its shapes alone; the reference alone at thumbnail size.  The overlay
is this file's own."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx

from bench_dry import check_line, harness

CELL = "solar-open2-spmd-seq8192-bs1"
GAUGES = ("mxnet_moe_assignments_held", "mxnet_moe_rows_computed",
          "mxnet_moe_expert_load_max_over_mean")
# both kinds of mixer (layer 0 softmax, 1-3 KDA), grouped heads, 4 of 16
# routed experts held, top-2, a length that is not a multiple of the chunk
# nor of the expert tile; one batch repeated so that the thumbnail learns it
# (at 0.1 from the first step: at 0.5 its loss rises for some fifteen steps,
# and a loaded machine makes no more in the window)
DRY = {"config": {"hidden_size": 64, "head_dim": 16,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "linear_attn_config": {"short_conv_kernel_size": 4,
                                         "head_dim": 16, "num_heads": 4,
                                         "num_kv_heads": None},
                  "moe_intermediate_size": 32, "n_routed_experts": 4,
                  "published": {"n_routed_experts": 16},
                  "first_routed_expert": 4, "num_experts_per_tok": 2,
                  "kda_chunk_size": 8, "kda_low_rank_dim": 16,
                  "expert_tile_rows": 4, "vocab_size": 64,
                  "num_classes": 64, "image": [30]},
       "job": {"batch": 2, "trace_seconds": 0.6, "pool_batches": 1,
               "optimizer_params": {"learning_rate": 0.1, "momentum": 0.9},
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
    return cell, run.run_cell(cell, seed=5, seconds=1.2, trace=trace,
                              devices=jax.devices()[:1], ctx=mx.cpu(),
                              dry=dry)


@pytest.mark.parametrize("trace", [0, 1])
def test_solar_cell_dry_drive(trace, capsys):
    cell, result = _drive(trace)
    result = check_line(cell, result, trace)
    out = capsys.readouterr().out
    assert "remat boundaries in the step program: 4 of 4 layers" in out
    assert "step_engaged=ok" in out and "logits=ok" in out
    assert "first_loss=ok" in out and "expert_load=ok" in out
    assert "over the 100.0% of tokens" in out
    got = result["metrics"]
    if not trace:
        assert set(got) == {"setup_s", "images_per_s"}
        return
    # a CPU trace has no device plane: the counts are what it can give
    assert got["compiles_in_window"]["value"] == 0
    assert got["setup_backend_compiles"]["value"] > 0
    assert "step_ms_p95" not in got


def _gauges():
    from mxnet_tpu import telemetry
    return {k: telemetry.REGISTRY.get(k).value() for k in GAUGES}


def test_expert_layer_gauges_have_numbers_that_repeat():
    """The gauges are means over the steps the driver made, and a window
    lasts a time, not a number of steps.  With the learning rate at 0 the
    parameters stay where the seed put them and every step routes alike
    (the loss then does not fall, so the line is not ``correct`` and is
    not checked as one): two drives read the same three numbers.  The
    assignments lie under 4 layers x 58 tokens x top-2, and padding is
    what tiles of 4 rows leave."""
    still = {"learning_rate": 0.0, "momentum": 0.9}
    runs = []
    for _ in range(2):
        _drive(0, optimizer_params=still)
        runs.append(_gauges())
    assert runs[0] == runs[1]
    held, rows, skew = (runs[0][k] for k in GAUGES)
    assert 0 < held <= 4 * 58 * 2 and held == int(held)
    assert held <= rows < held + 4 * 4 * 4 and rows % 4 == 0
    assert skew >= 1


def test_a_limit_the_routing_breaks_fails_its_check(capsys):
    """An expert-load limit that no count can meet fails the line by
    that check alone."""
    tol = dict(DRY["job"]["tolerances"], expert_load_rel=-1.0)
    _cell, result = _drive(0, tolerances=tol)
    out = capsys.readouterr().out
    assert "expert_load=FAILED" in out and "logits=ok" in out
    assert result["correct"] is False


def test_the_two_logits_numbers_tell_a_tail_from_the_bulk():
    """Two hundred tokens, each off by 1 % of its own norm, and four of
    them besides by a tenth: the median token's error stays at 1 %, the
    99th percentile rises to eleven times it; leaving those four out
    brings it back to 1.  A logit that is not finite gives no reading."""
    C, _run = harness()
    mod = C.Cell(CELL).driver_module()
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((1, 200, 50)).astype(np.float32)
    got = ref * np.float32(1.01)
    got[0, :4] += 0.1 * ref[0, :4]
    errors = mod.token_errors(got, ref)
    peak = float(np.abs(ref).max())
    every = np.ones((1, 200), bool)
    mid, tail, rms, worst = mod.readings(errors, peak, every)
    assert mid == pytest.approx(0.01, rel=1e-3)
    assert tail / mid == pytest.approx(11, rel=1e-2)
    assert rms > 1.5 * mid and worst > 0.05
    keep = every.copy()
    keep[0, :4] = False
    mid, tail, rms, worst = mod.readings(errors, peak, keep)
    assert tail / mid == pytest.approx(1.0, rel=1e-3)
    assert rms == pytest.approx(0.01, rel=1e-3) and worst <= 0.0101
    got[0, 0, 0] = np.inf
    assert mod.token_errors(got, ref) is None


def test_published_widths_give_the_issue_counts():
    C, _run = harness()
    cell = C.Cell(CELL)
    cfg, mod = cell.config, cell.config_module()
    shapes = mod.param_shapes(cfg, "gluon")
    count = {k: int(np.prod(s)) for k, s in shapes.items()}
    aux = count.pop("expert_load") + count.pop("expert_rows")
    assert aux == 4 * 8 + 4
    # the issue's arithmetic: a KDA layer's mixer 18.1 M, the softmax
    # layer's 13.6 M, a router 1.3 M, eight experts 125.8 M, the shared
    # expert 15.7 M, embedding and head 201.3 M: about 841 M
    part = lambda at: sum(v for k, v in count.items()  # noqa: E731
                          if k.startswith(at))
    assert part("layers.1.kda.") == 18_137_224
    assert part("layers.0.attn.") == 13_631_488
    assert count["layers.2.moe.router"] == 320 * 4096
    assert 3 * count["layers.2.moe.w1"] == 125_829_120
    assert part("layers.2.moe.shared") == 15_728_640
    assert count["embed"] + count["head"] == 201_326_592
    assert sum(count.values()) == 840_880_536
    # every published width is kept
    assert shapes["layers.0.moe.w1"] == (8, 1280, 4096)
    assert shapes["layers.1.kda.q"] == (8 * 128, 4096)
    assert shapes["layers.1.kda.q_conv_w"] == (8 * 128, 4)
    # 2.1 TMAC a sequence forward, 12.8 TFLOP a step
    macs = mod.macs_per_image(cfg, "gluon")
    assert 2.0e12 < macs < 2.2e12
    assert 12.3e12 < 6 * macs < 13.2e12
    # the routed experts count at the expected 0.2 assignments a token
    dense = mod.macs_per_image(
        dict(cfg, published={"n_routed_experts": 8}), "gluon")
    assert dense - macs == pytest.approx(
        8192 * 4 * (8 - 0.2) * 3 * 1280 * 4096
        - 8192 * 4 * 312 * 4096, rel=1e-9)


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
        logits = np.asarray(mod.reference(cfg, "gluon")(params, ids))
        loss = float(mod.loss(cfg, "gluon")(params, ids, ids))
    assert logits.shape == (2, 29, 64)
    assert np.isfinite(logits).all() and np.abs(logits).max() > 0
    assert abs(loss - np.log(64)) < 1.0
