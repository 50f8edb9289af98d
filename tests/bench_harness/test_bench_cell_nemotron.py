"""The token cell ``nemotron3-nano-spmd-seq8192-bs1`` dry-driven on the CPU
through ``run.run_cell``, untraced and traced, as ``test_bench_cell_solar.py``
drives Solar's: the unedited ``spmd_lm_moe`` driver's reference checks
(first loss, the first step's routing over s + b, the logits of the tokens
whose routing cannot flip), AdamW's two slots, the nine remat boundaries
of the timed pattern, the expert layer's three per-layer metrics in the
traced line; the three readers on a registry with and without the gauges;
the configuration's counts from its shapes alone; the reference alone at
thumbnail size.  The overlay is this file's own."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx

from bench_dry import check_line, harness

CELL = "nemotron3-nano-spmd-seq8192-bs1"
METRICS = {"moe_rows_padding_pct": "%", "moe_load_max_over_mean": "ratio",
           "moe_assignments_per_step": "count"}
GAUGES = ("mxnet_moe_assignments_held", "mxnet_moe_rows_computed",
          "mxnet_moe_expert_load_max_over_mean")
# the timed pattern's nine layers at thumbnail widths: 4 Mamba-2 heads of
# 16 in 2 groups, 4 query heads over 2 key/value heads, 4 of 16 routed
# experts held, top-3, a length that is not a multiple of the chunk nor of
# the expert tile; one batch repeated so that the thumbnail learns it
DRY = {"config": {"hidden_size": 64, "head_dim": 16,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "mamba_num_heads": 4, "mamba_head_dim": 16,
                  "ssm_state_size": 16, "n_groups": 2, "chunk_size": 8,
                  "moe_intermediate_size": 32,
                  "moe_shared_expert_intermediate_size": 48,
                  "n_routed_experts": 4,
                  "published": {"n_routed_experts": 16},
                  "first_routed_expert": 4, "num_experts_per_tok": 3,
                  "expert_tile_rows": 4, "vocab_size": 64,
                  "num_classes": 64, "image": [30]},
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
    return cell, run.run_cell(cell, seed=5, seconds=1.2, trace=trace,
                              devices=jax.devices()[:1], ctx=mx.cpu(),
                              dry=dry)


def _gauges():
    from mxnet_tpu import telemetry
    return {k: telemetry.REGISTRY.get(k).value() for k in GAUGES}


@pytest.mark.parametrize("trace", [0, 1])
def test_nemotron_cell_dry_drive(trace, capsys):
    cell, result = _drive(trace)
    result = check_line(cell, result, trace)
    out = capsys.readouterr().out
    assert "remat boundaries in the step program: 9 of 9 layers" in out
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
    # the expert layer's three numbers are the gauges the driver set
    held, rows, skew = (_gauges()[k] for k in GAUGES)
    assert {k: got[k]["unit"] for k in METRICS} == METRICS
    assert got["moe_assignments_per_step"]["value"] == held
    assert got["moe_load_max_over_mean"]["value"] == skew >= 1
    assert got["moe_rows_padding_pct"]["value"] == pytest.approx(
        100 * (1 - held / rows))
    # 4 expert layers x 58 tokens x top-3 at most; tiles of 4 rows
    assert 0 < held <= 4 * 58 * 3 and held <= rows < held + 4 * 4 * 4


def test_a_limit_the_routing_breaks_fails_its_check(capsys):
    tol = dict(DRY["job"]["tolerances"], expert_load_rel=-1.0)
    _cell, result = _drive(0, tolerances=tol)
    out = capsys.readouterr().out
    assert "expert_load=FAILED" in out and "logits=ok" in out
    assert result["correct"] is False


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_readers_read_the_gauges_and_nothing_else(metric, monkeypatch):
    """What ``record_moe_load`` set is what the readers give; on a program
    from before the gauges (the registry has none of them) each returns
    None and none raises; no rows computed gives no padding share."""
    C, _run = harness()
    from mxnet_tpu import telemetry
    read = C.Cell(CELL).reader(metric)
    load = np.array([[3.0, 1.0, 0.0, 0.0], [2.0, 2.0, 2.0, 2.0]])
    telemetry.record_moe_load(load, np.array([8.0, 8.0]), steps=2)
    assert read({}) == {"moe_rows_padding_pct": 25.0,
                        "moe_load_max_over_mean": 3.0,
                        "moe_assignments_per_step": 6.0}[metric]
    telemetry.record_moe_load(0 * load, np.array([0.0, 0.0]))
    assert read({}) == {"moe_rows_padding_pct": None,
                        "moe_load_max_over_mean": 0.0,
                        "moe_assignments_per_step": 0.0}[metric]
    monkeypatch.setattr(telemetry.REGISTRY, "get", lambda name: None)
    assert read({}) is None


def test_published_widths_give_the_issue_counts():
    C, _run = harness()
    cell = C.Cell(CELL)
    cfg, mod = cell.config, cell.config_module()
    shapes = mod.param_shapes(cfg, "gluon")
    count = {k: int(np.prod(s)) for k, s in shapes.items()}
    # auxiliary state: the two counts and a bias of 128 an expert layer
    assert sorted(set(shapes) - set(mod.trained(shapes))) == sorted(
        ["expert_load", "expert_rows"]
        + [f"layers.{i}.moe.bias" for i in (1, 3, 6, 8)])
    assert count["expert_load"] == 4 * 8 and count["expert_rows"] == 4
    assert shapes["layers.1.moe.bias"] == (128,)
    part = lambda at: sum(count[k] for k in mod.trained(shapes)  # noqa: E731
                          if k.startswith(at))
    # the issue's table, with each layer's pre-norm of 2,688
    assert part("layers.0.") == 38_744_896          # a Mamba-2 layer
    assert part("layers.5.") == 23_399_040          # the attention layer
    assert part("layers.1.") == 100_125_312         # an expert layer
    assert count["embed"] + count["head"] + count["final_norm"] == 88_083_072
    assert sum(count[k] for k in mod.trained(shapes)) == 666_962_944
    # every published width, head count, group count and the router's 128
    assert shapes["layers.0.mamba.in_proj"] == (4096 + 6144 + 64, 2688)
    assert shapes["layers.0.mamba.conv_w"] == (4096 + 2 * 8 * 128, 4)
    assert shapes["layers.1.moe.router"] == (128, 2688)
    assert shapes["layers.1.moe.w1"] == (8, 1856, 2688)
    assert shapes["layers.1.moe.shared_in"] == (3712, 2688)
    assert shapes["layers.5.attn.q"] == (32 * 128, 2688)
    assert shapes["layers.5.attn.k"] == (2 * 128, 2688)
    assert mod._kinds(cfg) == ["mamba", "moe", "mamba", "moe", "mamba",
                               "attn", "moe", "mamba", "moe"]
    # about 352 M products a token before conv and scan, 17.3-17.7 TFLOP a
    # step
    macs = mod.macs_per_image(cfg, "gluon")
    assert 352e6 < macs / 8192 < 360e6
    assert 17.3e12 < 6 * macs < 17.7e12
    # the routed experts count at the expected 0.375 assignments a token
    dense = mod.macs_per_image(
        dict(cfg, published={"n_routed_experts": 8}), "gluon")
    assert dense - macs == pytest.approx(
        8192 * 4 * (6 - 0.375) * 2 * 1856 * 2688
        - 8192 * 4 * 120 * 2688, rel=1e-9)


def test_reference_runs_at_thumbnail():
    """The reference alone, from the shapes: finite logits of the right
    shape and a loss near ln(vocab) at small random weights."""
    C, _run = harness()
    cell = C.Cell(CELL)
    mod = cell.config_module()
    cfg = dict(cell.config, **DRY["config"])
    rng = np.random.default_rng(0)
    params = {k: (np.ones(s) if k.endswith("norm")
                  else rng.standard_normal(s) * 0.1).astype(np.float32)
              for k, s in mod.param_shapes(cfg, "gluon").items()}
    ids = rng.integers(0, 64, (2, 29)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(mod.reference(cfg, "gluon")(params, ids))
        loss = float(mod.loss(cfg, "gluon")(params, ids, ids))
    assert logits.shape == (2, 29, 64)
    assert np.isfinite(logits).all() and np.abs(logits).max() > 0
    assert abs(loss - np.log(64)) < 1.0
