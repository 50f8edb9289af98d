"""The token cell ``granite4h-spmd-seq4096-bs1`` dry-driven on the CPU
through ``run.run_cell``, untraced and traced: the driver's own reference
checks (whole logits and first loss against the plain reference), the
per-layer remat boundaries and the line's contract, at thumbnail size.
The overlay is this file's own: ``bench_dry.DRY_CONFIG`` is the image
cells'."""
import jax
import pytest

import mxnet_tpu as mx

from bench_dry import check_line, harness

CELL = "granite4h-spmd-seq4096-bs1"
# both kinds of layer, grouped heads, a length that is not a multiple of
# the chunk; one batch repeated so that the thumbnail learns it at once
DRY = {"config": {"hidden_size": 64, "shared_intermediate_size": 128,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "attention_multiplier": 0.25, "mamba_n_heads": 4,
                  "mamba_d_head": 16, "mamba_d_state": 16,
                  "mamba_chunk_size": 8, "num_hidden_layers": 5,
                  "layer_types": ["mamba", "mamba", "attention", "mamba",
                                  "mamba"],
                  "vocab_size": 64, "num_classes": 64, "image": [29]},
       "job": {"batch": 2, "trace_seconds": 0.6, "pool_batches": 1,
               "optimizer_params": {"learning_rate": 0.5, "momentum": 0.9}}}


@pytest.mark.parametrize("trace", [0, 1])
def test_granite_cell_dry_drive(trace, capsys):
    C, run = harness()
    cell = C.Cell(CELL)
    result = run.run_cell(cell, seed=5, seconds=1.2, trace=trace,
                          devices=jax.devices()[:1], ctx=mx.cpu(), dry=DRY)
    result = check_line(cell, result, trace)
    out = capsys.readouterr().out
    assert "remat boundaries in the step program: 5 of 5 layers" in out
    assert "step_engaged=ok" in out and "logits=ok" in out
    if trace:
        got = result["metrics"]
        # a CPU trace has no device plane: the counts are what it can give
        assert got["compiles_in_window"]["value"] == 0
        assert got["setup_backend_compiles"]["value"] > 0
        assert "step_ms_p95" not in got
    else:
        assert set(result["metrics"]) == {"setup_s", "images_per_s"}
