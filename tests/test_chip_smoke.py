"""chip_smoke.py and the no-silent-fallback rules around it, on the CPU.

Tier-1 part (a few seconds): the smoke refuses to run without a TPU,
the one table of peaks refuses a device it does not know,
``mx.tpu(0)`` raises where there is no chip, the compile
cache resolves to the directory placed from outside.  The ``slow`` part
is the dry drive the on-chip-measurement guide asks for before chip time
is spent: the smoke's legs, imported as functions and given ``mx.cpu()``,
a thumbnail ResNet and interpreter kernels, end to end —

    JAX_PLATFORMS=cpu python -m pytest tests/test_chip_smoke.py -m slow
"""
import importlib.util
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *where):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, *where, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one host device: fastest start
    done = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=env,
        cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "platform 'cpu'" in done.stderr, done.stderr[-500:]
    # names what it found, prints no result line
    assert "platform=cpu" in done.stdout and '"ok"' not in done.stdout


def test_the_peak_table_refuses_an_unknown_device_kind():
    """chip_smoke.py's clock leg and the benchmark's MFU read one table."""
    benchcore = _load("benchcore", "benchmark", "harness")
    assert benchcore.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(benchcore.BenchFailure, match="TPU v9000"):
        benchcore.peak_flops("TPU v9000")


def test_tpu_context_raises_without_a_chip():
    with pytest.raises(MXNetError, match="no accelerator device"):
        mx.tpu(0).jax_device
    with pytest.raises(MXNetError, match="no accelerator device"):
        mx.nd.zeros((2,), ctx=mx.gpu(0))
    assert mx.context.num_tpus() == 0 == mx.context.num_gpus()
    # a Module told to train there fails at bind, it does not train on
    # the host instead
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(data, num_hidden=2),
                               name="softmax")
    mod = mx.mod.Module(net, context=mx.tpu(0))
    with pytest.raises(MXNetError, match="no accelerator device"):
        mod.bind(data_shapes=[("data", (2, 4))],
                 label_shapes=[("softmax_label", (2,))])


def test_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    from mxnet_tpu.compile import cache
    outside = str(tmp_path / "outside" / "")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    # exactly as given: no version sub-directory, never ours to move
    assert cache.cache_dir() == outside
    assert not cache._owned(outside)
    assert cache.cache_root() != outside
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # the hermetic knob the CPU tests run under keeps its versioned meaning
    knob = os.environ["MXNET_COMPILE_CACHE_DIR"]
    assert cache.cache_dir() == os.path.join(knob, cache.version_key())
    monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR")
    assert cache.cache_dir() == os.path.join(_REPO, ".jax_cache")
    assert cache.cache_root() == os.path.join(_REPO, ".jax_cache")
    assert not cache._owned(cache.cache_dir())


@pytest.mark.slow
def test_dry_drive_on_cpu(capsys):
    import jax

    from mxnet_tpu.symbol.resnet import resnet_v1
    smoke = _load("chip_smoke")
    ctx = mx.cpu()
    net = resnet_v1(units=(1, 1), filters=(8, 16), num_classes=4,
                    thumbnail=True)
    fused = smoke.leg_train(ctx, net, (2, 3, 8, 8), 4, steps=3)
    scan = smoke.leg_train(ctx, net, (2, 3, 8, 8), 4, steps=6, scan_steps=2)
    # same seed, same batch: the window is the fused step scanned
    assert scan["losses"][:3] == pytest.approx(fused["losses"], rel=1e-5)
    assert smoke.leg_agree(ctx, net, fused["arg_params"],
                           fused["aux_params"], (2, 3, 8, 8)) == 0.0
    smoke.leg_clock(jax.devices()[0], peak_flops=1e18, n=512, reps=8,
                    min_share=0.0)
    with pytest.raises(smoke.SmokeFailure, match="above the table peak"):
        smoke.leg_clock(jax.devices()[0], peak_flops=1.0, n=512, reps=8,
                        min_share=0.0)
    smoke.leg_kernels(ctx, (
        ("LayerNorm", "layernorm", (20, 128), "float32"),
        ("softmax_cross_entropy", "softmax_ce", (20, 16), "float32"),
        ("_contrib_flash_attention", "attention", (1, 1, 128, 8), "float32"),
    ))
    out = capsys.readouterr().out
    assert "interpreter" in out and "Mosaic" not in out
    # four "chips": the spmd leg on the virtual CPU mesh
    devs = jax.devices()[:4]
    smoke.leg_spmd(devs, "resnet18_v1", (8, 3, 16, 16), 4, steps=3)
