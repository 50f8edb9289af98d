"""Each configuration's FLOPs function and layer shapes.  That each
plain reference agrees with the program (eval logits and first training
loss, at thumbnail width on the CPU) is part of every dry drive
(``test_bench_cell_*``: ``correct`` holds only if both do)."""
import numpy as np
import pytest

from bench_dry import DRY_CONFIG, harness

# multiply-accumulates of one 224x224 forward pass, convolutions and
# dense layers: ResNet-50 with the stride in the 3x3 (the "v1.5" of
# MXNet's example symbol) is the 4.09 G that torchvision documents, with
# it in the first 1x1 (He et al. as published) 3.86 G; MobileNetV2 is
# the paper's 300 M plus the zoo's 1x1 expansion at t=1 and a 1000-way
# classifier
EXPECTED = {("resnet50_v1", "symbol"): (4_089_184_256, 25_557_032),
            ("resnet50_v1", "zoo"): (3_857_973_248, 25_575_912),
            ("mobilenetv2_1.0", "zoo"): (313_619_328, 3_504_960)}


def _config(name):
    C, _run = harness()
    cell = next(C.Cell(w["name"]) for w in C.load_json(
        C.CHECKOUT + "/BENCHMARK.json")["workloads"] if w["config"] == name)
    return cell.config, cell.config_module()


@pytest.mark.parametrize("name,build", sorted(EXPECTED))
def test_macs_and_parameters_from_the_shapes(name, build):
    cfg, mod = _config(name)
    macs, params = EXPECTED[name, build]
    assert mod.macs_per_image(cfg, build) == macs
    shapes = mod.param_shapes(cfg, build)
    trainable = sum(int(np.prod(s)) for k, s in shapes.items()
                    if not k.endswith((".mean", ".var")))
    assert trainable == params
    # the issue's sanity range: outside it the function is wrong
    lo, hi = (3.8e9, 4.1e9) if name == "resnet50_v1" else (0.29e9, 0.33e9)
    assert lo <= macs <= hi


def test_resnet50_symbol_has_the_shapes_the_configuration_says():
    """The Symbol at its published size, by shape inference alone."""
    cfg, mod = _config("resnet50_v1")
    sym = mod.build(cfg, "symbol")
    arg_shapes, _out, aux_shapes = sym.infer_shape(
        data=(2, 3, 224, 224), softmax_label=(2,))
    theirs = dict(zip(sym.list_arguments(), arg_shapes))
    theirs.update(zip(sym.list_auxiliary_states(), aux_shapes))
    names = mod.canonical(cfg, "symbol")
    ours = mod.param_shapes(cfg, "symbol")
    assert {names[n]: tuple(s) for n, s in theirs.items()
            if n in names} == ours


@pytest.mark.parametrize("name,build", sorted(EXPECTED))
def test_reference_runs_at_thumbnail(name, build):
    """The reference alone, from the shapes: finite logits of the right
    shape in both modes, and batch statistics matter in train mode."""
    import benchref
    cfg, mod = _config(name)
    cfg = dict(cfg, **DRY_CONFIG[name])
    rng = np.random.default_rng(0)
    params = {k: (np.abs(rng.standard_normal(s)) + 0.5
                  if k.endswith((".var", ".gamma"))
                  else rng.standard_normal(s) * 0.1).astype(np.float32)
              for k, s in mod.param_shapes(cfg, build).items()}
    x = rng.standard_normal((4,) + tuple(cfg["image"])).astype(np.float32)
    forward = mod.reference(cfg, build)
    logits = np.asarray(benchref.run_reference(forward, params, x))
    assert logits.shape == (4, cfg["num_classes"])
    assert np.isfinite(logits).all() and np.abs(logits).max() > 0
    loss = float(benchref.run_reference(forward, params, x,
                                        labels=np.zeros(4, np.float32)))
    assert np.isfinite(loss) and loss > 0
