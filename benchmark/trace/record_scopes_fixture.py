#!/usr/bin/env python3
"""How ``scopes_fixture_1chip.xplane.pb`` beside this file was recorded: a
few steps of the program's own ``parallel.spmd.TrainStep`` (``remat``
on, so the backward re-runs the forward) over a toy block on one chip: a
convolution, a BatchNorm and a max pool through the registered operators
(``op/Convolution``, ``op/BatchNorm``, ``op/Pooling``), then four dense
layers under the scopes ``toy/mamba/ssd``, ``toy/attention``,
``toy/moe/experts`` and ``toy/head``, the loss under ``step/loss`` and
momentum SGD under ``step/optimizer``, with the benchmark's
``bench/sync`` annotation after every step.

    python3 benchmark/trace/record_scopes_fixture.py <out-dir>

Run on the chip, from an empty compile cache (an executable compiled by a
tree from before the scopes carries none).  The numbers the tests hold
the readers to are worked out from the file by the tests' own arithmetic
(``tests/bench_harness/test_bench_scopes.py``), not by ``scopes.py``.
The file is not called ``fixture_*``: ``test_bench_trace.py`` takes every
file of that name for ``reduce.py``'s and wants its numbers in
``fixture_expected.json``.
"""
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out_dir, steps=4):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import TrainStep

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("record_scopes_fixture: needs a TPU", file=sys.stderr)
        return 1

    class Toy(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.conv = nn.Conv2D(32, 3, padding=1, use_bias=False)
                self.bn = nn.BatchNorm()
                self.pool = nn.MaxPool2D(2)
                self.ssd = nn.Dense(512)
                self.mix = nn.Dense(512)
                self.experts = nn.Dense(512)
                self.head = nn.Dense(16)

        def hybrid_forward(self, F, x):
            h = self.pool(F.Activation(self.bn(self.conv(x)),
                                       act_type="relu"))
            with jax.named_scope("toy/mamba/ssd"):
                h = F.tanh(self.ssd(h))
            with jax.named_scope("toy/attention"):
                h = F.tanh(self.mix(h))
            with jax.named_scope("toy/moe/experts"):
                h = F.tanh(self.experts(h))
            with jax.named_scope("toy/head"):
                return self.head(h)

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    x = rng.randn(64, 3, 64, 64).astype(np.float32)
    y = rng.randint(0, 16, size=(64,)).astype(np.float32)
    net = Toy()
    net.initialize(mx.initializer.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.01, "momentum": 0.9},
                     make_mesh(devices=devs[:1], dp=1),
                     example_batch=(mx.nd.array(x), mx.nd.array(y)),
                     remat=True)
    float(step(x, y))
    tmp = tempfile.mkdtemp(prefix="fixture-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/sync"):
        pass
    for _ in range(steps):
        with jax.profiler.TraceAnnotation("bench/step_call"):
            loss = step(x, y)
        float(loss)
        with jax.profiler.TraceAnnotation("bench/sync"):
            pass
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "scopes_fixture_1chip.xplane.pb")
    shutil.copy(files[0], dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "."))
