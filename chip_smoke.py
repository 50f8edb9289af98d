#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still starts on
the chip.  One process, no child processes, no network, no CPU mode.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one host with four (fails on fewer)

On one chip it (1) names the device, (2) trains ResNet-50 v1 at
3x224x224, batch 32, through ``Module(..., context=mx.tpu(0)).fit`` with
no environment variable set (``FusedTrainStep``) and once more with the
K=8 scanned window (``ScanTrainStep``), (3) compares one float32 forward
on ``mx.tpu(0)`` with ``mx.cpu()``, (4) checks the host clock around
``block_until_ready`` against the chip's table peak with a chained bf16
matmul, and (5) compiles the three Pallas kernels under Mosaic and
compares forward and backward with their plain-XLA references.  With
``--chips 4`` it trains ResNet-50 at global batch 128 through
``parallel.spmd.TrainStep`` on ``make_mesh(dp=4)`` and compares the
losses with the same step on one chip.

Any failed check or exception is a non-zero exit with no result line.
On success the last line of stdout is one JSON object naming the device
as jax reports it.  The legs are plain functions of a context so that
``tests/test_chip_smoke.py`` can dry-drive them on ``mx.cpu()`` at a
thumbnail size; ``main`` itself has no such mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# |tpu - cpu| logits, relative to max|cpu logit|.  Reason: the TPU's
# default matmul/conv precision multiplies float32 operands as bfloat16
# (8-bit mantissa, ~2e-3 per product, float32 accumulation) and the
# error compounds over ResNet-50's 53 convolutions; the host multiplies
# in float32.
AGREE_REL_TOL = 5e-2

# spmd losses, four chips against one on the same seed: same program,
# but the batch reductions (BatchNorm statistics, the loss mean, the
# gradient sum) are split over devices and so re-associated.
SPMD_LOSS_REL_TOL = 2e-2

KERNEL_CASES = (
    ("LayerNorm", "layernorm", (4096, 1024), "bfloat16"),
    ("softmax_cross_entropy", "softmax_ce", (128, 1000), "float32"),
    ("softmax_cross_entropy", "softmax_ce", (32, 1000), "float32"),
    ("_contrib_flash_attention", "attention", (2, 8, 1024, 128), "bfloat16"),
)


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


def _backend_compiles():
    from mxnet_tpu import compile as mxc
    return mxc.LEDGER.counts()["jax"].get("backend_compiles", 0)


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return stats["peak_bytes_in_use"] if stats else None


def _require_on(dev, what, buf):
    require(set(buf.devices()) == {dev},
            f"{what} lives on {sorted(map(str, buf.devices()))}, "
            f"expected {dev}")


# -- leg 2: Module.fit ---------------------------------------------------------
def leg_train(ctx, symbol, batch_shape, num_classes, steps, scan_steps=1):
    """``Module(symbol, context=ctx).fit`` for ``steps`` steps on one
    fixed synthetic batch repeated; ``scan_steps`` > 1 selects the
    K-step scanned window (MXNET_SCAN_STEPS, the only switch it has).
    Returns the loss trajectory, set-up and step seconds, and the
    trained parameters on the host."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.fused_step import FusedTrainStep, ScanTrainStep

    dev = ctx.jax_device
    K = max(1, scan_steps)
    require(steps % K == 0 and steps // K >= 3,
            "need at least three full windows: one compiles, two time")

    class LossTrace(mx.metric.EvalMetric):
        """Cross-entropy per update, with the host time and the compile
        count at which each batch's outputs reached the host."""

        def __init__(self):
            super().__init__("loss-trace")
            self.rows = []

        def update(self, labels, preds):
            host = [a.asnumpy() if hasattr(a, "asnumpy") else np.asarray(a)
                    for a in (labels[0], preds[0])]
            lab, prob = host[0].astype(np.int64), host[1]
            ce = float(-np.log(np.maximum(
                prob[np.arange(lab.size), lab], 1e-30)).mean())
            self.rows.append((time.perf_counter(), ce, _backend_compiles()))
            self.sum_metric += ce
            self.num_inst += 1

    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, batch_shape).astype(np.float32)
    y = rng.randint(0, num_classes, batch_shape[0]).astype(np.float32)
    train = mx.io.NDArrayIter(
        np.tile(x, (steps,) + (1,) * (x.ndim - 1)), np.tile(y, steps),
        batch_size=batch_shape[0], label_name="softmax_label")
    trace = LossTrace()
    mx.random.seed(0)
    mod = mx.mod.Module(symbol, context=ctx)
    prev = os.environ.get("MXNET_SCAN_STEPS")
    if K > 1:
        os.environ["MXNET_SCAN_STEPS"] = str(K)
    t0 = time.perf_counter()
    try:
        mod.fit(train, eval_metric=trace, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
                initializer=mx.initializer.Xavier(
                    rnd_type="gaussian", factor_type="in", magnitude=2))
    finally:
        if K > 1:
            if prev is None:
                del os.environ["MXNET_SCAN_STEPS"]
            else:
                os.environ["MXNET_SCAN_STEPS"] = prev
    jax.block_until_ready([a._data for a in mod._exec.arg_dict.values()])
    total_s = time.perf_counter() - t0

    rows = trace.rows
    require(len(rows) == steps, f"{len(rows)} metric updates, not {steps}")
    # the step object is the class this leg is about, and it ran every step
    if K > 1:
        require(type(mod._scan) is ScanTrainStep and not mod._scan_disabled,
                f"scanned window did not engage: {type(mod._scan).__name__}")
        require(mod._scan.windows == steps // K,
                f"{mod._scan.windows} windows, not {steps // K}")
    else:
        require(type(mod._fused) is FusedTrainStep,
                f"fused step did not engage: {type(mod._fused).__name__}")
        require(mod._fused.steps == steps,
                f"{mod._fused.steps} fused steps, not {steps}")
    # placement: parameters, inputs, aux, optimizer state, outputs
    for name, arr in {**mod._exec.arg_dict, **mod._exec.aux_dict}.items():
        _require_on(dev, name, arr._data)
    for leaf in jax.tree_util.tree_leaves(
            [mod._updater.states[i] for i in sorted(mod._updater.states)],
            is_leaf=lambda a: hasattr(a, "_data")):
        _require_on(dev, "optimizer state", leaf._data)
    for out in mod.get_outputs():
        _require_on(dev, "output", out._data)
    # losses
    losses = [r[1] for r in rows]
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall: first {losses[0]:.4f} last {losses[-1]:.4f}")
    # compile once: nothing compiles after the first window reached the host
    late = rows[-1][2] - rows[K - 1][2]
    require(late == 0, f"{late} backend compile(s) after the first "
            f"{'window' if K > 1 else 'step'}")
    # set-up (bind, init, compile, first window) apart from steady steps
    marks = [rows[i][0] for i in range(K - 1, steps, K)]
    setup_s = marks[0] - t0
    step_s = sorted(b - a for a, b in zip(marks, marks[1:]))[
        (len(marks) - 1) // 2] / K
    peak = _peak_bytes(dev)
    say(f"  {'scan K=%d' % K if K > 1 else 'fused'}: set-up {setup_s:.2f} s, "
        f"step {step_s * 1e3:.2f} ms (median of {len(marks) - 1} "
        f"{'windows' if K > 1 else 'steps'}, host clock, synced by the "
        f"metric's read), total {total_s:.2f} s")
    say(f"  loss {' '.join('%.4f' % v for v in losses)}")
    say(f"  peak_bytes_in_use {peak if peak is not None else 'not reported'}")
    arg_params, aux_params = mod.get_params()
    return {"losses": losses, "setup_s": setup_s, "step_s": step_s,
            "arg_params": arg_params, "aux_params": aux_params}


# -- leg 3: the chip agrees with the host --------------------------------------
def leg_agree(ctx, symbol, arg_params, aux_params, batch_shape,
              rel_tol=AGREE_REL_TOL):
    """One float32 inference forward of the same parameters on
    ``mx.cpu()`` and on ``ctx``; logits (``fc1_output``) must agree."""
    import numpy as np

    import mxnet_tpu as mx

    logits = symbol.get_internals()["fc1_output"]
    x = np.random.RandomState(1).uniform(-1, 1, batch_shape).astype(
        np.float32)

    def forward(where):
        mod = mx.mod.Module(logits, context=where, label_names=None)
        mod.bind(data_shapes=[("data", batch_shape)], for_training=False)
        mod.set_params(arg_params, aux_params)
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(x, ctx=where)]),
                    is_train=False)
        out = mod.get_outputs()[0]
        _require_on(where.jax_device, "logits", out._data)
        return out.asnumpy()

    ref, got = forward(mx.cpu()), forward(ctx)
    require(got.shape == ref.shape == (batch_shape[0], ref.shape[1]),
            f"logit shapes {got.shape} vs {ref.shape}")
    require(np.isfinite(got).all(), "non-finite logits")
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    say(f"  logits {ctx} vs cpu(0): max|diff| {err:.3e} over max|logit| "
        f"{scale:.3e} = {err / scale:.3e} (tolerance {rel_tol:g})")
    require(err <= rel_tol * scale,
            f"logits disagree: {err / scale:.3e} > {rel_tol:g}")
    return err / scale


# -- leg 4: the clock ----------------------------------------------------------
def leg_clock(dev, peak_flops, n=4096, reps=128, min_share=0.5):
    """A chained ``n``^3 bf16 matmul timed on the host around
    ``block_until_ready`` must imply a rate at or under the table peak
    and not a small fraction of it; the same chain ended by a 4-byte
    transfer says whether ``block_until_ready`` is a barrier."""
    import jax
    import jax.numpy as jnp

    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    # a ~ N(0, 1/n): b keeps unit scale down the chain, nothing overflows
    a = jax.device_put(
        (jax.random.normal(ka, (n, n)) / n ** 0.5).astype(jnp.bfloat16), dev)
    b = jax.device_put(
        jax.random.normal(kb, (n, n)).astype(jnp.bfloat16), dev)

    @jax.jit
    def chain(a, b):
        # b_{i+1} = a @ b_i: sequential dependence, nothing hoistable
        return jax.lax.fori_loop(0, reps, lambda _, b_: a @ b_, b)

    chain(a, b).block_until_ready()  # compile + warm

    def best_of(sync, tries=3):
        best = float("inf")
        for _ in range(tries):
            t0 = time.perf_counter()
            sync(chain(a, b))
            best = min(best, time.perf_counter() - t0)
        return best

    t_block = best_of(lambda out: out.block_until_ready())
    t_xfer = best_of(lambda out: float(out[0, 0]))
    rate = 2.0 * n ** 3 * reps / t_block
    # the transfer adds a slice dispatch and a copy: allow it 20 % (5 ms
    # at least — it dominates the tiny chain of the CPU dry drive)
    barrier = t_xfer - t_block <= max(0.2 * t_xfer, 5e-3)
    say(f"  {reps} chained {n}^3 bf16 matmuls: {t_block * 1e3:.2f} ms to "
        f"block_until_ready = {rate / 1e12:.1f} TFLOP/s "
        f"({rate / peak_flops:.1%} of the {peak_flops / 1e12:.0f} TFLOP/s "
        f"table peak); {t_xfer * 1e3:.2f} ms to a 4-byte transfer")
    say(f"  block_until_ready is a barrier on this machine: "
        f"{'yes' if barrier else 'NO'}")
    require(barrier, "block_until_ready returned before the device finished")
    require(rate <= peak_flops,
            f"{rate / 1e12:.1f} TFLOP/s is above the table peak: the clock "
            "does not wait for the device")
    require(rate >= min_share * peak_flops,
            f"{rate / 1e12:.1f} TFLOP/s is under {min_share:.0%} of the "
            "table peak")
    return rate


# -- leg 5: the kernels --------------------------------------------------------
def leg_kernels(ctx, cases=KERNEL_CASES):
    """Each Pallas kernel, called as an op through ``mx.nd`` on ``ctx``:
    compiled by Mosaic on a tpu device (``tpu_custom_call`` in the
    lowered program) and interpreted anywhere else; forward and the
    custom_vjp backward match the plain-XLA reference of its KernelSpec
    within the spec's tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.kernels.registry import get_spec
    from mxnet_tpu.ops import registry as op_registry

    dev = ctx.jax_device
    on_tpu = dev.platform == "tpu"
    for op_name, spec_name, shape, dtype in cases:
        spec = get_spec(spec_name)
        args, kwargs = spec.example_inputs(shape, jnp.dtype(dtype),
                                           np.random.RandomState(0))
        args = tuple(jax.device_put(a, dev) for a in args)
        attrs = dict(kwargs)
        lowered = jax.jit(
            lambda *a: op_registry.get(op_name).fcompute(attrs, *a)
        ).lower(*args).as_text()
        require(("tpu_custom_call" in lowered) == on_tpu,
                f"{op_name}{shape}: lowered for {dev.platform} "
                f"{'without' if on_tpu else 'with'} a tpu_custom_call")

        nds = [mx.nd.NDArray(a, ctx) for a in args]
        for i in spec.grad_argnums:
            nds[i].attach_grad()
        with mx.autograd.record():
            out = getattr(mx.nd, op_name)(*nds, **attrs)
        _require_on(dev, f"{op_name} output", out._data)
        ct = jax.device_put(jnp.asarray(
            np.random.RandomState(1).randn(*out.shape), out._data.dtype),
            dev)
        out.backward(mx.nd.NDArray(ct, ctx))

        def reference(*a):
            ref = spec.reference(*a, **kwargs)
            # the registered CE op totals the per-row losses
            return ref.sum() if out.shape != ref.shape else ref

        ref_out, vjp = jax.vjp(reference, *args)
        ref_grads = vjp(ct.astype(ref_out.dtype))
        rtol, atol = spec.tolerance(jnp.dtype(dtype))
        pairs = [("forward", out._data, ref_out)] + [
            (f"grad[{i}]", nds[i].grad._data, ref_grads[i])
            for i in spec.grad_argnums]
        worst = 0.0
        for what, got, ref in pairs:
            got = np.asarray(got, np.float32)
            ref = np.asarray(ref, np.float32)
            require(np.isfinite(got).all(), f"{op_name} {what}: non-finite")
            # the CE total sums n row losses: scale atol like its reference
            bound = atol * max(1.0, float(np.abs(ref).max())) \
                + rtol * np.abs(ref)
            excess = float((np.abs(got - ref) - bound).max())
            worst = max(worst, float(np.abs(got - ref).max()))
            require(excess <= 0, f"{op_name}{shape} {dtype} {what}: off its "
                    f"reference by {excess:.3e} beyond rtol {rtol:g} "
                    f"atol {atol:g}")
        say(f"  {op_name} {shape} {dtype}: "
            f"{'Mosaic (tpu_custom_call)' if on_tpu else 'interpreter'}, "
            f"forward + {len(spec.grad_argnums)} grads match the reference "
            f"(max|diff| {worst:.3e}, rtol {rtol:g} atol {atol:g})")


# -- four chips ----------------------------------------------------------------
def leg_spmd(devices, net_name, batch_shape, num_classes, steps,
             rel_tol=SPMD_LOSS_REL_TOL):
    """``parallel.spmd.TrainStep`` on ``make_mesh(dp=len(devices))`` —
    the path ``examples/train_imagenet.py --num-devices N`` takes — and
    the same step on the first device alone, same seed: the batch is
    sharded over distinct devices, each holds memory, losses agree."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import TrainStep, shard_batch

    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, batch_shape).astype(np.float32)
    y = rng.randint(0, num_classes, batch_shape[0]).astype(np.float32)

    def run(devs):
        mx.random.seed(0)
        np.random.seed(0)
        net = vision.get_model(net_name, classes=num_classes)
        net.initialize(mx.initializer.Xavier(magnitude=2.0))
        mesh = make_mesh(devices=list(devs), dp=len(devs))
        t0 = time.perf_counter()
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.01, "momentum": 0.9}, mesh,
                         example_batch=(mx.nd.array(x), mx.nd.array(y)))
        xs = shard_batch(mesh, x)
        shards = {s.device: s.data.shape for s in xs.addressable_shards}
        require(set(shards) == set(devs) and all(
            shp[0] == batch_shape[0] // len(devs) for shp in shards.values()),
            f"batch not sharded evenly over {len(devs)} devices: {shards}")
        losses, marks = [], []
        for _ in range(steps):
            losses.append(float(step(x, y)))
            marks.append(time.perf_counter())
        require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        for p in step.params:
            require(set(p.devices()) == set(devs),
                    f"parameter on {p.devices()}, expected {devs}")
        say(f"  dp={len(devs)}: set-up {marks[0] - t0:.2f} s, later steps "
            f"{(marks[-1] - marks[0]) / (steps - 1) * 1e3:.2f} ms each "
            f"(host clock, synced by the loss read); loss "
            f"{' '.join('%.4f' % v for v in losses)}")
        return losses

    one = run(devices[:1])
    many = run(devices)
    for d in devices:
        peak = _peak_bytes(d)
        say(f"  {d}: peak_bytes_in_use {peak}")
        require(peak or d.platform != "tpu",
                f"{d} reports no memory in use")
    worst = max(abs(a - b) / abs(a) for a, b in zip(one, many))
    say(f"  losses dp={len(devices)} vs one chip: max relative difference "
        f"{worst:.3e} (tolerance {rel_tol:g})")
    require(worst <= rel_tol, f"losses disagree: {worst:.3e} > {rel_tol:g}")
    require(many[-1] < many[0], "loss did not fall on the mesh")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # 1. name the device
    import jax
    devs = jax.devices()
    dev = devs[0]
    say(f"jax {jax.__version__}  platform={dev.platform}  "
        f"device_kind={dev.device_kind}  count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found platform "
              f"{dev.platform!r} ({dev.device_kind}); there is no CPU mode",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found {len(devs)}",
              file=sys.stderr)
        return 1
    # the one table of peaks is the benchmark's (peaks.json); the import
    # is one-way, benchmark/ imports nothing from here
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmark", "harness"))
    import benchcore
    try:
        peak_flops = benchcore.peak_flops(dev.device_kind)
    except benchcore.BenchFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1

    import mxnet_tpu as mx
    from mxnet_tpu import compile as mxc
    from mxnet_tpu.symbol.resnet import resnet_v1
    say(f"compile cache: {mxc.ensure_persistent_cache()}  "
        f"(jax.config.jax_compilation_cache_dir="
        f"{jax.config.jax_compilation_cache_dir}, JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', 'not set')})")

    if args.chips == 1:
        ctx = mx.tpu(0)
        require(ctx.jax_device == dev, f"mx.tpu(0) is {ctx.jax_device}")
        resnet50 = resnet_v1()
        say("[train] Module.fit ResNet-50 v1, 3x224x224, batch 32, SGD "
            "momentum, no environment variable set")
        fused = leg_train(ctx, resnet50, (32, 3, 224, 224), 1000, steps=6)
        say("[train] the same with the K=8 scanned window")
        leg_train(ctx, resnet50, (32, 3, 224, 224), 1000, steps=24,
                  scan_steps=8)
        say("[agree] one float32 forward at batch 8, chip against host")
        leg_agree(ctx, resnet50, fused["arg_params"], fused["aux_params"],
                  (8, 3, 224, 224))
        say("[clock] chained matmul against the table peak")
        leg_clock(dev, peak_flops)
        say("[kernels] LayerNorm, softmax_cross_entropy, flash attention")
        leg_kernels(ctx)
    else:
        say("[spmd] ResNet-50 v1, global batch 128, parallel.spmd.TrainStep "
            "on make_mesh(dp=4) against one chip")
        leg_spmd(devs[:4], "resnet50_v1", (128, 3, 224, 224), 1000, steps=4)

    jaxc = mxc.LEDGER.counts()["jax"]
    say(f"persistent cache: {jaxc.get('persistent_hits', 0)} hits, "
        f"{jaxc.get('persistent_misses', 0)} misses, "
        f"{jaxc.get('backend_compiles', 0)} backend compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
