"""``Module(symbol, context=ctx).fit``: the default fused step (a sync
every step) or, with ``MXNET_SCAN_STEPS=K`` in the job's environment, the
K-step scanned window (a sync every K steps).

``fit`` is called once.  The iterator hands out the seeded pool of host
batches in turn, fresh arrays every time as ``NDArrayIter`` does, and
ends the epoch at a window boundary once the recorder's time is up; the
metric is where every sync lands, as ``LossTrace`` in ``chip_smoke.py``.
"""
from __future__ import annotations

import os

import benchcore as C


def run(run):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.fused_step import FusedTrainStep, ScanTrainStep

    job, cfg, ctx, k = run.job, run.cfg, run.ctx, run.k
    want_k = int(os.environ.get("MXNET_SCAN_STEPS", "1") or 1)
    if want_k != k:
        raise C.BenchFailure(
            f"the job syncs every {k} steps but MXNET_SCAN_STEPS is "
            f"{want_k}")
    dev = ctx.jax_device
    xs, ys = run.pool
    rec = run.recorder()
    annotate = jax.profiler.TraceAnnotation

    class PoolIter(mx.io.DataIter):
        """The pool in turn, for ever; stops at a multiple of K batches
        so that no remainder falls back to another step."""

        def __init__(self):
            super().__init__(run.batch)
            self.provide_data = [mx.io.DataDesc(
                "data", (run.batch,) + run.image)]
            self.provide_label = [mx.io.DataDesc(
                "softmax_label", (run.batch,))]
            self.handed = 0

        def next(self):
            if self.handed % k == 0 and rec.done:
                raise StopIteration
            i = self.handed % len(xs)
            self.handed += 1
            with annotate("bench/next_batch"):
                return mx.io.DataBatch(
                    data=[mx.nd.array(xs[i], ctx=mx.cpu())],
                    label=[mx.nd.array(ys[i], ctx=mx.cpu())], pad=0)

    class SyncMetric(mx.metric.EvalMetric):
        """Cross-entropy per step; the read of the outputs is the sync."""

        def __init__(self):
            super().__init__("bench-loss")
            self.held = []

        def update(self, labels, preds):
            with annotate("bench/metric_read"):
                lab, prob = (a.asnumpy() if hasattr(a, "asnumpy")
                             else np.asarray(a)
                             for a in (labels[0], preds[0]))
            ce = C.cross_entropy(prob, lab)
            self.sum_metric += ce
            self.num_inst += 1
            self.held.append(ce)
            if len(self.held) == k:
                held, self.held = self.held, []
                rec.sync(held)

    symbol = run.cfgmod.build(cfg, job["build"])
    mx.random.seed(run.seed)
    np.random.seed(run.seed)
    init = mx.initializer.Xavier(rnd_type="gaussian", factor_type="in",
                                 magnitude=2)
    train = PoolIter()
    mod = mx.mod.Module(symbol, context=ctx)
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label, for_training=True)
    mod.init_params(initializer=init)
    arg0, aux0 = mod.get_params()

    # the plain reference at the initial parameters
    names = run.cfgmod.canonical(cfg, job["build"])
    params = {names[n]: a.asnumpy() for n, a in {**arg0, **aux0}.items()
              if n in names}
    logits = symbol.get_internals()["fc1_output"]
    infer = mx.mod.Module(logits, context=ctx, label_names=None)
    infer.bind(data_shapes=[("data", (8,) + run.image)], for_training=False)
    infer.set_params(arg0, aux0)
    infer.forward(mx.io.DataBatch(data=[mx.nd.array(xs[0][:8], ctx=ctx)]),
                  is_train=False)
    checks, first_loss = run.reference_checks(
        job["build"], params, infer.get_outputs()[0].asnumpy(), dev)
    del infer, arg0, aux0, params

    metric = SyncMetric()
    mod.fit(train, eval_metric=metric, num_epoch=1,
            optimizer=job["optimizer"],
            optimizer_params=dict(job["optimizer_params"]),
            initializer=init)
    jax.block_until_ready([a._data for a in mod._exec.arg_dict.values()])
    rec.stop_trace()

    steps = len(rec.losses)
    checks["first_loss"] = first_loss(rec.losses[0])
    # the step class this cell is about engaged for every step
    if k > 1:
        engaged = (type(mod._scan) is ScanTrainStep
                   and not mod._scan_disabled
                   and mod._scan.windows == steps // k)
        counters = {"program_launches": mod._scan.windows}
    else:
        engaged = (type(mod._fused) is FusedTrainStep
                   and mod._fused.steps == steps)
        counters = {"program_launches": mod._fused.steps}
    checks["step_engaged"] = bool(engaged) and steps == train.handed
    checks["placed_on_device"] = all(
        set(a._data.devices()) == {dev}
        for a in list(mod._exec.arg_dict.values())
        + list(mod._exec.aux_dict.values()) + mod.get_outputs())
    counters["steps"] = steps
    return {"checks": checks, "counters": counters}
