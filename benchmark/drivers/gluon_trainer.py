"""The Gluon entry point, as ``examples/train_imagenet.py`` writes it:
``net.hybridize()``, then per step ``autograd.record`` ->
``loss.backward()`` -> ``gluon.Trainer.step(batch)``, the batch copied
from the host and the loss read back every step (the sync).
"""
from __future__ import annotations

import time

import benchcore as C


def run(run):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    job, cfg, ctx = run.job, run.cfg, run.ctx
    if run.k != 1:
        raise C.BenchFailure("the Gluon loop syncs every step")
    dev = ctx.jax_device
    xs, ys = run.pool
    rec = run.recorder()
    annotate = jax.profiler.TraceAnnotation

    mx.random.seed(run.seed)
    np.random.seed(run.seed)
    net = run.cfgmod.build(cfg, job["build"])
    net.initialize(mx.initializer.Xavier(magnitude=2.0), ctx=ctx)
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # the plain reference at the initial parameters (the forward outside
    # autograd.record is the eval-mode one, and finishes deferred init)
    logits = net(mx.nd.array(xs[0][:8], ctx=ctx)).asnumpy()
    names = run.cfgmod.canonical(cfg, job["build"], net)
    params = {names[n]: p.data().asnumpy()
              for n, p in net.collect_params().items() if n in names}
    checks, first_loss = run.reference_checks(job["build"], params, logits,
                                              dev)
    del params

    trainer = gluon.Trainer(net.collect_params(), job["optimizer"],
                            dict(job["optimizer_params"]))
    update_s = []
    step = 0
    while not rec.done:
        i = step % len(xs)
        with annotate("bench/copy_in"):
            x = mx.nd.array(xs[i], ctx=ctx)
            y = mx.nd.array(ys[i], ctx=ctx)
        with annotate("bench/forward"):
            with mx.autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
        with annotate("bench/backward"):
            loss.backward()
        t = time.perf_counter()
        with annotate("bench/trainer_step"):
            trainer.step(run.batch)
        update_s.append(time.perf_counter() - t)
        with annotate("bench/loss_read"):
            value = float(loss.asnumpy().mean())
        rec.sync([value])
        step += 1
    rec.stop_trace()

    checks["first_loss"] = first_loss(rec.losses[0])
    grads = [p.grad() for p in net.collect_params().values()
             if p.grad_req != "null"]
    checks["step_engaged"] = len(rec.losses) == step and bool(grads)
    checks["placed_on_device"] = all(
        set(p.data()._data.devices()) == {dev}
        for p in net.collect_params().values())
    first = (rec.start + 1) * run.k
    return {"checks": checks,
            "counters": {"steps": step,
                         "trainer_step_s": update_s[first:],
                         "parameter_tensors": len(grads)}}
