"""``parallel.spmd.TrainStep`` on ``make_mesh(**job["mesh"])``: one
pjit'd program per step across the chips, the global batch sharded from
the host every step, the loss read every step (the sync).  The path
``examples/train_imagenet.py --num-devices N`` takes; the checks are
``chip_smoke.py``'s ``leg_spmd``.
"""
from __future__ import annotations

import benchcore as C


def run(run):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import TrainStep, shard_batch

    job, cfg = run.job, run.cfg
    if run.k != 1:
        raise C.BenchFailure("the spmd loop syncs every step")
    devs = run.devices
    xs, ys = run.pool
    rec = run.recorder()
    annotate = jax.profiler.TraceAnnotation

    mx.random.seed(run.seed)
    np.random.seed(run.seed)
    net = run.cfgmod.build(cfg, job["build"])
    net.initialize(mx.initializer.Xavier(magnitude=2.0))
    mesh = make_mesh(devices=list(devs), **job["mesh"])
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     job["optimizer"], dict(job["optimizer_params"]), mesh,
                     example_batch=(mx.nd.array(xs[0]), mx.nd.array(ys[0])))

    # four distinct devices each hold a quarter of the batch
    shards = {s.device: s.data.shape
              for s in shard_batch(mesh, xs[0]).addressable_shards}
    sharded = set(shards) == set(devs) and all(
        shp[0] == run.batch // len(devs) for shp in shards.values())

    # the plain reference at the initial parameters: the program's own
    # forward in eval mode, on the mesh, with the parameters as placed
    names = run.cfgmod.canonical(cfg, job["build"], net)
    placed = step.params
    params = {names[n]: np.asarray(a)
              for n, a in zip(step.param_names, placed) if n in names}
    with mesh.jax_mesh:
        logits = jax.jit(lambda ps, x: step._apply(
            jax.random.PRNGKey(0), ps, (x,))[0][0])(
                placed, jax.device_put(xs[0][:8], mesh.replicated()))
    checks, first_loss = run.reference_checks(
        job["build"], params, np.asarray(logits), devs[0])
    del params, placed, logits

    n = 0
    while not rec.done:
        i = n % len(xs)
        with annotate("bench/step_call"):
            loss = step(xs[i], ys[i])
        with annotate("bench/loss_read"):
            value = float(loss)
        rec.sync([value])
        n += 1
    rec.stop_trace()

    checks["first_loss"] = first_loss(rec.losses[0])
    checks["step_engaged"] = bool(sharded) and len(rec.losses) == n
    checks["placed_on_device"] = all(
        set(p.devices()) == set(devs) for p in step.params)
    return {"checks": checks, "counters": {"steps": n,
                                           "program_launches": n}}
