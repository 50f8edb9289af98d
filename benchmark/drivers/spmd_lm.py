"""``parallel.spmd.TrainStep`` over token sequences on
``make_mesh(**job["mesh"])``: one pjit'd program per step, a ``(batch, T)``
batch of token ids and its ``(batch, T)`` labels copied from the host every
step, the loss read every step (the sync).  With ``job["remat"]`` the step
holds one rematerialisation boundary per decoder layer.

The harness's ``Run`` thinks in images: ``cfg["image"] = [T + 1]`` is one
pool row of ids (inputs ``[:-1]``, labels ``[1:]``), ``num_classes`` the
vocabulary held here, an "image" of ``images_per_s`` one sequence of T
tokens.  The pool ``Run`` makes (uniform floats) is not used: token ids
come from ``token_pool`` below, from the same ``--seed``.

The reference checks are this driver's own, at the timed shape: the
program's forward over one whole pool batch against the configuration's
plain reference fed the same initial parameters, the whole
``(batch, T, vocab)`` array of logits, and the first training loss.
"""
from __future__ import annotations

import math

import benchcore as C

# The program multiplies float32 operands at the TPU's default precision
# (bfloat16 products, float32 sums) and keeps activations in float32; the
# reference multiplies at "highest".  Each tolerance lies between what this
# program read on the chip and what the same step read there with bfloat16
# parameters and activations (my chip runs, PR 27; PERF.md section 6), so
# that a step computed in bfloat16 end to end fails.
#
# ‖got − ref‖₂ / ‖ref‖₂ over all batch · T · vocab logits.  The rounding of
# some forty matrix products in sequence, averaged over 51 M logits, so it
# hardly moves from seed to seed: 1.319e-2 and 1.313e-2 here, 2.172e-2 in
# bfloat16
LOGITS_RMS_REL_TOL = 1.7e-2
# max|got − ref| / max|ref|: the worst single logit against the largest,
# 5.71e-3 and 5.76e-3 here, 1.001e-2 in bfloat16
LOGITS_MAX_REL_TOL = 8.0e-3
# the loss is a mean of 4096 log-probabilities near ln(vocab): errors of
# the logits average out (1.6e-6 and 1.4e-6 here), and bfloat16 cannot hold
# ln 12544 = 9.437 to better than its spacing of 0.0625 between 8 and 16
# (1.29e-3 there, and its loss then never moves)
LOSS_REL_TOL = 1.0e-4


def token_pool(seed, n, batch, length, vocab, zipf_s):
    """``n`` host batches of ``batch`` rows of ``length`` token ids drawn
    from Zipf(``zipf_s``) over ``vocab`` ids (id k with probability
    ∝ 1 / (k + 1)^s), int32.  The same seed gives the same ids."""
    import numpy as np
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(zipf_s)
    return rng.choice(vocab, size=(n, batch, length),
                      p=p / p.sum()).astype(np.int32)


def compare_logits(got, ref):
    """(ok, rms relative, max relative) of the whole arrays."""
    import numpy as np
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if got.shape != ref.shape or not np.isfinite(got).all() \
            or not np.abs(ref).max() > 0:
        return False, float("inf"), float("inf")
    diff = got.astype(np.float64) - ref
    rms = float(np.sqrt((diff ** 2).sum() / (ref.astype(np.float64) ** 2).sum()))
    worst = float(np.abs(diff).max() / np.abs(ref).max())
    return (rms <= LOGITS_RMS_REL_TOL and worst <= LOGITS_MAX_REL_TOL,
            rms, worst)


def compare_loss(got, ref):
    if not (math.isfinite(got) and math.isfinite(ref)):
        return False, float("inf")
    rel = abs(got - ref) / max(abs(ref), 1e-30)
    return rel <= LOSS_REL_TOL, rel


def run_reference(cfgmod, cfg, build, params, ids, labels, device):
    """Logits and loss of the plain reference on ``device``, float32 at the
    highest matmul precision, from host parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    put = {k: jax.device_put(jnp.asarray(v, jnp.float32), device)
           for k, v in params.items()}
    ids, labels = (jax.device_put(a, device) for a in (ids, labels))
    forward = cfgmod.reference(cfg, build)

    def both(p, x, y):      # one forward pass for the two
        logits = forward(p, x)
        return logits, cfgmod.cross_entropy(logits, y)

    with jax.default_matmul_precision("highest"):
        logits, loss = jax.jit(both)(put, ids, labels)
    return np.asarray(logits), float(loss)


def build_step(run, pool):
    """The network initialised from ``--seed`` on the host, its initial
    parameters under canonical names, and the train step on the mesh."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import TrainStep

    job, cfg = run.job, run.cfg
    mx.random.seed(run.seed)
    np.random.seed(run.seed)
    net = run.cfgmod.build(cfg, job["build"])
    net.initialize(mx.initializer.Normal(0.02))
    names = run.cfgmod.canonical(cfg, job["build"], net)
    params = {names[k]: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    want = run.cfgmod.param_shapes(cfg, job["build"])
    if {k: v.shape for k, v in params.items()} != \
            {k: tuple(s) for k, s in want.items()}:
        raise C.BenchFailure(
            "the program's parameters do not match the configuration's "
            f"layer shapes: {sorted(set(want) ^ set(params))[:4]}")
    mesh = make_mesh(devices=list(run.devices), **job["mesh"])
    x0, y0 = pool[0][:, :-1], pool[0][:, 1:]
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     job["optimizer"], dict(job["optimizer_params"]), mesh,
                     example_batch=(mx.nd.array(x0), mx.nd.array(y0)),
                     remat=bool(job["remat"]))
    return net, params, mesh, step


def run(run):
    import jax
    import numpy as np

    try:
        import mxnet_tpu.gluon.model_zoo.language  # noqa: F401
    except ImportError as e:
        raise C.BenchFailure(
            f"this program has no language model zoo ({e}): it cannot run "
            "a token configuration") from e
    from mxnet_tpu import telemetry

    job, cfg = run.job, run.cfg
    if run.k != 1:
        raise C.BenchFailure("the spmd loop syncs every step")
    devs = run.devices
    length = run.image[0]
    pool = token_pool(run.seed, int(job["pool_batches"]), run.batch, length,
                      int(cfg["vocab_size"]), job["zipf_s"])
    xs = np.ascontiguousarray(pool[:, :, :-1])
    ys = np.ascontiguousarray(pool[:, :, 1:])
    run.phase(f"pool of {len(pool)} token batches of {run.batch} x "
              f"{length - 1} made")
    rec = run.recorder()
    annotate = jax.profiler.TraceAnnotation

    net, params, mesh, step = build_step(run, pool)
    n_layers = len(net.remat_layers)
    run.phase(f"{sum(v.size for v in params.values()):,} parameters "
              "initialised on the host, the step's state placed")

    # the program's own forward at the timed shape, on the mesh, with the
    # parameters as placed; then the plain reference from the same values
    with mesh.jax_mesh:
        logits = np.asarray(jax.jit(lambda ps, x: step._apply(
            jax.random.PRNGKey(0), ps, (x,))[0][0])(
                step.params, jax.device_put(xs[0], mesh.replicated())))
    run.phase("the program's forward run at the timed shape")
    ref_logits, ref_loss = run_reference(
        run.cfgmod, cfg, job["build"], params, xs[0], ys[0], devs[0])
    del params
    ok, rms, worst = compare_logits(logits, ref_logits)
    C.say(f"  logits {logits.shape} vs the plain reference: "
          f"rms relative {rms:.3e} (tolerance {LOGITS_RMS_REL_TOL:g}), "
          f"max|diff|/max|logit| {worst:.3e} (tolerance "
          f"{LOGITS_MAX_REL_TOL:g})")
    checks = {"logits": ok}
    del logits, ref_logits
    run.phase("plain reference run (logits, first loss)")

    n = 0
    while not rec.done:
        i = n % len(xs)
        with annotate("bench/step_call"):
            loss = step(xs[i].copy(), ys[i].copy())
        with annotate("bench/loss_read"):
            value = float(loss)
        rec.sync([value])
        n += 1
    rec.stop_trace()

    ok, rel = compare_loss(rec.losses[0], ref_loss)
    C.say(f"  first training loss {rec.losses[0]:.6f} vs the plain "
          f"reference {ref_loss:.6f}: relative {rel:.3e} (tolerance "
          f"{LOSS_REL_TOL:g})")
    gauge = telemetry.REGISTRY.get("mxnet_step_remat_boundaries")
    C.say(f"  remat boundaries in the step program: "
          f"{step.remat_boundaries} of {n_layers} layers (gauge "
          f"{gauge.value() if gauge is not None else 'absent'}); tokens/s "
          f"= images/s x {length - 1}")
    checks["first_loss"] = ok
    checks["step_engaged"] = len(rec.losses) == n and (
        step.remat_boundaries == n_layers if job["remat"]
        else step.remat_boundaries == 0)
    checks["placed_on_device"] = all(
        set(p.devices()) == set(devs) for p in step.params)
    return {"checks": checks, "counters": {"steps": n,
                                           "program_launches": n}}
