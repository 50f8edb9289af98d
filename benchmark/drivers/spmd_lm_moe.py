"""``spmd_lm.py``'s loop for a token configuration with routed experts:
the same pool, the same build of net, mesh and ``TrainStep``, the same
closed loop (ids and labels copied in every step, the loss read every
step), reusing that file's functions.  Three things differ.

* The limits of the reference checks come from the job file
  (``tolerances``, each with its two readings in ``tolerances_why``): they
  are readings of this configuration, not of the one ``spmd_lm.py`` was
  written beside.
* The plain reference runs AFTER the window, once the step's state has
  been freed: 10.1 GB of weights, gradients and momentum leave no room on
  a 16 GB chip for the reference's own 3.4 GB of parameters and its
  logits beside them.  The program's logits at the timed shape are taken
  before the window, from the initial parameters as placed, and kept on
  the host until then; the first training loss is the window's own.
* Routing is checked apart from arithmetic.  The step program's own count
  of the first step's assignments to each held expert (its auxiliary
  state after that step) is held against the reference's count
  (``expert_load``).  The logits are compared over the tokens whose
  routing cannot flip: those of which no held expert's score, in any
  layer of the reference, lies within ``routing_margin`` of the edge of
  the top k (the reference's ``_held_margin``); a token inside it gains
  or loses a whole expert's output by the rounding of its hidden state,
  which is no fault.  Two numbers over them: the median token's relative
  error (the rounding of the products, which a step in the next lower
  precision fails) and the 99th percentile's over that median (the tail:
  what whole expert outputs lost on a per cent of the tokens raise, as
  they do over ALL tokens, the reading on its other side).  After the
  window the auxiliary state, which sums over the steps, is handed to the
  program's ``record_expert_load``, which sets the ``mxnet_moe_*`` gauges
  to means a step.
"""
from __future__ import annotations

import os

import benchcore as C

base = C.load_py(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "spmd_lm.py"), "benchmark_driver_spmd_lm")


def run_reference(cfgmod, cfg, build, params, ids, labels, device):
    """``spmd_lm.run_reference`` with the reference's routing beside its
    logits and loss: each layer's margin a token and count a held expert."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    put = {k: jax.device_put(jnp.asarray(v, jnp.float32), device)
           for k, v in params.items()}
    ids, labels = (jax.device_put(a, device) for a in (ids, labels))
    forward = cfgmod.reference(cfg, build, routing=True)

    def both(p, x, y):      # one forward pass for all
        logits, margin, counts = forward(p, x)
        return logits, cfgmod.cross_entropy(logits, y), margin, counts

    with jax.default_matmul_precision("highest"):
        logits, loss, margin, counts = jax.jit(both)(put, ids, labels)
    return (np.asarray(logits), float(loss), np.asarray(margin),
            np.asarray(counts))


def token_errors(got, ref):
    """Per token: the squared error and the squared norm of its logits,
    and its worst logit's error; None where ``got`` is not finite."""
    import numpy as np
    if got.shape != ref.shape or not np.isfinite(got).all():
        return None
    diff = got.astype(np.float64) - ref
    return ((diff ** 2).sum(-1), (ref.astype(np.float64) ** 2).sum(-1),
            np.abs(diff).max(-1))


def readings(errors, peak, keep):
    """Over the tokens kept: (the median token's relative error, the 99th
    percentile's, rms relative error, max error relative to ``peak``)."""
    import numpy as np
    err, norm, worst = (a[keep] for a in errors)
    mid, tail = np.quantile(np.sqrt(err / norm), [0.5, 0.99])
    return (float(mid), float(tail),
            float(np.sqrt(err.sum() / norm.sum())),
            float(worst.max() / peak))


def run(run):
    import gc

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
    tol = job["tolerances"]
    if run.k != 1:
        raise C.BenchFailure("the spmd loop syncs every step")
    devs = run.devices
    length = run.image[0]
    pool = base.token_pool(run.seed, int(job["pool_batches"]), run.batch,
                           length, int(cfg["vocab_size"]), job["zipf_s"])
    xs = np.ascontiguousarray(pool[:, :, :-1])
    ys = np.ascontiguousarray(pool[:, :, 1:])
    run.phase(f"pool of {len(pool)} token batches of {run.batch} x "
              f"{length - 1} made")
    rec = run.recorder()
    annotate = jax.profiler.TraceAnnotation

    try:
        net, params, mesh, step = base.build_step(run, pool)
    except ImportError as e:
        raise C.BenchFailure(
            f"this program cannot build {run.cell.row['config']}: {e}") from e
    n_layers = len(net.remat_layers)
    run.phase(f"{sum(v.size for v in params.values()):,} parameters "
              "initialised on the host, the step's state placed")

    # the program's own forward at the timed shape, on the mesh, with the
    # parameters as placed; the reference waits until the state is freed
    with mesh.jax_mesh:
        logits = np.asarray(jax.jit(lambda ps, x: step._apply(
            jax.random.PRNGKey(0), ps, (x,))[0][0])(
                step.params, jax.device_put(xs[0], mesh.replicated())))
    run.phase("the program's forward run at the timed shape")

    def state():
        return dict(zip(step.param_names, step.params))

    n, first_load = 0, None
    while not rec.done:
        i = n % len(xs)
        with annotate("bench/step_call"):
            loss = step(xs[i].copy(), ys[i].copy())
        with annotate("bench/loss_read"):
            value = float(loss)
        rec.sync([value])
        n += 1
        if first_load is None:      # a warm-up step: before the window
            first_load = np.asarray(state()[net.expert_load.name])
    rec.stop_trace()

    checks = {
        "step_engaged": len(rec.losses) == n and (
            step.remat_boundaries == n_layers if job["remat"]
            else step.remat_boundaries == 0),
        "placed_on_device": all(set(p.devices()) == set(devs)
                                for p in step.params)}
    gauge = telemetry.REGISTRY.get("mxnet_step_remat_boundaries")
    C.say(f"  remat boundaries in the step program: "
          f"{step.remat_boundaries} of {n_layers} layers (gauge "
          f"{gauge.value() if gauge is not None else 'absent'}); tokens/s "
          f"= images/s x {length - 1}")
    load, rows = net.record_expert_load(state(), steps=n)
    held = {k: telemetry.REGISTRY.get(k).value() for k in (
        "mxnet_moe_assignments_held", "mxnet_moe_rows_computed",
        "mxnet_moe_expert_load_max_over_mean")}
    C.say(f"  routed experts, means over the {n} steps: assignments held "
          f"{held['mxnet_moe_assignments_held']:.1f} a step of "
          f"{n_layers * run.batch * (length - 1) * cfg['num_experts_per_tok']}"
          f", rows computed {held['mxnet_moe_rows_computed']:.1f} (padding "
          f"{100 * (1 - load.sum() / max(rows.sum(), 1)):.1f} %), busiest "
          f"expert over the mean "
          f"{held['mxnet_moe_expert_load_max_over_mean']:.2f}; per layer "
          f"{[round(float(v) / n, 1) for v in load.sum(axis=1)]}")
    counters = {"steps": n, "program_launches": n}

    del step, net
    gc.collect()
    run.phase("the window is over, the step's state freed")
    ref_logits, ref_loss, margin, ref_load = run_reference(
        run.cfgmod, cfg, job["build"], params, xs[0], ys[0], devs[0])
    del params

    off = float(np.abs(first_load - ref_load).sum() / ref_load.sum())
    C.say(f"  the first step's assignments to each held expert vs the "
          f"plain reference's count: {first_load.astype(int).tolist()} vs "
          f"{ref_load.tolist()}: sum|diff|/sum {off:.3e} (tolerance "
          f"{tol['expert_load_rel']:g})")
    checks["expert_load"] = off <= tol["expert_load_rel"]

    errors = token_errors(logits, ref_logits)
    checks["logits"] = errors is not None
    if errors is not None:
        peak = float(np.abs(ref_logits).max())
        keep = margin.min(axis=0) >= tol["routing_margin"]
        mid_all, tail_all, rms_all, worst_all = readings(
            errors, peak, np.ones_like(keep))
        mid, tail, rms, worst = readings(errors, peak, keep)
        C.say(f"  logits {logits.shape} vs the plain reference over the "
              f"{keep.mean():.1%} of tokens with no held expert within "
              f"{tol['routing_margin']:g} of the top-"
              f"{cfg['num_experts_per_tok']} edge: the median token's "
              f"relative error {mid:.3e} (tolerance "
              f"{tol['logits_median_rel']:g}), the 99th percentile's over "
              f"it {tail / mid:.3f} (tolerance "
              f"{tol['logits_p99_over_median']:g}); rms relative {rms:.3e}, "
              f"max|diff|/max|logit| {worst:.3e}; over all tokens "
              f"{mid_all:.3e}, {tail_all / mid_all:.3f}; {rms_all:.3e}, "
              f"{worst_all:.3e}")
        checks["logits"] = mid <= tol["logits_median_rel"] and \
            tail <= tol["logits_p99_over_median"] * mid
    _, rel = base.compare_loss(rec.losses[0], ref_loss)
    C.say(f"  first training loss {rec.losses[0]:.6f} vs the plain "
          f"reference {ref_loss:.6f}: relative {rel:.3e} (tolerance "
          f"{tol['loss_rel']:g})")
    checks["first_loss"] = rel <= tol["loss_rel"]
    run.phase("plain reference run (logits, routing, first loss)")
    return {"checks": checks, "counters": counters}
