"""``spmd_lm_moe.py``'s loop for a token configuration trained by diffusion
over blocks: the same Zipf pool, mesh and closed loop through
``parallel.spmd.TrainStep``, the reference after the window once the
step's state is freed, the first step's routing count and the logits over
the tokens whose routing cannot flip, reusing ``spmd_lm.py``'s and
``spmd_lm_moe.py``'s functions.  What differs:

* A step takes THREE arrays.  Each pool row ``x0`` of T ids is noised on
  the host, from ``--seed``, by the configuration's schedule: a level
  ``t ~ U(noise_level_min, 1)`` per block of ``block_length``, each token
  of the block replaced by ``mask_token_id`` with probability t.  The
  program's data is ``[x0 ; xt]`` (2T ids), its labels ``x0``, and the
  third array the loss weights ``1/t`` where a position is masked, 0
  elsewhere, ``(batch, T, 1)``, which ``TrainStep`` hands the loss as its
  ``sample_weight``.  The reference is fed the same three.
* Which tokens are kept for the logits.  A logit row belongs to a noisy
  position; its routing flips with its own margin, and attention hands a
  flipped position's change on: a noisy position's to the other noisy
  positions of its block (nothing else sees a noisy key), a clean
  position's to every later block.  The first is one key of at most
  4 + 4b, so a token's margin is the least over the noisy positions of
  ITS BLOCK, in any layer.  The second thins with the keys a row sees and
  is left in: holding the clean blocks before a token to the margin too
  moved no reading on the chip (the job's ``tolerances_why`` has them by
  the number of blocks held).
* The ``mxnet_diffusion_*`` counters count in the step's own spans; the
  share of the T positions that carry loss is printed from the pool.
"""
from __future__ import annotations

import os

import benchcore as C

_HERE = os.path.dirname(os.path.abspath(__file__))
base = C.load_py(os.path.join(_HERE, "spmd_lm.py"),
                 "benchmark_driver_spmd_lm")
moe = C.load_py(os.path.join(_HERE, "spmd_lm_moe.py"),
                "benchmark_driver_spmd_lm_moe")


def noised_pool(seed, x0, block, mask_id, t_min):
    """``(xt, weights)`` for clean ids ``x0`` (..., T): a level ``t ~
    U(t_min, 1)`` per block of ``block``, each token of the block masked
    with probability t; a masked position weighs 1/t, another 0
    (float32).  The same seed gives the same noise."""
    import numpy as np
    rng = np.random.default_rng([int(seed), 0x5DA2])
    t = np.repeat(rng.uniform(t_min, 1.0, x0.shape[:-1]
                              + (x0.shape[-1] // block,)), block, axis=-1)
    masked = rng.random(x0.shape) < t
    return (np.where(masked, mask_id, x0).astype(np.int32),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))


def token_margin(margin, block):
    """(batch, T): per noisy position, the least routing margin over the
    layers and the noisy positions of its block.  ``margin`` (layers,
    batch, 2T) is the reference's, a position."""
    import numpy as np
    noisy = margin.min(axis=0)[:, margin.shape[-1] // 2:]
    by_block = noisy.reshape(noisy.shape[0], -1, block).min(axis=-1)
    return np.repeat(by_block, block, axis=-1)


def run_reference(cfgmod, cfg, build, params, ids, labels, weights, device):
    """Logits, loss, routing margin a position and count a held expert of
    the plain reference on ``device``, float32 at the highest matmul
    precision, from host parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    put = {k: jax.device_put(jnp.asarray(v, jnp.float32), device)
           for k, v in params.items()}
    ids, labels, weights = (jax.device_put(a, device)
                            for a in (ids, labels, weights))
    forward = cfgmod.reference(cfg, build, routing=True)

    def both(p, x, y, w):      # one forward pass for all
        logits, margin, counts = forward(p, x)
        return logits, cfgmod.cross_entropy(logits, y, w), margin, counts

    with jax.default_matmul_precision("highest"):
        logits, loss, margin, counts = jax.jit(both)(put, ids, labels,
                                                     weights)
    return (np.asarray(logits), float(loss), np.asarray(margin),
            np.asarray(counts))


def build_step(run, batch):
    """``spmd_lm.build_step`` with a third example array: the network
    initialised from ``--seed`` on the host, its initial parameters under
    canonical names, and the train step on the mesh."""
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
    try:
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         job["optimizer"], dict(job["optimizer_params"]),
                         mesh, example_batch=tuple(
                             mx.nd.array(a) for a in batch),
                         remat=bool(job["remat"]))
    except ValueError as e:     # a TrainStep of two batch arrays
        raise C.BenchFailure(
            f"this program's TrainStep takes no loss weights: {e}") from e
    return net, params, mesh, step


def run(run):
    import gc

    import jax
    import numpy as np

    try:
        from mxnet_tpu.gluon.model_zoo.language import sdar_moe  # noqa: F401
    except ImportError as e:
        raise C.BenchFailure(
            f"this program cannot build {run.cell.row['config']}: {e}") from e
    from mxnet_tpu import telemetry

    job, cfg = run.job, run.cfg
    tol = job["tolerances"]
    if run.k != 1:
        raise C.BenchFailure("the spmd loop syncs every step")
    devs = run.devices
    length, block = run.image[0], int(cfg["block_length"])
    mask_id = int(cfg["mask_token_id"])
    # ids below the mask id alone: it never stands for a real token
    x0 = base.token_pool(run.seed, int(job["pool_batches"]), run.batch,
                         length, mask_id, job["zipf_s"])
    xt, weights = noised_pool(run.seed, x0, block, mask_id,
                              float(cfg["noise_level_min"]))
    xs = np.ascontiguousarray(np.concatenate([x0, xt], axis=-1))
    ws = np.ascontiguousarray(weights[..., None])
    run.phase(f"pool of {len(x0)} token batches of {run.batch} x {length} "
              f"made and noised per block of {block}: "
              f"{100 * (weights > 0).mean():.2f} % of the positions masked, "
              f"a step lays out {2 * length} positions")
    rec = run.recorder()
    annotate = jax.profiler.TraceAnnotation

    net, params, mesh, step = build_step(run, (xs[0], x0[0], ws[0]))
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
            loss = step(xs[i].copy(), x0[i].copy(), ws[i].copy())
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
    tiles = telemetry.REGISTRY.get("mxnet_flash_attention_tiles")
    C.say(f"  remat boundaries in the step program: "
          f"{step.remat_boundaries} of {n_layers} layers (gauge "
          f"{gauge.value() if gauge is not None else 'absent'}); attention "
          "tiles a head under block_diffusion: " + (
              "gauge absent" if tiles is None else ", ".join(
                  f"{k} {tiles.value({'mask': 'block_diffusion', 'kind': k}):g}"
                  for k in ("empty", "partial", "full")))
          + f"; tokens/s = images/s x {length}")
    load, rows = net.record_expert_load(state(), steps=n)
    held = {k: telemetry.REGISTRY.get(k).value() for k in (
        "mxnet_moe_assignments_held", "mxnet_moe_rows_computed",
        "mxnet_moe_expert_load_max_over_mean")}
    C.say(f"  routed experts, means over the {n} steps: assignments held "
          f"{held['mxnet_moe_assignments_held']:.1f} a step of "
          f"{n_layers * run.batch * 2 * length * cfg['num_experts_per_tok']}"
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
        run.cfgmod, cfg, job["build"], params, xs[0], x0[0], weights[0],
        devs[0])
    del params

    off = float(np.abs(first_load - ref_load).sum() / ref_load.sum())
    C.say(f"  the first step's assignments to each held expert vs the "
          f"plain reference's count: per layer "
          f"{first_load.sum(axis=1).astype(int).tolist()} vs "
          f"{ref_load.sum(axis=1).tolist()}: sum|diff|/sum {off:.3e} "
          f"(tolerance {tol['expert_load_rel']:g})")
    checks["expert_load"] = off <= tol["expert_load_rel"]

    errors = moe.token_errors(logits, ref_logits)
    checks["logits"] = errors is not None
    if errors is not None:
        peak = float(np.abs(ref_logits).max())
        keep = token_margin(margin, block) >= tol["routing_margin"]
        mid_all, tail_all, rms_all, worst_all = moe.readings(
            errors, peak, np.ones_like(keep))
        mid, tail, rms, worst = moe.readings(errors, peak, keep)
        C.say(f"  logits {logits.shape} vs the plain reference over the "
              f"{keep.mean():.1%} of tokens in whose block no held expert "
              f"lies within {tol['routing_margin']:g} of the top-"
              f"{cfg['num_experts_per_tok']} edge: the median token's "
              f"relative error {mid:.3e} (tolerance "
              f"{tol['logits_median_rel']:g}), the 99th percentile's over "
              f"it {tail / mid:.3f} (tolerance "
              f"{tol['logits_p99_over_median']:g}); rms relative {rms:.3e}, "
              f"max|diff|/max|logit| {worst:.3e}; over all tokens "
              f"{mid_all:.3e}, {tail_all / mid_all:.3f}; {rms_all:.3e}, "
              f"{worst_all:.3e}")
        checks["logits"] = bool(mid <= tol["logits_median_rel"] and
                                tail <= tol["logits_p99_over_median"] * mid)
    _, rel = base.compare_loss(rec.losses[0], ref_loss)
    C.say(f"  first training loss {rec.losses[0]:.6f} vs the plain "
          f"reference {ref_loss:.6f}: relative {rel:.3e} (tolerance "
          f"{tol['loss_rel']:g})")
    checks["first_loss"] = rel <= tol["loss_rel"]
    run.phase("plain reference run (logits, routing, first loss)")
    return {"checks": checks, "counters": counters}
