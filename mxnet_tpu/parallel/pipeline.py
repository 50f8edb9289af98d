"""Pipeline parallelism over the mesh 'pp' axis (GPipe microbatching).

The reference has NO first-class pipeline parallelism (SURVEY.md §2.4:
"no schedule/µbatch abstraction" — its dependency engine merely overlaps
model-parallel stages opportunistically, docs/faq/model_parallel_lstm.md).
This module is the greenfield TPU capability SURVEY §7 step 8 plans:

* the network is split into S stages with identical structure (the SPMD
  formulation: one program, per-stage weights stacked on a leading axis
  sharded over 'pp');
* a batch is split into M microbatches; a `lax.scan` runs the classic
  GPipe schedule of T = M + S - 1 ticks; at tick t, stage s computes
  microbatch t-s (bubble ticks compute masked garbage);
* activations hop stage→stage with ONE `lax.ppermute` per tick riding
  the ICI neighbour link — no host involvement, no engine threads;
* the backward pipeline comes from jax.grad: autodiff reverses the scan
  and every ppermute (shift-right becomes shift-left), yielding the
  textbook reverse schedule without any hand-written machinery.

Pipeline efficiency is M / (M + S - 1) (the GPipe bubble); choose M ≥ 4·S
to keep it above 80%. Composes with 'dp' (batch also sharded over dp) by
building the mesh {"dp": d, "pp": s}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from .mesh import DeviceMesh

__all__ = ["stack_stage_params", "pipeline_apply", "gpipe_fn",
           "pipeline_efficiency"]


def pipeline_efficiency(num_stages, num_microbatches):
    """Fraction of ticks doing useful work (GPipe bubble accounting)."""
    return num_microbatches / (num_microbatches + num_stages - 1)


def stack_stage_params(per_stage_params):
    """[S trees with equal structure] -> one tree with leading stage axis.

    The stacked leaves are what gets sharded P('pp', ...): each pp rank
    holds exactly its stage's slice.
    """
    if not per_stage_params:
        raise MXNetError("need at least one stage")
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(stage_fn, stacked_params, x):
    """Single-device reference: apply the S stages sequentially.

    stage_fn(params, x) -> y with y.shape == x.shape (stage-homogeneous
    pipelining; embed/head layers live outside the pipelined region).
    """
    num_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    for s in range(num_stages):
        p_s = jax.tree_util.tree_map(lambda a: a[s], stacked_params)
        x = stage_fn(p_s, x)
    return x


def gpipe_fn(stage_fn, mesh, num_microbatches, axis="pp", batch_axis="dp",
             param_specs=None):
    """Build the pipelined forward: fn(stacked_params, x) -> y.

    stacked_params leaves carry the stage axis first (stack_stage_params),
    sharded P('pp', ...). x is the full batch [B, ...]; it is split into
    `num_microbatches` equal microbatches internally (B % M == 0). When the
    mesh also has a `batch_axis` of size > 1, x is additionally sharded
    over it and the pipeline runs per data-parallel shard.

    ``param_specs`` (optional) is a pytree matching stacked_params whose
    leaves are PartitionSpecs INCLUDING the leading stage axis — e.g.
    ``P('pp', None, 'tp')`` for a stage weight that is also tensor-
    parallel.  ``stage_fn`` may then use the extra mesh axes (psum over
    'tp', all_to_all over 'ep', ...) inside the pipeline body: that is
    how pp composes with tp/ep in one program.  Default: ``P(axis)`` on
    every leaf (stage-sharded, otherwise replicated).

    Returns a function suitable for jax.jit / jax.grad; the backward
    schedule is derived by autodiff.
    """
    if not isinstance(mesh, DeviceMesh):
        raise MXNetError("mesh must be a parallel.DeviceMesh")
    if axis not in mesh.axes:
        raise MXNetError(f"mesh has no '{axis}' axis")
    num_stages = mesh.size(axis)
    M = int(num_microbatches)
    if M < 1:
        raise MXNetError("num_microbatches must be >= 1")

    has_dp = batch_axis in mesh.axes and mesh.size(batch_axis) > 1
    x_spec = P(batch_axis) if has_dp else P()
    # every mesh axis must appear in specs or be explicitly replicated;
    # shard_map replicates unmentioned axes by default
    param_spec = P(axis) if param_specs is None else param_specs

    def shifted(out):
        """One tick's activation hop: stage s sends its output to s+1. The
        wrap-around edge (S-1 -> 0) carries garbage that stage-0's input
        mask discards next tick, so a full ring ppermute is safe AND keeps
        the collective a single neighbour-shift on the ICI torus."""
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        return jax.lax.ppermute(out, axis, perm)

    @functools.partial(
        shard_map, mesh=mesh.jax_mesh,
        in_specs=(param_spec, x_spec), out_specs=x_spec,
        check_vma=False)
    def run(params_blk, x_blk):
        # params_blk leaves: [1, ...] (this rank's stage) -> drop stage axis
        p_local = jax.tree_util.tree_map(lambda a: a[0], params_blk)
        b = x_blk.shape[0]
        if b % M:
            raise MXNetError(f"batch {b} not divisible by "
                             f"num_microbatches {M}")
        mb = b // M
        xs = x_blk.reshape((M, mb) + x_blk.shape[1:])
        stage_idx = jax.lax.axis_index(axis)

        T = M + num_stages - 1
        act0 = jnp.zeros_like(xs[0])

        def tick(act, t):
            # stage 0 reads microbatch t (clamped; masked past M),
            # later stages read the activation shifted in last tick
            x_in = jnp.where(stage_idx == 0,
                             xs[jnp.minimum(t, M - 1)], act)
            out = stage_fn(p_local, x_in)
            act_next = shifted(out)
            # last stage emits microbatch t-(S-1), valid when t >= S-1
            valid = (stage_idx == num_stages - 1) & (t >= num_stages - 1)
            y = jnp.where(valid, out, jnp.zeros_like(out))
            return act_next, y

        _, ys = jax.lax.scan(tick, act0, jnp.arange(T))
        # ys: [T, mb, ...]; rows S-1..T-1 hold microbatches 0..M-1 on the
        # last stage and zeros elsewhere — one psum replicates them
        ys = ys[num_stages - 1:]
        ys = jax.lax.psum(ys, axis)
        return ys.reshape((M * mb,) + ys.shape[2:])

    return run
