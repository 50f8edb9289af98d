"""Mesh-fused distributed train step: ONE donated XLA dispatch per
K-step window *under the DeviceMesh*, with overlapped bucketed
gradient collectives (ISSUE 9 tentpole).

PR 4/PR 6 collapsed the single-device train step to one donated
dispatch (and one per K steps under ``jax.lax.scan``); every
*distributed* path still paid the tax they eliminated — the kvstore
data-parallel loop issues one ``push`` + one ``pull`` per parameter per
step (163 host round-trips for ResNet-50), exactly on the workloads
that should run as fast as the hardware allows.  This module applies
the same whole-iteration-capture argument (PyGraph, PAPERS.md) to the
mesh: forward + VJP + **gradient reduction** + whole-pytree optimizer
update trace into one donated ``jax.jit(shard_map(...))`` computation
per window, and gradient synchronization moves *inside* the traced
step as bucketed collectives:

* trainable parameters are grouped into ``MXNET_COLLECTIVE_BUCKET_MB``-
  sized flat buckets (same-dtype, training order);
* each bucket issues ONE ``psum`` (replicated layout) or ONE
  ``psum_scatter`` + ``all_gather`` pair (fsdp layout) over the flat
  concatenation — ≤ ceil(total_param_MB / bucket_MB) reduction ops per
  step instead of one per parameter — so XLA's async collective
  scheduler can overlap each bucket's communication with the remaining
  backward compute (Opara's independent-work concurrency argument,
  PAPERS.md);
* ``jax.lax.scan`` composes on top exactly like the single-device
  ScanTrainStep: ``MXNET_SCAN_STEPS``/``MXNET_SCAN_ACCUM`` work under
  the mesh, host control stays at window boundaries.

Contracts kept (the same ones fused_step.py holds single-device):

* **Bit parity** with the sequential per-param kvstore loop in the
  replicated layout: each mesh rank computes the gradients of its batch
  shard with the exact executor math, the bucketed ``psum`` adds the
  per-shard partials element-for-element like the store's ``add_n``,
  and ``Optimizer.fused_update`` mirrors the per-param ops bit for bit.
  (The fsdp layout's ring reduce-scatter may legally reassociate the
  shard sum — parity there is to 1 ulp, see docs/parallel.md.)
* **Views stay consistent**: parameters/optimizer state live in the
  same ``arg_dict``/``Updater.states`` NDArrays (now holding
  mesh-replicated ``jax.Array`` buffers), so metrics, checkpointing and
  ``get_optimizer_states`` work unchanged — and PR 2's elastic
  checkpoint restore is the resize mechanism: save at a window
  boundary, restore onto ANY dp×tp×pp mesh, continue (docs/parallel.md
  resize runbook).
* **Donation safety**: the PR-4 ownership ledger, extended with the
  parameter sharding — externally-set buffers are copied AND re-placed
  onto the mesh before their first donation.

``Module.fit`` routes here when a ``dist_device_sync``-style in-process
kvstore is installed and the setup is eligible (module.py
``_mesh_fused_eligible``; docs/parallel.md has the matrix): the host
kvstore shrinks to init/broadcast + optimizer-state fetch, and the
per-step push/pull loop dies on the hot path.  Opt-out:
``MXNET_MESH_FUSED_STEP=0``.  ``python -m mxnet_tpu.parallel.fused`` is
the CI smoke (8-fake-device dp×tp fit: dispatch budget + bitwise parity
vs the per-param kvstore loop).
"""
from __future__ import annotations

import logging
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import profiler as _prof
from .. import random as _random
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..telemetry import numerics as _numerics
from ..fused_step import ScanTrainStep, host_hyperparams, hyper_scalars
from ..gradient_compression import (COLLECTIVE_CODECS, codec_wire_bytes,
                                    decode_2bit_sum, quantize_2bit_flat)
from ..ndarray import NDArray
from .mesh import DeviceMesh

log = logging.getLogger(__name__)

LAYOUTS = ("replicated", "fsdp")


# -- bucket planning ---------------------------------------------------------
def plan_buckets(shapes, dtypes, bucket_mb, state_keys=None):
    """Group parameters (training order) into flat collective buckets.

    Returns a list of index lists.  A bucket holds consecutive params of
    the SAME dtype (flat concatenation must be homogeneous) and the same
    optimizer-state structure (``state_keys``, for the fsdp flat-state
    path) whose cumulative size stays under ``bucket_mb`` MB — except
    that a single oversized param always gets its own bucket.  Total
    reduction ops per step = len(plan) <= ceil(total_MB / bucket_MB) +
    (#dtype/state boundaries), the "not one per param" contract the
    mesh-fused trace test pins down.
    """
    limit = max(1, int(float(bucket_mb) * (1 << 20)))
    plan, cur, cur_bytes = [], [], 0
    cur_key = None
    for i, (shape, dtype) in enumerate(zip(shapes, dtypes)):
        nbytes = int(np.prod(shape, dtype=np.int64)) * \
            np.dtype(dtype).itemsize
        key = (str(dtype),
               state_keys[i] if state_keys is not None else None)
        if cur and (key != cur_key or cur_bytes + nbytes > limit):
            plan.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_key = key
    if cur:
        plan.append(cur)
    return plan


def bucketed_all_reduce(grads, axis_names, plan):
    """Sum ``grads`` across ``axis_names`` with ONE ``psum`` per bucket.

    Usable inside any shard_map program (the spmd/tp/pipeline
    integration point): each bucket's grads are raveled into one flat
    vector, reduced with a single collective, and split back — the
    per-element adds are identical to per-param psums, so results are
    bitwise unchanged, but the collective count drops from len(grads)
    to len(plan) and XLA can overlap each bucket with the remaining
    backward compute.
    """
    out = [None] * len(grads)
    for bucket in plan:
        flat = jnp.concatenate([grads[i].ravel() for i in bucket]) \
            if len(bucket) > 1 else grads[bucket[0]].ravel()
        flat = jax.lax.psum(flat, axis_names)  # graftlint: disable=per-param-collective -- this IS the bucketed form: one psum per BUCKET, the loop the rule steers callers toward
        off = 0
        for i in bucket:
            n = grads[i].size
            out[i] = jax.lax.dynamic_slice(flat, (off,), (n,)).reshape(
                grads[i].shape)
            off += n
    return out


def compressed_bucket_all_reduce(grads, axis_names, plan, codec,
                                 threshold, residuals):
    """Per-bucket gradient exchange with an opt-in codec (ISSUE 11):

    * ``fp16`` — ONE half-width ``psum`` per bucket (wire bytes halved;
      the sum reassociates in fp16, ~1e-3 relative tolerance);
    * ``2bit`` — kTwoBit error-feedback quantization *inside the trace*:
      each rank quantizes its flat bucket against its own residual
      (``residuals[b]`` is this rank's (1, n) slice of the rank-sharded
      residual carry), ONE ``all_gather`` per bucket moves the packed
      uint8 codes (4 codes/byte — 2 bits/element on the wire), and
      every rank decodes + sums the gathered codes, exactly like the
      reference parameter server's DataHandleCompressed.

    Buckets whose dtype is not float32 fall back to the dense ``psum``.
    Returns ``(grads_out, new_residuals)``; residuals pass through
    untouched for codecs that keep no state.
    """
    out = [None] * len(grads)
    new_res = list(residuals)
    for b, bucket in enumerate(plan):
        flat = jnp.concatenate([grads[i].ravel() for i in bucket]) \
            if len(bucket) > 1 else grads[bucket[0]].ravel()
        if codec == "2bit" and flat.dtype == jnp.float32:
            packed, res = quantize_2bit_flat(
                flat, residuals[b][0], threshold)
            gathered = jax.lax.all_gather(packed, axis_names)  # graftlint: disable=per-param-collective -- one all-gather of packed CODES per bucket: the compressed batched form
            flat = decode_2bit_sum(gathered, threshold, flat.shape[0])
            new_res[b] = res.reshape((1,) + res.shape)
        elif codec == "fp16" and flat.dtype == jnp.float32:
            flat = jax.lax.psum(flat.astype(jnp.float16), axis_names)  # graftlint: disable=per-param-collective -- one half-width psum per BUCKET
            flat = flat.astype(jnp.float32)
        else:
            flat = jax.lax.psum(flat, axis_names)  # graftlint: disable=per-param-collective -- dense fallback for non-f32 buckets, still one psum per BUCKET
        off = 0
        for i in bucket:
            n = grads[i].size
            out[i] = jax.lax.dynamic_slice(flat, (off,), (n,)).reshape(
                grads[i].shape)
            off += n
    return out, tuple(new_res)


def _flat_bucket(arrs, pad):
    flat = jnp.concatenate([a.ravel() for a in arrs]) \
        if len(arrs) > 1 else arrs[0].ravel()
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def _unflatten_bucket(flat, templates):
    out, off = [], 0
    for t in templates:
        n = int(np.prod(t.shape, dtype=np.int64)) if t.shape else 1
        out.append(jax.lax.dynamic_slice(flat, (off,), (n,)).reshape(
            t.shape))
        off += n
    return out


def fsdp_bucket_update(opt, params, grads, states, lrs, wds, axis_names,
                       plan, n_shards):
    """Per-bucket reduce-scatter → local flat-shard optimizer update →
    all-gather (the fsdp collective layout).

    Each rank reduces+keeps only its 1/n_shards slice of the bucket's
    flat gradient (``psum_scatter``), updates that slice of the flat
    parameter/state with per-element lr/wd vectors (the optimizer's
    ``fused_update`` math is elementwise for every fused-eligible
    optimizer, so flat slices update exactly like per-param arrays),
    and re-materializes the full parameters with one ``all_gather`` per
    bucket leaf.  Reduction ops per step = len(plan), same bound as the
    replicated layout.
    """
    new_params = [None] * len(params)
    new_states = [None] * len(states)
    idx = jax.lax.axis_index(axis_names)
    for bucket in plan:
        ws = [params[i] for i in bucket]
        total = sum(int(w.size) for w in ws)
        pad = (-total) % n_shards
        shard_len = (total + pad) // n_shards
        start = idx * shard_len

        flat_g = _flat_bucket([grads[i] for i in bucket], pad)
        g_shard = jax.lax.psum_scatter(flat_g, axis_names,  # graftlint: disable=per-param-collective -- one reduce-scatter per BUCKET: the batched form itself
                                       scatter_dimension=0, tiled=True)
        flat_w = _flat_bucket(ws, pad)
        w_shard = jax.lax.dynamic_slice(flat_w, (start,), (shard_len,))

        # per-element lr/wd: constant over each param's flat segment
        # (lr/wd arrive as traced scalars, so schedules never retrace)
        lr_vec = jnp.concatenate(
            [jnp.broadcast_to(lrs[i], (int(params[i].size),))
             for i in bucket] +
            ([jnp.zeros((pad,), jnp.float32)] if pad else []))
        wd_vec = jnp.concatenate(
            [jnp.broadcast_to(wds[i], (int(params[i].size),))
             for i in bucket] +
            ([jnp.zeros((pad,), jnp.float32)] if pad else []))
        lr_shard = jax.lax.dynamic_slice(lr_vec, (start,), (shard_len,))
        wd_shard = jax.lax.dynamic_slice(wd_vec, (start,), (shard_len,))

        # optimizer state: flatten leaf-position-wise across the bucket
        # (plan_buckets guaranteed a uniform state structure), slice the
        # local shard, update, all-gather back to full per-param trees
        st_template = states[bucket[0]]
        leaves0, treedef = jax.tree_util.tree_flatten(st_template)
        st_shard_leaves = []
        for leaf_pos in range(len(leaves0)):
            flat_s = _flat_bucket(
                [jax.tree_util.tree_leaves(states[i])[leaf_pos]
                 for i in bucket], pad)
            st_shard_leaves.append(jax.lax.dynamic_slice(
                flat_s, (start,), (shard_len,)))
        st_shard = jax.tree_util.tree_unflatten(treedef, st_shard_leaves)

        with jax.named_scope("step/optimizer"):
            upd_p, upd_s = opt.fused_update(
                [w_shard], [g_shard], [st_shard], [lr_shard], [wd_shard])
        new_flat_w = jax.lax.all_gather(upd_p[0], axis_names, tiled=True)  # graftlint: disable=per-param-collective -- one all-gather per BUCKET: the batched form itself
        bucket_params = _unflatten_bucket(new_flat_w, ws)
        for i, npar in zip(bucket, bucket_params):
            new_params[i] = npar
        new_leaves = jax.tree_util.tree_leaves(upd_s[0])
        gathered = [jax.lax.all_gather(l, axis_names, tiled=True)  # graftlint: disable=per-param-collective -- one all-gather per bucket STATE LEAF (2 for Adam), not per parameter
                    for l in new_leaves]
        per_param_leaves = [
            _unflatten_bucket(g, [jax.tree_util.tree_leaves(states[i])[k]
                                  for i in bucket])
            for k, g in enumerate(gathered)]
        for j, i in enumerate(bucket):
            new_states[i] = jax.tree_util.tree_unflatten(
                treedef, [per_param_leaves[k][j]
                          for k in range(len(gathered))])
    return new_params, new_states


def _state_key(state):
    """Structure fingerprint of one param's optimizer state (buckets
    must be state-structure-homogeneous for the fsdp flat path)."""
    return str(jax.tree_util.tree_structure(state))


# -- the mesh-fused window step ----------------------------------------------
class MeshFusedTrainStep(ScanTrainStep):
    """K fused train steps under a DeviceMesh as ONE donated dispatch.

    The single-device ScanTrainStep body (forward + VJP + optimizer
    update, scanned over K steps) becomes the per-shard program of a
    ``shard_map`` over the mesh: the batch dim of every feed shards
    over ALL mesh axes (a symbolic Module graph is data-parallel; tp/pp
    programs compose through the functional helpers above instead),
    parameters and optimizer state ride replicated, and gradient
    reduction runs inside the trace as one collective per flat bucket.
    """

    def __init__(self, module, mesh, scan_steps=1, accum=1,
                 layout="replicated", bucket_mb=None, comm_mode=None,
                 compression=None):
        from .. import config as _config
        if not isinstance(mesh, DeviceMesh):
            raise MXNetError("mesh must be a parallel.DeviceMesh")
        if layout not in LAYOUTS:
            raise MXNetError(f"unknown mesh layout {layout!r}; "
                             f"options: {LAYOUTS}")
        super().__init__(module, scan_steps, accum)
        self.codec = compression if compression is not None else \
            _config.get("MXNET_COLLECTIVE_COMPRESSION")
        if self.codec not in COLLECTIVE_CODECS:
            raise MXNetError(
                f"unknown collective compression {self.codec!r}; "
                f"options: {COLLECTIVE_CODECS}")
        if self.codec != "none" and layout == "fsdp":
            raise MXNetError(
                "collective compression composes with the replicated "
                "layout only (the fsdp flat-shard update needs exact "
                "per-shard reduce-scatter semantics)")
        self.codec_threshold = float(
            _config.get("MXNET_COLLECTIVE_COMPRESSION_THRESHOLD"))
        if self._aux_names:
            # per-replica aux mutation (BN running stats) would need
            # sync-BN semantics the per-param loop does not have —
            # module eligibility already excludes this; double-lock it
            raise MXNetError(
                "mesh fused step does not support auxiliary states")
        self.mesh = mesh
        self.layout = layout
        self.comm_mode = comm_mode if comm_mode is not None else \
            _config.get("MXNET_COLLECTIVE_MODE")
        self.bucket_mb = float(bucket_mb if bucket_mb is not None
                               else _config.get("MXNET_COLLECTIVE_BUCKET_MB"))
        self._axes = tuple(mesh.axis_names)
        self._n_shards = mesh.size()
        self._repl = mesh.replicated()
        self._plan = None
        self._grad_bytes = 0
        self._comm_est_s = None  # calibrated standalone collective cost
        self._bucket_elems = ()   # per-bucket flat element counts
        self._residual_bufs = None  # 2bit error-feedback carry (rank-sharded)
        self._rest_cache = {}     # multiprocess replicated rest-arg cache

    # Module routes mesh training through whole windows only; the
    # single-batch fused entry point stays on the per-param loop
    def step(self, data_batch):
        raise MXNetError("MeshFusedTrainStep dispatches whole windows "
                         "(run_window); Module.fit routes here via the "
                         "scanned fit path")

    def _build_plan(self):
        exec_ = self._module._exec
        shapes = [tuple(exec_.arg_dict[n].shape) for n in self._train_names]
        dtypes = [str(exec_.arg_dict[n]._data.dtype)
                  for n in self._train_names]
        updater = self._module._updater
        state_keys = None
        if self.layout == "fsdp":
            state_keys = [
                _state_key(jax.tree_util.tree_map(
                    lambda x: 0, updater.states[i]))
                for i in self._opt_indices]
        self._plan = plan_buckets(shapes, dtypes, self.bucket_mb,
                                  state_keys)
        self._grad_bytes = sum(
            int(np.prod(s, dtype=np.int64)) * np.dtype(d).itemsize
            for s, d in zip(shapes, dtypes))
        elems = [int(np.prod(s, dtype=np.int64)) if s else 1
                 for s in shapes]
        self._bucket_elems = tuple(sum(elems[i] for i in bucket)
                                   for bucket in self._plan)
        if self.codec == "2bit":
            # error-feedback residual: one (n_shards, bucket_elems) f32
            # array per bucket, rank-sharded on dim 0 — each mesh rank
            # carries ITS OWN residual through the donated scan carry
            # (fresh zeros on rebuild/restore; docs/parallel.md)
            self._residual_bufs = [
                self.mesh.put_batch(
                    np.zeros((self._n_shards, n), np.float32), 0)
                for n in self._bucket_elems]
        else:
            self._residual_bufs = []

    # -- trace ---------------------------------------------------------------
    def _build_scan_jit(self):
        from .. import compile as _compile
        _compile.ensure_persistent_cache()
        _compile.record_trace(
            "mesh_step",
            "build" if self._scan_jit is None else "signature-change")
        self._just_built = True
        self._build_plan()
        module = self._module
        fn = module._exec._build_fn(True)
        opt = module._optimizer
        n_args = len(self._arg_names)
        train_slots = tuple(self._train_slots)
        feed_slots = tuple(self._arg_names.index(n)
                           for n in self._feed_order)
        feed_set = set(self._feed_order)
        self._rest_names = [n for n in self._other_names
                            if n not in feed_set]
        rest_slots = tuple(self._arg_names.index(n)
                           for n in self._rest_names)
        accum = self.accum
        axes = self._axes
        plan = self._plan
        layout = self.layout
        comm_on = self.comm_mode != "off"
        n_shards = self._n_shards
        codec = self.codec
        threshold = self.codec_threshold
        # numerics observatory (ISSUE 14): stats need the globally
        # REDUCED gradient, so the mesh sentinel arms only where the
        # reduced pytree exists in-trace — the replicated layout with
        # collectives on (fsdp shards the sum; comm off computes nothing true)
        self._num_mode = _numerics.trace_mode()
        if self._num_mode != "off" and not (comm_on and
                                            layout == "replicated"):
            log.warning(
                "numerics observatory disabled for this mesh window: "
                "MXNET_NUMERICS=%s needs comm_mode='bucketed' and the "
                "replicated layout (got %s/%s)", self._num_mode,
                self.comm_mode, layout)
            self._num_mode = "off"
        num_mode = self._num_mode
        num_groups = self._plan if num_mode != "off" else []
        self._num_poison = num_mode != "off" and _numerics.poison_armed()
        num_poison = self._num_poison
        self._num_labels = _numerics.group_names(
            num_groups, self._train_names)
        outer = self

        def window(keys, feeds, lrs, wds, train_vals, rest_vals, states,
                   residuals, poison):
            # per-shard program: feeds arrive batch-sharded, params and
            # optimizer state replicated; ONE collective per bucket per
            # scanned step synchronizes gradients across the mesh
            outer._scan_trace_count += 1  # host side: runs at trace only

            def micro(key, feed_vals, train_vals):
                def fwd(*tv):
                    full = [None] * n_args
                    for slot, v in zip(train_slots, tv):
                        full[slot] = v
                    for slot, v in zip(feed_slots, feed_vals):
                        full[slot] = v
                    for slot, v in zip(rest_slots, rest_vals):
                        full[slot] = v
                    return fn(key, tuple(full), ())

                (outs, new_aux), vjp_fn = jax.vjp(fwd, *train_vals)
                cts = tuple(jnp.ones_like(o) for o in outs)
                grads = vjp_fn((cts, ()))
                grads = [g.astype(w.dtype)
                         for g, w in zip(grads, train_vals)]
                return outs, grads

            def body(carry, xs):
                tv, st, res = carry
                res0 = res
                key_s, feed_s, lr_s, wd_s = xs
                grads_sum = None
                outs_micro = []
                for m in range(accum):
                    outs, grads = micro(
                        key_s[m, 0], tuple(f[m] for f in feed_s), tv)
                    outs_micro.append(outs)
                    grads_sum = grads if grads_sum is None else \
                        [a + b for a, b in zip(grads_sum, grads)]
                lr_row, wd_row = hyper_scalars(lr_s, wd_s, tv, st)
                if comm_on and layout == "fsdp":
                    new_params, new_states = fsdp_bucket_update(
                        opt, list(tv), grads_sum, list(st),
                        lr_row, wd_row, axes, plan, n_shards)
                else:
                    if comm_on and codec != "none":
                        grads_sum, res = compressed_bucket_all_reduce(
                            grads_sum, axes, plan, codec, threshold, res)
                    elif comm_on:
                        grads_sum = bucketed_all_reduce(
                            grads_sum, axes, plan)
                    if num_poison:
                        # poison AFTER the reduction: the reduced pytree
                        # is what the sentinel judges, codec or not
                        grads_sum = [g * poison.astype(g.dtype)
                                     for g in grads_sum]
                    with jax.named_scope("step/optimizer"):
                        new_params, new_states = opt.fused_update(
                            list(tv), grads_sum, list(st),
                            lr_row, wd_row)
                ys = tuple(jnp.stack([o[i] for o in outs_micro])
                           for i in range(len(outs_micro[0])))
                if num_mode != "off":
                    # stats from replicated values only (reduced grads,
                    # replicated params/states, pmean'd loss) — every
                    # rank computes identical numbers, so the stats
                    # output legally rides an out_spec of P()
                    new_params, (new_states, res), stats = \
                        _numerics.trace_step(
                            num_mode, grads_sum, [ys[0]], tv, new_params,
                            [(new_states, st), (res, res0)], num_groups,
                            axes=axes)
                    ys = ys + (stats,)
                return (tuple(new_params), new_states, res), ys

            carry, ys = jax.lax.scan(
                body, (train_vals, states, residuals),
                (keys, feeds, lrs, wds))
            tv, st, res = carry
            if num_mode != "off":
                stats = _numerics.window_param_stats(
                    ys[-1], tv, train_vals)
                return tv, st, res, ys[:-1], stats
            return tv, st, res, ys, ()

        batch_spec = P(None, None, axes)  # (K, M, B, ...), B sharded
        state_specs = jax.tree_util.tree_map(lambda _: P(),
                                             self._states_template)
        res_spec = P(axes)  # (n_shards, n): each rank its own residual
        in_specs = (batch_spec,                            # keys
                    tuple(batch_spec for _ in self._feed_order),
                    P(), P(),                              # lrs, wds
                    tuple(P() for _ in self._train_names),
                    tuple(P() for _ in self._rest_names),
                    state_specs,
                    tuple(res_spec for _ in self._residual_bufs),
                    P())                                   # poison scalar
        out_specs = (tuple(P() for _ in self._train_names),
                     state_specs,
                     tuple(res_spec for _ in self._residual_bufs),
                     tuple(batch_spec for _ in range(self._n_outs)),
                     # stats are computed from replicated values only
                     P() if num_mode != "off" else ())
        smapped = shard_map(window, mesh=self.mesh.jax_mesh,
                            in_specs=in_specs, out_specs=out_specs,
                            check_vma=False)
        # donate the carry (weights + optimizer state + codec
        # residuals): the window's final carry aliases them in place,
        # one buffer set per window
        self._scan_jit = jax.jit(smapped, donate_argnums=(4, 6, 7))
        self._comm_est_s = None

    # -- multi-process placement helpers ------------------------------------
    def _owned_or_copy(self, token, buf, sharding=None):
        """Ledger copy with multi-process-safe re-placement: a buffer
        not produced by our own last window (checkpoint restore, user
        set_params) is fully replicated host-side, so every process can
        rebuild the global replicated array from its own copy —
        ``jax.device_put`` cannot reach non-addressable devices."""
        if self._owned.get(token) is buf:
            return buf
        if sharding is not None and self.mesh.is_multiprocess:
            return self.mesh.put_replicated(np.asarray(buf))
        return super()._owned_or_copy(token, buf, sharding)

    def _place_rest(self, name, buf):
        """Non-trained, non-feed args ride replicated; on a multi-process
        mesh they are placed once and cached by source buffer."""
        if not self.mesh.is_multiprocess:
            return buf
        src, placed = self._rest_cache.get(name, (None, None))
        if src is not buf:
            placed = self.mesh.put_replicated(np.asarray(buf))
            self._rest_cache[name] = (buf, placed)
        return placed

    def _local_rows_of(self, y, W):
        """Re-assemble this process's addressable rows of a batch-
        sharded (K, M, B, ...) output into a host (W, B_local, ...)
        array (shards sorted by their batch offset)."""
        shards = sorted(y.addressable_shards,
                        key=lambda s: s.index[2].start or 0)
        local = np.concatenate([np.asarray(s.data) for s in shards],
                               axis=2)
        return local.reshape((W,) + tuple(local.shape[2:]))

    def _calibrate_comm(self):
        """Standalone cost of ONE scanned step's gradient collectives
        (zeros through the exact bucket program, timed best-of-3).
        Inside the fused window XLA overlaps these with backward
        compute; the standalone figure is the un-overlapped upper bound
        the ``comm_collective`` telemetry lane reports per step."""
        if self.comm_mode == "off" or not self._plan:
            self._comm_est_s = 0.0
            return 0.0
        exec_ = self._module._exec
        shapes = [tuple(exec_.arg_dict[n].shape)
                  for n in self._train_names]
        dtypes = [exec_.arg_dict[n]._data.dtype
                  for n in self._train_names]
        axes, plan = self._axes, self._plan

        def comm_only(grads):
            return tuple(bucketed_all_reduce(list(grads), axes, plan))

        smapped = shard_map(
            comm_only, mesh=self.mesh.jax_mesh,
            in_specs=(tuple(P() for _ in shapes),),
            out_specs=tuple(P() for _ in shapes), check_vma=False)
        jitted = jax.jit(smapped)
        zeros = tuple(self.mesh.put_replicated(np.zeros(s, np.dtype(str(d))))
                      for s, d in zip(shapes, dtypes))
        jax.block_until_ready(jitted(zeros))  # compile outside the clock
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(zeros))
            # graftlint: disable=raw-phase-timing -- one-shot calibration at trace time, not a per-step phase metric
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        self._comm_est_s = float(best)
        return self._comm_est_s

    def _post_dispatch(self, tv, st, res, ys):
        """Hook between the window dispatch and the first host read of
        its results; the multi-host subclass bounds the wait here."""

    def comm_seconds_per_step(self):
        """Calibrated standalone collective seconds per train step.
        Skipped (0.0) on a multi-process mesh: the calibration dispatch
        is an uncoordinated collective with an unbounded block — a peer
        dying mid-calibration would hang it (docs/parallel.md)."""
        if self.mesh.is_multiprocess:
            return 0.0
        if self._comm_est_s is None:
            self._calibrate_comm()
        return self._comm_est_s or 0.0

    # -- per-window host path ------------------------------------------------
    def run_window(self, sbatch):
        """Dispatch one K-step (x M micro-batch) window across the mesh.
        Same contract as ScanTrainStep.run_window: returns the flattened
        per-position output buffers (leading dim K*M) for the boundary
        metric flush, or False when the window is short or the stacked
        shapes don't match.  ``sbatch`` arrays are host numpy stacks
        (the fit loop stages mesh windows with ``host=True`` — one
        batch-sharded ``put_batch`` placement below instead of a full
        device_put here and a re-place there)."""
        from ..chaos.failpoints import failpoint as _failpoint
        module = self._module
        exec_ = module._exec
        K, M = self.scan_steps, self.accum
        W = K * M
        if sbatch.count != W:
            return False
        feed = {}
        for desc, arr in zip(module._data_shapes, sbatch.data):
            feed[desc.name] = arr
        if module._label_shapes and sbatch.label:
            for desc, arr in zip(module._label_shapes, sbatch.label):
                feed[desc.name] = arr
        for name, arr in feed.items():
            bound = exec_.arg_dict.get(name)
            if bound is None or \
                    tuple(arr.shape) != (W,) + tuple(bound.shape):
                return False

        opt = module._optimizer
        sig = (opt.fused_static_signature(), K, M, self._axes,
               tuple(self.mesh.axes.items()), self.layout,
               self.bucket_mb, self.comm_mode, self.codec,
               self.codec_threshold, self._numerics_sig(),
               tuple(sorted((n, tuple(a.shape), str(a.dtype))
                            for n, a in feed.items())))
        # stage the carry FIRST: the states template (structure + count)
        # is part of the trace signature inputs
        train_vals, aux_vals, states, states_nd = \
            self._stage_carry(self._repl)
        if self._scan_jit is None or sig != self._scan_sig:
            self._feed_order = sorted(feed)
            self._states_template = jax.tree_util.tree_map(
                lambda x: 0, states)
            self._n_outs = len(module.output_names)
            self._build_scan_jit()
            self._scan_sig = sig
            # resource observatory (ISSUE 13): re-state the mesh carry's
            # device footprint at each (re)build — params/opt-state plus
            # the mesh-specific gradient buckets and codec residuals
            from ..telemetry import resources as _resources
            _resources.account_train_step(
                "mesh_step", params=train_vals, opt_state=states,
                extra={"grad_buckets": self._grad_bytes,
                       "codec_residuals": _resources.pytree_nbytes(
                           list(self._residual_bufs))})

        # stacked feeds: (K, M, *bound), batch dim sharded over the mesh
        # (a multi-process mesh routes through put_batch, where each
        # process contributes only its local row block)
        feed_bufs = []
        for name in self._feed_order:
            buf = feed[name]
            bound = exec_.arg_dict[name]
            if buf.dtype != bound._data.dtype:
                buf = buf.astype(bound._data.dtype)
            buf = buf.reshape((K, M) + tuple(bound.shape))
            feed_bufs.append(self.mesh.put_batch(np.asarray(buf), 2))  # graftlint: disable=per-param-collective -- one resharding put per INPUT POSITION per window (2 for data+label), not per parameter

        rest_vals = tuple(self._place_rest(n, exec_.arg_dict[n]._data)
                          for n in self._rest_names)
        lrs, wds = host_hyperparams(opt, self._opt_indices, K)
        # one key per (micro forward, mesh rank): rank r consumes the
        # same counter stream as the r-th simulated device of the
        # sequential kvstore loop — bitwise-identical randomness
        keys = np.stack([np.asarray(_random.next_key())
                         for _ in range(W * self._n_shards)])
        keys = keys.reshape((K, M, self._n_shards) + keys.shape[1:])
        keys = self.mesh.put_batch(keys, 2)

        # the host-side window boundary: the chaos 'parallel/collective'
        # site arms delay/wedge/kill here, deterministically between the
        # last boundary's host control and this window's dispatch
        _failpoint("parallel/collective")

        residuals = tuple(self._residual_bufs)
        poison = _numerics.poison_value() if self._num_poison \
            else np.float32(1.0)
        with _telemetry.span("fit/step/mesh_dispatch"):
            if self._just_built:
                from .. import compile as _compile
                with _compile.LEDGER.attribute("mesh_step"):
                    tv, st, res, ys, stats = self._scan_jit(
                        keys, tuple(feed_bufs), lrs, wds,
                        train_vals, rest_vals, states, residuals,
                        poison)
                self._just_built = False
            else:
                tv, st, res, ys, stats = self._scan_jit(
                    keys, tuple(feed_bufs), lrs, wds,
                    train_vals, rest_vals, states, residuals, poison)
        _prof.record_dispatch("mesh_window")
        # coordination hook (parallel/elastic.py): a multi-host step
        # bounds the wait on the in-flight window HERE, before any host
        # read below could block unboundedly on a doomed collective
        self._post_dispatch(tv, st, res, ys)

        self._writeback_carry(tv, (), st, states_nd)
        self._residual_bufs = list(res)
        module._zero_grads()
        self._account_collectives(K)

        # (K, M, *out) -> (K*M, *out): position j is micro-batch j's
        # full-batch forward outputs, replicated back off the mesh for
        # the boundary metric flush.  On a multi-process mesh each
        # process re-assembles only its ADDRESSABLE batch rows (metrics
        # are per-host over the local shard; module slices labels to
        # the same rows via _mesh_local_rows).
        if self.mesh.is_multiprocess:
            outs_flat = [self._local_rows_of(y, W) for y in ys]
            module._mesh_local_rows = self.mesh.local_rows(
                exec_.arg_dict[self._feed_order[0]].shape[0])
        else:
            outs_flat = [y.reshape((W,) + tuple(y.shape[2:]))
                         for y in ys]
            module._mesh_local_rows = None
        exec_.outputs = [NDArray(y[W - 1], module._context)
                         for y in outs_flat]
        exec_._vjp_holder = None
        exec_._last_is_train = True
        self.steps += K
        self.windows += 1
        _prof.record_counter("train:fused_step_total", self.steps)
        if self._num_mode != "off":
            # boundary sentinel: every rank observes (per-rank families
            # ride the fleet push); stats are replicated, so all ranks
            # reach the same verdict — a halt halts the whole mesh
            _numerics.observe_window(
                stats, kind="mesh_window",
                first_step=self.steps - K + 1, window=self.windows,
                group_labels=self._num_labels)
        return outs_flat

    def _account_collectives(self, K):
        """Telemetry for one window: logical collective bytes by kind,
        plus the ``comm_collective`` step-lane share (reattributed out
        of the enclosing ``step_dispatch`` lane so the lane sum stays
        exact — the collectives execute inside the fused program and
        have no separately observable host wall time)."""
        if self.comm_mode == "off":
            return
        est = self.comm_seconds_per_step()
        if self.codec != "none":
            # compressed exchange: account the bytes that actually ride
            # the wire per rank under the ring schedule (2 bits/element
            # packed for 2bit, half-width for fp16) — the shrink the
            # MXNET_COLLECTIVE_COMPRESSION gate measures
            kind = ("all_gather_q2bit" if self.codec == "2bit"
                    else "psum_fp16")
            wire = codec_wire_bytes(self._grad_bytes, self._n_shards,
                                    self.codec)
            _telemetry.record_collective(kind, wire * K, est * K,
                                         len(self._plan) * K)
            return
        # dense collectives account the same per-rank ring-schedule wire
        # bytes as the compressed kinds (codec_wire_bytes), so the
        # compression ratio reads directly off mxnet_collective_bytes
        kind = "reduce_scatter" if self.layout == "fsdp" else "psum"
        r = self._n_shards
        half = int(self._grad_bytes * (r - 1) / max(1, r))
        dense = half if self.layout == "fsdp" else 2 * half
        _telemetry.record_collective(kind, dense * K,
                                     est * K, len(self._plan) * K)
        if self.layout == "fsdp":
            _telemetry.record_collective(
                "all_gather", half * K, 0.0, len(self._plan) * K)
        st = _telemetry.current_step_timer()
        if st.active and est:
            share = est * K
            st.add("comm_collective", share)
            st.add("step_dispatch", -share)


# -- CI smoke ----------------------------------------------------------------
def _mesh_models():
    import mxnet_tpu as mx

    def build():
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=64, name="fc1")
        h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    init = {"fc1_weight": mx.nd.array(rng.randn(64, 50) * 0.1),
            "fc1_bias": mx.nd.zeros((64,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 64) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}
    return build, init, rng


def _run_mesh_fit(K, NB, BS, opt_name, opt_params, build, init, x, y,
                  dp=2, tp=2):
    """Module.fit routed through the mesh fused window path, ONE epoch
    from ``init`` so the result is step-for-step comparable to an
    NB-step reference loop; returns (params, updater_states,
    dispatch_counts, module)."""
    import os

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio

    os.environ["MXNET_MESH_FUSED_STEP"] = "1"
    os.environ["MXNET_SCAN_STEPS"] = str(K)
    mx.random.seed(0)
    from .mesh import make_mesh
    mesh = make_mesh(dp=dp, tp=tp)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=BS,
                          label_name="softmax_label")
    mod = mx.mod.Module(build(), context=mx.cpu())
    with mesh:
        _prof.reset_dispatch_counts()
        mod.fit(it, num_epoch=1, optimizer=opt_name,
                optimizer_params=opt_params, kvstore="dist_device_sync",
                arg_params={k: v.copy() for k, v in init.items()})
        assert mod._mesh is not None, "mesh fused path did not engage"
    counts = _prof.dispatch_counts()
    params, _ = mod.get_params()
    states = {i: mod._updater.states[i]
              for i in range(len(mod._param_names))}
    return ({k: v.asnumpy() for k, v in params.items()},
            states, counts, mod)


def _run_kv_loop(NB, BS, n_shards, opt_name, opt_params, build, init,
                 x, y):
    """The sequential per-param kvstore loop this path replaces:
    n_shards simulated devices, per-shard forward/backward, one
    push + one pull PER PARAMETER per step, updater in-store."""
    import os

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio
    from mxnet_tpu import kvstore as kvs
    from mxnet_tpu import optimizer as opt_mod

    os.environ["MXNET_FUSED_STEP"] = "0"
    mx.random.seed(0)
    b = BS // n_shards
    mod = mx.mod.Module(build(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (b,) + x.shape[1:])],
             label_shapes=[("softmax_label", (b,))])
    mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
    opt = opt_mod.create(
        opt_name, rescale_grad=1.0 / BS,
        param_idx2name={i: n for i, n in enumerate(mod._param_names)},
        **dict(opt_params))
    kv = kvs.KVStore("device")
    kv.set_optimizer(opt)
    for n in mod._param_names:
        kv.init(n, mod._exec.arg_dict[n])
    for step in range(NB):
        xb = x[step * BS:(step + 1) * BS]
        yb = y[step * BS:(step + 1) * BS]
        grads = []
        for s in range(n_shards):
            batch = mxio.DataBatch(
                data=[mx.nd.array(xb[s * b:(s + 1) * b])],
                label=[mx.nd.array(yb[s * b:(s + 1) * b])])
            mod.forward(batch, is_train=True)
            mod.backward()
            grads.append({n: mod._exec.grad_dict[n].copy()
                          for n in mod._param_names})
            mod._zero_grads()
        for i, n in enumerate(mod._param_names):
            kv.push(n, [grads[s][n] for s in range(n_shards)],  # graftlint: disable=per-param-collective -- deliberately the sequential per-param reference the smoke proves parity against
                    priority=-i)
        for i, n in enumerate(mod._param_names):
            kv.pull(n, mod._exec.arg_dict[n], priority=-i)  # graftlint: disable=per-param-collective -- deliberately the sequential per-param reference the smoke proves parity against
    os.environ.pop("MXNET_FUSED_STEP", None)
    params = {n: mod._exec.arg_dict[n].asnumpy()
              for n in mod._param_names}
    states = {i: kv._updater.states[n]
              for i, n in enumerate(mod._param_names)}
    return params, states


def _state_arrays(state):
    out = []

    def _walk(s):
        if s is None:
            return
        if isinstance(s, (tuple, list)):
            for x in s:
                _walk(x)
            return
        out.append(np.asarray(s.asnumpy() if hasattr(s, "asnumpy")
                              else s))

    _walk(state)
    return out


def _require_devices(n):
    import sys
    if len(jax.devices()) < n:
        print(f"FAIL: mesh smoke needs {n} devices "
              f"(run under XLA_FLAGS=--xla_force_host_platform_"
              f"device_count={n})", file=sys.stderr)
        sys.exit(1)


def _smoke():
    """CI gate: an 8-fake-device dp=2,tp=2 Module.fit with a
    dist_device_sync kvstore must run 2 scanned windows as 2 dispatches
    (budget <= (1+eps)/K per step) and stay bitwise identical — weights
    AND optimizer state — to the sequential per-param kvstore loop."""
    import sys

    _require_devices(4)
    K, NB, BS = 8, 16, 32  # two full windows
    build, init, rng = _mesh_models()
    x = rng.randn(NB * BS, 50).astype(np.float32)
    y = rng.randint(0, 10, NB * BS).astype(np.float32)

    p_mesh, s_mesh, counts, _mod = _run_mesh_fit(
        K, NB, BS, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        build, init, x, y)
    p_loop, s_loop = _run_kv_loop(
        NB, BS, 4, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        build, init, x, y)

    per_step = counts.get("total", 0) / NB
    budget = (1 + 0.25) / K
    print(f"mesh K={K} dp=2 tp=2: {per_step:.3f} dispatches/step "
          f"{counts}; budget {budget:.3f}")
    if counts.get("mesh_window", 0) != NB // K:
        print("FAIL: mesh fused window did not engage", file=sys.stderr)
        sys.exit(1)
    if per_step > budget:
        print(f"FAIL: mesh path exceeds {budget:.3f} dispatches/step",
              file=sys.stderr)
        sys.exit(1)
    for k in p_loop:
        if not np.array_equal(p_mesh[k], p_loop[k]):
            print(f"FAIL: mesh/kvstore-loop parity broke on {k}",
                  file=sys.stderr)
            sys.exit(1)
    for i in s_loop:
        for a, b in zip(_state_arrays(s_mesh[i]),
                        _state_arrays(s_loop[i])):
            if not np.array_equal(a, b):
                print(f"FAIL: optimizer-state parity broke on index {i}",
                      file=sys.stderr)
                sys.exit(1)
    print(f"mesh smoke OK: <= {budget:.3f} dispatches/step at K={K} on "
          "dp=2 x tp=2, bitwise weights+optimizer-state parity with the "
          "per-param kvstore loop")


if __name__ == "__main__":
    _smoke()
