"""SPMD training: functionalized gluon blocks + pjit over a DeviceMesh.

The reference's data-parallel train loop (SURVEY.md §3.4/3.5) moves gradients
through kvstore comm trees / ps-lite. Here the WHOLE train step — forward,
backward, gradient reduction, optimizer update — is one pjit'd XLA program:
batch sharded over 'dp', parameters replicated (or sharded over 'fsdp'),
gradient psum inserted by XLA over ICI. BatchNorm under a sharded batch
reduces globally (collectives), i.e. sync-BN semantics for free (the
reference needs a dedicated sync_batch_norm op, contrib/sync_batch_norm).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import random as _random
from ..base import MXNetError
from ..ndarray import NDArray
from ..ops import registry as _registry
from ..gluon.block import (KERNEL_RESIDUALS, kernel_residuals_traced,
                           remat_scope)
from .mesh import DeviceMesh


def host_cpu_scope():
    """Context manager pinning computation to the host CPU backend, or a
    no-op when the cpu platform is unavailable (e.g. JAX_PLATFORMS=tpu)."""
    import contextlib
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return contextlib.nullcontext()
    return jax.default_device(cpu)


class FunctionalizedBlock:
    """Pure-function view of an initialized HybridBlock.

    Unpacks as (apply_fn, param_arrays, param_names) for backward compat;
    also exposes ``mutated_idx()`` — the indices of params the forward
    mutates in place (BatchNorm running stats), available after the first
    (abstract or concrete) trace of ``apply_fn``.
    """

    def __init__(self, apply_fn, param_arrays, names, mutated_idx_box):
        self.apply_fn = apply_fn
        self.param_arrays = param_arrays
        self.names = names
        self._mutated_idx_box = mutated_idx_box

    def __iter__(self):
        return iter((self.apply_fn, self.param_arrays, self.names))

    def mutated_idx(self, example_inputs=None):
        """Indices into params of in-place-mutated (aux) arrays.

        Known only after a trace of apply_fn; pass ``example_inputs``
        (tuple of arrays/ShapeDtypeStructs) to (re)derive them with one
        abstract trace (jax.eval_shape — no compile, no device work) under
        the CURRENT train/predict mode.  Without example_inputs, returns
        whatever the last trace observed (mode-dependent: an inference
        trace legitimately mutates nothing).
        """
        if example_inputs is not None:
            # re-trace rather than trusting whichever mode traced first —
            # a prior inference trace would have latched [] and BN stats
            # would silently be fed through the optimizer
            del self._mutated_idx_box[:]
            key = jax.random.PRNGKey(0)
            jax.eval_shape(self.apply_fn, key, self.param_arrays,
                           tuple(example_inputs))
        return list(self._mutated_idx_box[0]) if self._mutated_idx_box else []

    def split_train_aux(self, example_inputs):
        """(train_idx, aux_idx): params the optimizer owns vs aux arrays the
        forward updates itself (BN running stats).  Derived with one
        train-mode abstract trace."""
        from .. import autograd as _ag
        with _ag.train_mode():
            aux = sorted(self.mutated_idx(example_inputs))
        aux_set = set(aux)
        train = [i for i in range(len(self.param_arrays))
                 if i not in aux_set]
        return train, aux


def merge_params(train_idx, aux_idx, train_params, aux_params):
    """Reassemble the full functionalize-order param tuple from the
    trainable/aux split (inverse of split_train_aux)."""
    full = [None] * (len(train_idx) + len(aux_idx))
    for i, w in zip(train_idx, train_params):
        full[i] = w
    for i, a in zip(aux_idx, aux_params):
        full[i] = a
    return tuple(full)


def functionalize(block, *example_args):
    """Turn an initialized HybridBlock into a pure function.

    Returns a FunctionalizedBlock unpacking as
    (apply_fn, param_arrays, param_names) with
    apply_fn(key, params_tuple, inputs_tuple) -> (outputs_tuple, mutated_tuple)
    — the functional core the reference's CachedOp wraps statefully.

    The deferred-init dry-run executes op-by-op; to avoid one device
    compile per op (fatal over a remote-compile TPU link) it runs on the
    host CPU backend with jit disabled — values are thrown away, only
    shapes matter.
    """
    from ..gluon.block import _flatten
    from .. import autograd

    # one imperative dry-run to finish deferred init — on the host CPU
    # backend when available, uncompiled either way
    needs = any(p._data is None for p in block.collect_params().values())
    if needs:
        with autograd.pause(), host_cpu_scope(), jax.disable_jit():
            block(*example_args)
    params = [p for p in block.collect_params().values()
              if p._data is not None]
    flat, fmt, _ = block._trace_signature(example_args)
    entry = block._build_jit(flat, fmt, params)
    raw = entry.raw
    names = [p.name for p in params]
    arrays = tuple(p.data()._data for p in params)
    return FunctionalizedBlock(raw, arrays, names, entry.mutated_idx_box)


def data_parallel_shardings(mesh, params, batch_axis="dp",
                            param_axis=None):
    """(param_sharding, batch_sharding) for plain DP or fsdp-style DP."""
    if param_axis is None:
        param_sh = mesh.replicated()
        param_shardings = tuple(param_sh for _ in params)
    else:
        # shard the largest axis of each parameter over param_axis when
        # divisible (zero/fsdp-style); small/indivisible params replicate
        n = mesh.size(param_axis)
        shardings = []
        for p in params:
            shape = p.shape
            best = None
            for i, s in enumerate(shape):
                if s % n == 0 and (best is None or s > shape[best]):
                    best = i
            if best is None:
                shardings.append(mesh.replicated())
            else:
                spec = [None] * len(shape)
                spec[best] = param_axis
                shardings.append(mesh.sharding(*spec))
        param_shardings = tuple(shardings)
    batch_sharding = mesh.sharding(batch_axis)
    return param_shardings, batch_sharding


def _placeable(array):
    """``(data, on_host)``: the array as ``jax.device_put`` should take it.

    An ``NDArray`` or a ``jax.Array`` already lives on a device and is
    resharded from there.  Anything else stays in host memory, cast there
    to the dtype ``jnp.asarray`` would give (float64 -> float32, int64 ->
    int32 without x64): ``device_put`` then slices it on the host and
    sends each device only its own rows, so nothing lands whole on one
    device to be sliced there and copied on."""
    if isinstance(array, NDArray):
        return array._data, False
    if isinstance(array, jax.Array):
        return array, False
    host = np.asarray(array)
    dtype = jax.dtypes.canonicalize_dtype(host.dtype)
    return (host if host.dtype == dtype else host.astype(dtype)), True


def shard_batch(mesh, array, axis="dp"):
    """Place a batch onto the mesh, sharded along its leading dim: a host
    array shard by shard from host memory, a device array resharded."""
    from .. import telemetry as _telemetry
    data, on_host = _placeable(array)
    _telemetry.record_spmd_batch_array("host" if on_host else "device")
    return jax.device_put(data, mesh.sharding(axis))


def replicate(mesh, array):
    """Place a copy of the array on every device of the mesh: from host
    memory to each device for a host array."""
    return jax.device_put(_placeable(array)[0], mesh.replicated())


# -- functional optimizers ---------------------------------------------------
def _opt_sgd(attrs):
    mom = float(attrs.get("momentum", 0.0))
    if mom == 0.0:
        fc = _registry.get("sgd_update").fcompute

        def init(w):
            return ()

        def update(attrs_, w, g, state):
            return fc(attrs_, w, g), ()
    else:
        fc = _registry.get("sgd_mom_update").fcompute

        def init(w):
            return (jnp.zeros_like(w),)

        def update(attrs_, w, g, state):
            new_w, new_m = fc(attrs_, w, g, state[0])
            return new_w, (new_m,)
    return init, update


def _opt_adam(attrs):
    fc = _registry.get("adam_update").fcompute

    def init(w):
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def update(attrs_, w, g, state):
        new_w, m, v = fc(attrs_, w, g, state[0], state[1])
        return new_w, (m, v)
    return init, update


def _opt_adamw(attrs):
    fc = _registry.get("adamw_update").fcompute

    def init(w):
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def update(attrs_, w, g, state):
        new_w, m, v = fc(attrs_, w, g, state[0], state[1])
        return new_w, (m, v)
    return init, update


_FUNCTIONAL_OPTS = {"sgd": _opt_sgd, "adam": _opt_adam, "adamw": _opt_adamw}


def _matmul_conv_saveable(prim, *_args, **_params):
    """Checkpoint policy: save matmul AND convolution outputs, recompute
    everything else (elementwise/norm chains) in backward. The built-in
    dots_with_no_batch_dims_saveable covers only dot_general — useless
    for conv nets, which would recompute the entire forward."""
    return getattr(prim, "name", "") in ("dot_general",
                                         "conv_general_dilated")


_MIRROR_POLICY = jax.checkpoint_policies.save_from_both_policies(
    _matmul_conv_saveable,
    jax.checkpoint_policies.save_only_these_names(*KERNEL_RESIDUALS))


def remat_wrap(fwd):
    """Wrap a forward fn with rematerialization (parity:
    MXNET_BACKWARD_DO_MIRROR, src/nnvm/gradient.cc mirror fn): activation
    memory shrinks to the matmul/conv outputs and the flash and KDA
    kernels' named outputs (``gluon.block.KERNEL_RESIDUALS``, so their
    forward kernels are not run again); elementwise
    intermediates are recomputed during backward."""
    return jax.checkpoint(fwd, policy=_MIRROR_POLICY)


class TrainStep:
    """One compiled SPMD train step for a gluon block.

    Usage:
        mesh = make_mesh(dp=8)
        step = TrainStep(net, loss_fn, "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9},
                         mesh, example_batch=(x, y))
        for x, y in data:
            loss = step(x, y)        # params/opt state live sharded on device

    With a third example array, ``example_batch=(x, y, w)``, every call is
    ``step(x, y, w)`` and ``w`` reaches a Gluon loss as its
    ``sample_weight`` (a weight a position, zero where a position carries
    no loss), sharded like the other two.

    The whole step is ONE pjit'd XLA program; gradient reduction over 'dp'
    and (with param_axis='fsdp') parameter all-gathers are XLA collectives.
    """

    def __init__(self, block, loss_fn, optimizer, optimizer_params, mesh,
                 example_batch, batch_axis="dp", param_axis=None,
                 dtype=None, remat=None, bucket_mb=None):
        """remat: rematerialize the forward during backward, trading
        FLOPs for activation memory (parity: MXNET_BACKWARD_DO_MIRROR,
        src/nnvm/gradient.cc mirror fn). None reads the env var; True
        wraps the forward in jax.checkpoint with a policy keeping matmul
        AND conv outputs (elementwise recomputed) — the standard recipe
        for large-batch training that would otherwise spill HBM.  A block
        that declares ``remat_layers`` (a sequence model's decoder
        layers) gets a boundary per layer instead: only each layer's
        input and its flash kernels' ``out`` and ``lse`` are kept, and the
        rest of the layer is computed again in its backward.
        ``remat_boundaries`` holds how many boundaries the step program
        was traced with (0 until its first trace, and without remat).

        bucket_mb: when set, the step compiles as an EXPLICIT shard_map
        program whose gradient reduction is one psum per bucket_mb-sized
        flat bucket (parallel/fused.bucketed_all_reduce) instead of the
        pjit-inserted per-tensor psums — the collective count drops from
        one-per-param to ceil(total_MB/bucket_MB) and XLA can overlap
        each bucket with remaining backward compute.  Requires
        replicated params (param_axis=None) and a block without
        in-place-mutated aux (BatchNorm keeps the pjit path)."""
        from .. import autograd as _ag

        if remat is None:
            from ..config import get as _cfg
            remat = bool(_cfg("MXNET_BACKWARD_DO_MIRROR"))
        self.remat = bool(remat)
        self.remat_boundaries = 0

        if not isinstance(mesh, DeviceMesh):
            raise MXNetError("mesh must be a parallel.DeviceMesh")
        self.mesh = mesh
        self.block = block
        x_ex, _y_ex, *w_ex = example_batch
        # how many batch arrays a call takes: data, label and, where the
        # example had one, the loss's sample weight
        self._n_batch = 2 + len(w_ex)
        if len(w_ex) > 1 or (w_ex and
                             not hasattr(loss_fn, "hybrid_forward")):
            raise MXNetError(
                "example_batch is (data, label) or (data, label, "
                "sample_weight), the last for a gluon loss")
        fb = functionalize(block, x_ex)
        apply_fn, param_arrays, names = fb
        if dtype is not None:
            param_arrays = tuple(a.astype(dtype) if
                                 jnp.issubdtype(a.dtype, jnp.floating) else a
                                 for a in param_arrays)
        self._apply = apply_fn
        self.param_names = names

        # discover aux params (BatchNorm running stats — mutated in-place by
        # the forward) with ONE abstract trace in train mode: no compile.
        x_sds = jax.ShapeDtypeStruct(tuple(x_ex.shape), np.dtype(x_ex.dtype))
        self._train_idx, self._aux_idx = fb.split_train_aux((x_sds,))

        lr = float(optimizer_params.get("learning_rate", 0.01))
        self._opt_attrs = {"lr": lr,
                           "wd": float(optimizer_params.get("wd", 0.0)),
                           "rescale_grad": 1.0}
        for k in ("momentum", "beta1", "beta2", "epsilon", "clip_gradient"):
            if k in optimizer_params:
                self._opt_attrs[k] = optimizer_params[k]
        if optimizer not in _FUNCTIONAL_OPTS:
            raise MXNetError(
                f"functional optimizer {optimizer!r} not available "
                f"(options: {sorted(_FUNCTIONAL_OPTS)}); use gluon.Trainer "
                "for the imperative path")
        opt_init, opt_update = _FUNCTIONAL_OPTS[optimizer](self._opt_attrs)
        self._opt_update = opt_update

        # shardings (param_axis='fsdp' shards the largest divisible dim)
        param_sh, batch_sh = data_parallel_shardings(
            mesh, [type("S", (), {"shape": a.shape})() for a in param_arrays],
            batch_axis, param_axis)
        self._param_sh = param_sh
        self._batch_sh = batch_sh
        train_sh = tuple(param_sh[i] for i in self._train_idx)
        aux_sh = tuple(param_sh[i] for i in self._aux_idx)

        # place params + opt state on the mesh (opt state only for
        # trainable params — the round-1 bug fed BN stats through SGD)
        self._train_params = tuple(
            jax.device_put(param_arrays[i], param_sh[i])
            for i in self._train_idx)
        self._aux_params = tuple(
            jax.device_put(param_arrays[i], param_sh[i])
            for i in self._aux_idx)
        self.opt_state = tuple(
            tuple(jax.device_put(s, sh) for s in opt_init(a))
            for a, sh in zip(self._train_params, train_sh))

        def loss_raw(pred, label, *weight):
            if hasattr(loss_fn, "hybrid_forward"):
                from ..context import current_context
                l = loss_fn(*(NDArray(a, current_context())
                              for a in (pred, label, *weight)))
                return l._data.mean()
            return loss_fn(pred, label)

        opt_attrs = dict(self._opt_attrs)
        train_idx = list(self._train_idx)
        aux_idx = list(self._aux_idx)

        from .. import telemetry as _telemetry
        # the layers the block declares as rematerialisation boundaries;
        # a block that declares none has its whole forward wrapped
        remat_layers = tuple(getattr(block, "remat_layers", ())) \
            if self.remat else ()
        whole_remat = self.remat and not remat_layers

        def make_step(grad_sync):
            def step(key, train_params, aux_params, opt_state, x, y, *w):
                def fwd(tps, x_):
                    ps = merge_params(train_idx, aux_idx, tps, aux_params)
                    named = kernel_residuals_traced()
                    with _ag.train_mode(), remat_scope(remat_layers) as sc:
                        outs, mutated = apply_fn(key, ps, (x_,))
                    # facts about the program: taken as it is traced
                    self.remat_boundaries = sc.boundaries or int(whole_remat)
                    saved = sc.saved_residuals or whole_remat * (
                        kernel_residuals_traced() - named)
                    _telemetry.record_remat_boundaries(
                        self.remat_boundaries, saved)
                    return outs[0], mutated

                if whole_remat:
                    fwd = remat_wrap(fwd)

                def compute_loss(tps):
                    pred, mutated = fwd(tps, x)
                    with jax.named_scope("step/loss"):
                        return loss_raw(pred, y, *w), mutated

                (loss, mutated), grads = jax.value_and_grad(
                    compute_loss, has_aux=True)(train_params)
                if grad_sync is not None:
                    with jax.named_scope("step/grad_sync"):
                        grads, loss = grad_sync(list(grads), loss)
                new_params = []
                new_state = []
                # the functional optimizers call the update ops' fcompute
                # themselves, past the registry's ``op/<name>`` scope
                with jax.named_scope("step/optimizer"):
                    for w, g, st in zip(train_params, grads, opt_state):
                        nw, ns = opt_update(opt_attrs, w, g, st)
                        new_params.append(nw)
                        new_state.append(ns)
                # mutated comes back in ascending-param-index order == aux
                # order; write the new running stats into the aux slot
                # (round-1 dropped them: inference-mode BN saw frozen
                # stats forever)
                new_aux = tuple(m.astype(a.dtype) for m, a in
                                zip(mutated, aux_params)) if mutated \
                    else aux_params
                return tuple(new_params), new_aux, tuple(new_state), loss
            return step

        state_sh = tuple(tuple(sh for _ in st)
                         for st, sh in zip(self.opt_state, train_sh))
        self.bucket_mb = bucket_mb
        if bucket_mb is None:
            # one pjit'd program: params/opt state pinned to their
            # shardings and DONATED (no 2x HBM), batch arrives dp-sharded;
            # XLA inserts the dp psum for grads and fsdp all-gathers
            self._step = jax.jit(
                make_step(None),
                in_shardings=(None, train_sh, aux_sh, state_sh)
                + (batch_sh,) * self._n_batch,
                donate_argnums=(1, 2, 3))
        else:
            # explicit-collective formulation: the same step body runs as
            # the per-shard program of a shard_map, and gradient sync is
            # ONE psum per flat bucket.  The per-shard grads are of the
            # LOCAL mean loss, so the bucketed global sum divides by the
            # shard count to match the pjit global-mean gradients.
            if param_axis is not None:
                raise MXNetError(
                    "bucket_mb requires replicated parameters "
                    "(param_axis=None); fsdp-style sharding keeps the "
                    "pjit formulation")
            if self._aux_idx:
                raise MXNetError(
                    "bucket_mb: blocks with in-place-mutated aux "
                    "(BatchNorm running stats) keep the pjit path — "
                    "per-shard aux would need sync-BN semantics")
            from jax import shard_map
            from .fused import bucketed_all_reduce, plan_buckets
            t_shapes = [tuple(param_arrays[i].shape)
                        for i in self._train_idx]
            t_dtypes = [str(param_arrays[i].dtype)
                        for i in self._train_idx]
            self._bucket_plan = plan_buckets(t_shapes, t_dtypes, bucket_mb)
            n_dp = mesh.size(batch_axis)
            plan = self._bucket_plan

            def grad_sync(grads, loss):
                grads = bucketed_all_reduce(grads, batch_axis, plan)
                return [g / n_dp for g in grads], \
                    jax.lax.psum(loss, batch_axis) / n_dp

            state_spec = tuple(tuple(P() for _ in st)
                               for st in self.opt_state)
            smapped = shard_map(
                make_step(grad_sync), mesh=mesh.jax_mesh,
                in_specs=(P(), tuple(P() for _ in self._train_idx),
                          tuple(P() for _ in self._aux_idx), state_spec)
                + (P(batch_axis),) * self._n_batch,
                out_specs=(tuple(P() for _ in self._train_idx),
                           tuple(P() for _ in self._aux_idx),
                           state_spec, P()),
                check_vma=False)
            self._step = jax.jit(smapped, donate_argnums=(1, 2, 3))

    @property
    def params(self):
        """Full parameter tuple (trainable + aux) in functionalize order."""
        return merge_params(self._train_idx, self._aux_idx,
                            self._train_params, self._aux_params)

    def __call__(self, x, y, w=None):
        """Run one step on ``(x, y)`` or, for a step built with a third
        example array, ``(x, y, sample_weight)``; returns scalar loss
        (host float on .item())."""
        from .. import telemetry as _telemetry
        batch = (x, y) if w is None else (x, y, w)
        if len(batch) != self._n_batch:
            raise MXNetError(f"this step takes {self._n_batch} batch "
                             f"arrays, {len(batch)} given")
        _telemetry.next_step()   # the spans below share this step's id
        with _telemetry.span("spmd/step"):
            with _telemetry.span("spmd/step/shard_batch"):
                placed = tuple(a if isinstance(a, jax.Array)
                               else shard_batch(self.mesh, a) for a in batch)
                _telemetry.record_io_stage_bytes(sum(
                    s.nbytes for a, s in zip(batch, placed) if s is not a))
                if isinstance(w, np.ndarray):
                    _telemetry.record_loss_weights(w)
            with _telemetry.span("spmd/step/prepare"):
                args = (_random.next_key(), self._train_params,
                        self._aux_params, self.opt_state, *placed)
                host_args = _telemetry.host_arg_stats(
                    args, set(self.mesh.jax_mesh.devices.flat)) \
                    if _telemetry.enabled() else None
            with _telemetry.span("spmd/step/dispatch"):
                _telemetry.record_step_host_args("spmd", host_args)
                with self.mesh.jax_mesh:
                    (self._train_params, self._aux_params, self.opt_state,
                     loss) = self._step(*args)
            # the donated parameters and state die here, inside the span,
            # not at the return: a few hundred buffers' worth of time
            del args
        return loss

    def sync_to_block(self):
        """Write the trained parameters (and BN stats) back into the block."""
        for name, arr in zip(self.param_names, self.params):
            p = self.block.collect_params()[name]
            d = p.data()
            d._set_data(jnp.asarray(arr, dtype=d.dtype))

    # -- checkpointing (mxnet_tpu.checkpoint integration) -------------------
    def state_dict(self):
        """{name: jax.Array} of the full training state, still sharded on
        the mesh: ``param:<name>`` for every parameter (trainable + aux)
        and ``opt:<name>:<j>`` per optimizer-state slot.  The checkpoint
        manager snapshots each array shard-wise, so every host saves only
        the shards it owns."""
        d = {}
        for name, arr in zip(self.param_names, self.params):
            d[f"param:{name}"] = arr
        for i, st in zip(self._train_idx, self.opt_state):
            name = self.param_names[i]
            for j, s in enumerate(st):
                d[f"opt:{name}:{j}"] = s
        return d

    def save_checkpoint(self, manager, step, block=None, extra=None):
        """Checkpoint params + optimizer state + step through a
        checkpoint.CheckpointManager (async by default: the train loop
        blocks only for the device->host shard snapshot)."""
        return manager.save(step, arrays=self.state_dict(),
                            mesh=self.mesh, extra=extra, block=block)

    def load_state_dict(self, arrays):
        """Install a restored state dict ({name: host np.ndarray}) onto
        THIS TrainStep's mesh — the elastic half of restore: the arrays
        were assembled from whatever dp×tp×pp layout saved them, and are
        re-sharded here onto the current layout bit-identically."""
        def _take(key, like, sharding):
            arr = arrays.get(key)
            if arr is None:
                raise MXNetError(f"checkpoint is missing tensor {key!r}")
            if tuple(arr.shape) != tuple(like.shape):
                raise MXNetError(
                    f"checkpoint tensor {key!r} has shape {arr.shape}, "
                    f"expected {tuple(like.shape)}")
            return jax.device_put(arr.astype(like.dtype), sharding)

        new_train, new_aux, new_state = [], [], []
        for k, i in enumerate(self._train_idx):
            name = self.param_names[i]
            w = _take(f"param:{name}", self._train_params[k],
                      self._param_sh[i])
            new_train.append(w)
            st = []
            for j, s in enumerate(self.opt_state[k]):
                st.append(_take(f"opt:{name}:{j}", s, self._param_sh[i]))
            new_state.append(tuple(st))
        for k, i in enumerate(self._aux_idx):
            name = self.param_names[i]
            new_aux.append(_take(f"param:{name}", self._aux_params[k],
                                 self._param_sh[i]))
        self._train_params = tuple(new_train)
        self._aux_params = tuple(new_aux)
        self.opt_state = tuple(new_state)

    def restore_checkpoint(self, source, step=None):
        """Restore from a CheckpointManager or a checkpoint directory
        saved by ANY mesh layout; returns the Checkpoint (step,
        metadata).  Params + optimizer state land re-sharded onto this
        TrainStep's mesh."""
        if hasattr(source, "restore"):
            ckpt = source.restore(step)
        else:
            from ..checkpoint import restore as _restore
            ckpt = _restore(str(source), step=step)
        self.load_state_dict(ckpt.arrays)
        return ckpt
