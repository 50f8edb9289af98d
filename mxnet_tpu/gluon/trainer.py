"""gluon.Trainer (parity: python/mxnet/gluon/trainer.py:27).

Applies an Optimizer on a set of Parameters. Reference flow: _allreduce_grads
via kvstore push/pull (trainer.py:356), then per-device fused updates
(trainer.py:399). Here the default single-chip path updates in place; with
multiple contexts the gradient reduction is an explicit cross-device mean
(kvstore='local'/'device' semantics); SPMD data parallelism over a mesh lives
in mxnet_tpu.parallel and plugs in through the same KVStore facade.

The update is ONE jitted program per context and step: ``Optimizer.
fused_update`` over every parameter of the step together, under the scope
``step/optimizer``, with the step's learning rates and weight decays as two
host ``float32`` arrays (``fused_step.host_hyperparams``).  A program is
built once per key and kept on the trainer; the key is
``optimizer.fused_static_signature()`` (what ``fused_update`` bakes in:
``rescale_grad``, ``clip_gradient``, ``multi_precision``, momentum or the
betas), the indices of the parameters taking part, and the structure of
their states.  A learning rate, a weight decay or a scheduler's step is an
argument and builds nothing.  The states stay ``Updater.states``, index by
index; their buffers are donated, the weights' are not.  Which path a tensor
takes is read from the step itself: the per-tensor updater call keeps a
``RowSparseNDArray`` gradient, and every tensor of an optimizer without a
``fused_update`` that states its current per-tensor update (NAG, RMSProp,
LBSGD; Adam under ``multi_precision``; a subclass that overrides ``update``
below the class that wrote ``fused_update``).  A parameter with
``grad_req='null'``, a stale gradient or ``update_on_kvstore`` takes part in
neither, as before.
"""
from __future__ import annotations

from .. import optimizer as opt
from ..base import MXNetError
from ..ndarray import NDArray
from .parameter import Parameter


def _fused_update_is_current(optimizer):
    """Whether ``optimizer.fused_update`` states the update the optimizer
    makes per tensor: it exists, it implements the master-weight wrapper
    if ``multi_precision`` asks for one, and no subclass below the class
    that wrote it has overridden ``update`` or ``update_multi_precision``
    since (a user's ``class MySGD(SGD)`` with its own ``update`` inherits
    a ``fused_update`` that no longer says what it does)."""
    if not callable(getattr(optimizer, "fused_update", None)):
        return False
    if optimizer.multi_precision and not optimizer.fused_multi_precision:
        return False
    mro = type(optimizer).__mro__

    def depth(name):
        return next(k for k, c in enumerate(mro) if name in vars(c))
    return depth("fused_update") <= min(depth("update"),
                                        depth("update_multi_precision"))


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        param_list = []
        if isinstance(params, (dict,)) or hasattr(params, "items"):
            for key in sorted(list(params.keys())):
                param_list.append(params[key])
            params = param_list
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            self._param2idx[param.name] = i
            self._params.append(param)
            param._trainer = self
        self._compression_params = compression_params
        optimizer_params = optimizer_params if optimizer_params else {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._contexts = self._check_contexts()
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_params = {
            "kvstore": kvstore, "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._distributed = None
        # grad-version bookkeeping for the stale-gradient check
        # (parity: Parameter._fresh_grad in reference trainer.py:408-428)
        self._last_grad_version = {}
        self._reset_kvstore()

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx() if param._data is not None or \
                param._deferred_init else None
            if ctx is None:
                continue
            assert contexts is None or contexts == ctx, \
                (f"All Parameters must be initialized on the same set of "
                 f"contexts, but Parameter {param.name} is initialized on "
                 f"{ctx} while previous Parameters are initialized on "
                 f"{contexts}.")
            contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer " \
                "instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)
                          for _ in self._contexts] or \
            [opt.get_updater(self._optimizer)]
        self._one_program = _fused_update_is_current(self._optimizer)
        # (fused_static_signature, parameter indices, state structure)
        # -> jitted update; see _update_in_one_program
        self._update_programs = {}

    def _reset_kvstore(self):
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None

    def _init_kvstore(self):
        """Create the kvstore (parity: trainer.py:169 _init_kvstore)."""
        config = self._kvstore_params
        kvstore = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        if kvstore and not isinstance(kvstore, str):
            self._kvstore = kvstore
            self._distributed = "dist" in kvstore.type
        elif kvstore and ("dist" in kvstore or len(self._contexts) > 1):
            # dist stores must be created even on a single-device worker —
            # otherwise multi-worker training silently never synchronizes
            # (parity: model.py _create_kvstore creates dist stores
            # regardless of device count)
            from .. import kvstore as kvs_mod
            self._kvstore = kvs_mod.create(kvstore)
            self._distributed = "dist" in self._kvstore.type
        else:
            self._kvstore = None
            self._distributed = False
        if self._kvstore is not None and update_on_kvstore:
            self._kvstore.set_optimizer(self._optimizer)
            self._update_on_kvstore = True
        else:
            self._update_on_kvstore = False
        if self._kvstore is not None:
            for i, param in enumerate(self._params):
                if param._data is not None:
                    self._kvstore.init(i, param.list_data()[0])
        self._kv_initialized = True

    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning(
                "Optimizer has to be defined before its learning rate can be "
                "accessed.")
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning(
                "Optimizer has to be defined before its learning rate is "
                "mutated.")
        self._optimizer.set_learning_rate(lr)

    def _row_sparse_pull(self, parameter, out, row_id, full_idx=False):
        """Pull only the rows named by row_id for a sparse parameter
        (parity: trainer.py _row_sparse_pull → kvstore.row_sparse_pull)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is None:
            raise MXNetError(
                "row_sparse parameters require a kvstore; create the "
                "Trainer with kvstore='local' (or a dist store)")
        i = self._param2idx[parameter.name]
        self._kvstore.row_sparse_pull(i, out=out, row_ids=row_id,
                                      priority=-i)

    def step(self, batch_size, ignore_stale_grad=False):
        """Make one parameter update step: rescale, allreduce, update
        (parity: trainer.py:305).  With amp.init_trainer attached, the
        gradient rescale folds in the loss scale and the update is skipped
        (scale halved) on inf/nan gradients — reference amp step contract."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None:
            # check BEFORE allreduce: with update_on_kvstore the push
            # itself applies the update server-side — inf/nan must never
            # reach the store
            grads = [g for p in self._params
                     if p.grad_req != "null" and p._grad is not None
                     for g in p.list_grad()]
            overflow = scaler.has_overflow(grads)
            scaler.update_scale(overflow)
            if overflow:
                return  # skip push + update entirely (reference semantics)
        from .. import telemetry as _telemetry
        with _telemetry.span("gluon/trainer/allreduce"):
            self._allreduce_grads()
        with _telemetry.span("gluon/trainer/update"):
            self._update(ignore_stale_grad)
        # a step ends here: forward, backward and this update of one
        # batch share the step id of their span records
        _telemetry.next_step()

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        assert not (self._kvstore and self._update_on_kvstore), \
            "allreduce_grads() when parameters are updated on kvstore " \
            "is not supported. Try setting `update_on_kvstore` to False " \
            "when creating trainer."
        self._allreduce_grads()

    def _allreduce_grads(self):
        """Sum gradients across contexts (parity: trainer.py:356)."""
        if self._kvstore is not None:
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.push(i, param.list_grad(), priority=-i)
                    if self._update_on_kvstore:
                        # optimizer ran in-store (server side for dist):
                        # pull the updated weights back unconditionally
                        # here — not in _update, where the stale-grad
                        # `continue` would skip it and workers would drift
                        # from the server (parity: trainer.py:418-423)
                        self._kvstore.pull(i, param.list_data(), priority=-i)
                    else:
                        self._kvstore.pull(i, param.list_grad(), priority=-i,
                                           ignore_sparse=self._distributed)
            return
        if len(self._contexts) <= 1:
            return
        from .. import ndarray as nd
        for param in self._params:
            if param.grad_req == "null" or param._grad is None:
                continue
            grads = param.list_grad()
            ctx0 = grads[0].ctx
            total = nd.add_n(*[g.as_in_context(ctx0) for g in grads])
            for g in grads:
                g[:] = total.as_in_context(g.ctx)

    def update(self, batch_size, ignore_stale_grad=False):
        """Make one update step (when autograd was used with custom reduce)."""
        if not self._kv_initialized:
            self._init_kvstore()
        assert not (self._kvstore and self._update_on_kvstore), \
            "update() when parameters are updated on kvstore is not " \
            "supported. Try setting `update_on_kvstore` to False when " \
            "creating trainer."
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        """Run the optimizer on every (param, ctx) pair
        (parity: trainer.py:399): one program per context for the pairs
        ``_one_program`` takes, the per-tensor updater call for the rest."""
        from ..ndarray.sparse import BaseSparseNDArray
        batched = [[] for _ in self._updaters]
        calls = 0
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            if not ignore_stale_grad:
                versions = tuple(g.version for g in param.list_grad())
                if self._last_grad_version.get(i) == versions:
                    import warnings
                    warnings.warn(
                        f"Gradient of Parameter `{param.name}` on context "
                        f"{param.list_ctx()} has not been updated by backward "
                        "since last `step`. This could mean a bug in your "
                        "model that made it only use a subset of the "
                        "Parameters for this iteration. If you are "
                        "intentionally only using a subset, call step with "
                        "ignore_stale_grad=True to suppress this warning and "
                        "skip updating of Parameters with stale gradient",
                        stacklevel=3)
                    continue
                self._last_grad_version[i] = versions
            if self._kvstore and self._update_on_kvstore:
                continue  # weights already pulled in _allreduce_grads
            for j, (upd, arr, grad) in enumerate(
                    zip(self._updaters, param.list_data(),
                        param.list_grad())):
                if self._one_program and \
                        not isinstance(grad, BaseSparseNDArray):
                    batched[j].append((i, grad, arr))
                else:
                    upd(i, grad, arr)
                    calls += 1
        for upd, triples in zip(self._updaters, batched):
            if triples:
                self._update_in_one_program(upd, triples)
                calls += 1
        from .. import telemetry as _telemetry
        if _telemetry.enabled():
            _telemetry.record_trainer_update_calls(calls)

    def _update_in_one_program(self, updater, triples):
        """Update every ``(index, grad, weight)`` of one context in ONE
        jitted call of ``Optimizer.fused_update``.

        The states are the ``updater.states`` the per-tensor call keeps
        (``save_states``/``load_states`` see no difference); their buffers
        are donated.  The weights are not: the pullback residuals of the
        step just walked still hold them."""
        import jax
        from .. import engine, profiler
        from ..fused_step import host_hyperparams
        opt = self._optimizer
        indices, grads, weights = zip(*triples)
        for i, w in zip(indices, weights):
            updater._ensure_state(i, w)
        states, structure = jax.tree_util.tree_flatten(
            [updater.states[i] for i in indices])
        key = (opt.fused_static_signature(), indices, structure)
        program = self._update_programs.get(key)
        if program is None:
            program = self._update_programs[key] = \
                self._build_update_program(structure)
        lrs, wds = host_hyperparams(opt, indices)
        new_weights, new_states = program(
            [w._data for w in weights], [g._data for g in grads],
            [s._data for s in states], lrs, wds)
        for arr, buf in zip(weights, new_weights):
            arr._set_data(buf)
        for arr, buf in zip(states, new_states):
            arr._set_data(buf)
        profiler.record_dispatch("trainer_update")
        engine.get().on_compute(weights)

    def _build_update_program(self, structure):
        """``update(weights, grads, states, lrs, wds)`` for one key of
        ``_update_in_one_program``; ``states`` are the leaves of
        ``structure``.  What ``fused_update`` bakes in as constants is
        the key's ``fused_static_signature``; the rates and decays are two
        array arguments, so a schedule builds nothing."""
        import jax
        from .. import compile as _compile
        from ..fused_step import hyper_scalars
        _compile.ensure_persistent_cache()
        _compile.record_trace(
            "gluon_trainer_update",
            "key-change" if self._update_programs else "build")
        opt = self._optimizer

        def update(weights, grads, states, lrs, wds):
            states = jax.tree_util.tree_unflatten(structure, states)
            with jax.named_scope("step/optimizer"):
                new_weights, new_states = opt.fused_update(
                    weights, grads, states,
                    *hyper_scalars(lrs, wds, weights, states))
            return new_weights, jax.tree_util.tree_leaves(new_states)

        return jax.jit(update, donate_argnums=(2,))

    def save_states(self, fname):
        """Save optimizer/updater states (parity: trainer.py save_states).

        Atomic temp + os.replace: the states file is a durable restart
        artifact and must never be observable half-written.
        """
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        import os
        tmp = f"{fname}.tmp-{os.getpid()}"
        with open(tmp, "wb") as fout:
            fout.write(self._updaters[0].get_states(dump_optimizer=False))
        os.replace(tmp, fname)

    def load_states(self, fname):
        """Load optimizer/updater states (parity: trainer.py load_states)."""
        if not self._kv_initialized:
            self._init_kvstore()
        with open(fname, "rb") as f:
            states = f.read()
        for updater in self._updaters:
            updater.set_states(states)
            updater.optimizer = self._optimizer
