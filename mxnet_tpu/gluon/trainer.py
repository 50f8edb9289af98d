"""gluon.Trainer (parity: python/mxnet/gluon/trainer.py:27).

Applies an Optimizer on a set of Parameters. Reference flow: _allreduce_grads
via kvstore push/pull (trainer.py:356), then per-device fused updates
(trainer.py:399). Here the default single-chip path updates in place; with
multiple contexts the gradient reduction is an explicit cross-device mean
(kvstore='local'/'device' semantics); SPMD data parallelism over a mesh lives
in mxnet_tpu.parallel and plugs in through the same KVStore facade.
"""
from __future__ import annotations

from .. import optimizer as opt
from ..base import MXNetError
from ..ndarray import NDArray
from .parameter import Parameter


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
        (parity: trainer.py:399)."""
        import collections
        pending = collections.defaultdict(list)
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
                pending[j].append((i, grad, arr))
        agg = getattr(self._optimizer, "aggregate_num", 0)
        calls = 0
        for j, triples in pending.items():
            upd = self._updaters[j]
            if agg and len(triples) > 1:
                # multi-tensor dispatch: agg weights per updater call
                # (reference trainer.py batches when aggregate_num > 0)
                for k in range(0, len(triples), agg):
                    chunk = triples[k:k + agg]
                    upd([t[0] for t in chunk], [t[1] for t in chunk],
                        [t[2] for t in chunk])
                    calls += 1
            else:
                for i, grad, arr in triples:
                    upd(i, grad, arr)
                calls += len(triples)
        from .. import telemetry as _telemetry
        if _telemetry.enabled():
            _telemetry.record_trainer_update_calls(calls)

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
