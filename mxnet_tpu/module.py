"""Module API: symbolic training interface.

Re-design of reference python/mxnet/module/ (BaseModule.fit:409, Module:364
over DataParallelExecutorGroup, BucketingModule). Each Module owns one
Executor per context; forward/backward run the whole compiled graph (the
per-node engine pushes + bulking of graph_executor.cc collapse into one XLA
program per signature). Batches bigger than one context are split along the
batch axis (DataParallelExecutorGroup._load_data semantics).
"""
from __future__ import annotations

import logging
import os
import time

import numpy as np

from . import io as mx_io
from . import metric as metric_mod
from . import ndarray as nd
from . import optimizer as opt_mod
from . import telemetry as _telemetry
from .base import (MXNetError, NonFiniteError, PeerLostError,
                   PreemptionError)
from .context import cpu
from .initializer import Uniform
from .model import (BatchEndParam, load_checkpoint, save_checkpoint,
                    _create_kvstore, _initialize_kvstore,
                    _update_params_on_kvstore)
from .ndarray import NDArray


class BaseModule:
    """Base class defining the Module API (parity: module/base_module.py)."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self.inputs_need_grad = False
        self._symbol = None

    # -- high-level train/eval loops ---------------------------------------
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None,
            kvstore="local", optimizer="sgd",
            optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None):
        """Train the module (parity: base_module.py:409 fit)."""
        assert num_epoch is not None, "please specify number of epochs"
        if initializer is None:
            initializer = Uniform(0.01)
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        stager = mx_io.make_batch_stager(getattr(self, "_context", None))
        # step-time breakdown (telemetry lanes) + hang watchdog: both are
        # shared no-ops unless MXNET_TELEMETRY / MXNET_WATCHDOG_S arm them
        timeline = _telemetry.step_timer()
        wdog = _telemetry.watchdog
        try:
            self._fit_epochs(train_data, eval_data, eval_metric,
                             validation_metric, monitor, stager, timeline,
                             wdog, epoch_end_callback, batch_end_callback,
                             eval_end_callback, eval_batch_end_callback,
                             begin_epoch, num_epoch)
        except (PeerLostError, PreemptionError) as e:
            # the elastic self-heal hook: a lost peer / preemption
            # notice surfaced at a window boundary — hand the module to
            # the elastic session (boundary checkpoint on the survivor,
            # telemetry) before the typed error propagates to the
            # worker main for the survivor-mesh restore
            from .parallel import elastic as _elastic
            _elastic.on_fit_fault(self, e)
            raise
        finally:
            timeline.close()

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, monitor, stager, timeline, wdog,
                    epoch_end_callback, batch_end_callback,
                    eval_end_callback, eval_batch_end_callback,
                    begin_epoch, num_epoch):
        """The epoch/batch loop of ``fit`` (instrumented: every loop
        iteration attributes its wall time to telemetry step lanes and
        beats the hang watchdog).  With ``MXNET_SCAN_STEPS``/``_ACCUM``
        the epoch body runs K-step scanned windows instead of per-batch
        steps (one donated XLA dispatch per window; host control only at
        window boundaries) when the module supports it."""
        with wdog.arm("train/fit"):
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                eval_metric.reset()
                plan = self._scan_plan()
                if plan is not None:
                    nbatch = self._fit_epoch_scan(
                        epoch, train_data, eval_metric, plan, stager,
                        timeline, wdog, batch_end_callback)
                else:
                    nbatch = self._fit_epoch_loop(
                        epoch, train_data, eval_metric, monitor, stager,
                        timeline, wdog, batch_end_callback)
                self.flush_metric_updates()
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name,
                                     val)
                toc = time.time()
                # legacy per-epoch log line (reference parity); per-step
                # phases go through telemetry lanes
                cost = toc - tic  # graftlint: disable=raw-phase-timing -- epoch wall is a user log line, not a phase metric
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, cost)
                arg_params, aux_params = self.get_params()
                self.set_params(arg_params, aux_params)
                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params, aux_params)
                if eval_data is not None:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                train_data.reset()
                wdog.beat("train/fit")

    def _fit_epoch_loop(self, epoch, train_data, eval_metric, monitor,
                        stager, timeline, wdog, batch_end_callback):
        """One epoch, one host visit per batch (the pre-scan fit body)."""
        nbatch = 0
        data_iter = iter(train_data)
        end_of_batch = False
        with timeline.lane("data_wait"):
            next_data_batch = next(data_iter)
        if stager is not None:
            with timeline.lane("h2d_stage"):
                next_data_batch = stager(next_data_batch)
        timeline.begin_step()
        while not end_of_batch:
            data_batch = next_data_batch
            if monitor is not None:
                monitor.tic()
            with timeline.lane("step_dispatch"):
                self.forward_backward(data_batch)
            if stager is not None:
                # double-buffer input feed: batch N+1's
                # host->device copy overlaps the step still in
                # flight on batch N (the staged copy also makes
                # buffer-reusing iterators safe to prefetch from
                # before update_metric reads batch N's labels)
                fetched = None
                with timeline.lane("data_wait"):
                    try:
                        fetched = next(data_iter)
                    except StopIteration:
                        end_of_batch = True
                if fetched is not None:
                    with timeline.lane("h2d_stage"):
                        next_data_batch = stager(fetched)
            with timeline.lane("step_dispatch"):
                self.update()
            # device_block/metric_flush lanes are attributed
            # inside update_metric (it knows where the sync is)
            self.update_metric(eval_metric, data_batch.label)
            if stager is None:
                with timeline.lane("data_wait"):
                    try:
                        next_data_batch = next(data_iter)
                    except StopIteration:
                        end_of_batch = True
            if monitor is not None:
                monitor.toc_print()
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(
                    epoch=epoch, nbatch=nbatch,
                    eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_params)
            nbatch += 1
            timeline.end_step()
            wdog.beat("train/fit")
        return nbatch

    def _scan_plan(self):
        """(K, M) when this epoch should run K-step scanned windows with
        M-way in-scan gradient accumulation, else None.  Only Module
        overrides the eligibility; every other module type keeps the
        per-batch loop."""
        return None

    def _fit_epoch_scan(self, epoch, train_data, eval_metric, plan,
                        stager, timeline, wdog, batch_end_callback):
        """One epoch in K-step windows: each full window of K*M
        same-shape batches is staged as one super-batch and dispatched
        as ONE scanned XLA computation; metrics, callbacks, watchdog
        beats and timeline accounting happen at window boundaries.
        Once window N is dispatched the loop collects AND stages window
        N+1 (``io.stage_super_batch``'s staging: the batches go to the
        device one by one and are stacked there) while N's scan runs,
        and only then runs N's boundary, whose metric read waits for
        the device: the next top of the loop finds its window staged
        and goes straight to the dispatch.  Nothing of window N+1 is
        dispatched before N's boundary.  Only the first window of an
        epoch, and the first after a per-batch fallback, is staged at
        need (``mxnet_io_stage_windows_total`` counts both kinds).
        Batches that don't fill a window (epoch tail, shape-mismatched
        batches) run through the per-batch path unchanged, in arrival
        order, and are never staged as a window; an error raised by a
        window or by its staging propagates to the caller of fit."""
        K, M = plan[0], plan[1]
        W = K * M
        # a healthy window legitimately goes W batch-times between
        # beats: scale the watchdog deadline so K=32 runs stay silent
        # while real wedges still fire
        wdog.set_scale("train/fit", W)
        _telemetry.record_scan_window(K)
        try:
            return self._fit_epoch_scan_inner(
                epoch, train_data, eval_metric, plan, stager, timeline,
                wdog, batch_end_callback)
        finally:
            wdog.set_scale("train/fit", 1)

    def _fit_epoch_scan_inner(self, epoch, train_data, eval_metric, plan,
                              stager, timeline, wdog, batch_end_callback):
        K, M = plan[0], plan[1]
        W = K * M
        ctx = getattr(self, "_context", None)
        # a mesh window re-places its stacked feeds itself
        # (DeviceMesh.put_batch shards the batch axis), so stage the
        # super-batch host-side there — one placement, not two
        stage_host = len(plan) > 2 and plan[2] is not None
        data_iter = iter(train_data)
        state = {"exhausted": False}
        nbatch = 0
        from . import io_pipeline as mx_pipe
        feed = None
        if mx_pipe.feed_enabled():
            # streaming data plane (ISSUE 19): collect AND stage the
            # next window off the train thread, double-buffered — the
            # stage/dispatch thread-pair idiom applied to input.  The
            # train thread only blocks in feed.get(), charged to the
            # data_wait lane; a wedged feed stops the train/fit beats,
            # so the watchdog still pages.
            feed = mx_pipe.WindowFeed(data_iter, W, ctx,
                                      self._scan_batch_ok,
                                      host=stage_host)

        def collect():
            # the next W same-shape batches (+ their pre-staged
            # super-batch when the window feed is on); shorter on epoch
            # end or when a shape-mismatched batch (tail partial,
            # bucketing) shows up — those route through the per-batch
            # path in arrival order
            if feed is not None:
                with timeline.lane("data_wait"):
                    kind, payload, sbatch, span = feed.get()
                if kind == "end":
                    state["exhausted"] = True
                    state["collect"] = None
                    return [], [], None
                state["collect"] = span
                if kind == "window":
                    return payload, [], sbatch
                return payload, [], None
            t_c0 = time.perf_counter()
            batches, tail = [], []
            while len(batches) < W:
                with timeline.lane("data_wait"):
                    try:
                        b = next(data_iter)
                    except StopIteration:
                        state["exhausted"] = True
                        break
                if not self._scan_batch_ok(b):
                    tail.append(b)
                    break
                batches.append(b)
            # the interval the NEXT window's trace claims as its
            # "collect" stage (prefetched collects belong to the window
            # they feed, not the one in flight while they ran)
            state["collect"] = (t_c0, time.perf_counter())
            return batches, tail, None

        def per_batch(batch):
            nonlocal nbatch
            if stager is not None:
                with timeline.lane("h2d_stage"):
                    batch = stager(batch)
            with timeline.lane("step_dispatch"):
                self.forward_backward(batch)
                self.update()
            self.update_metric(eval_metric, batch.label)
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(
                    epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                    locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_params)
            nbatch += 1
            timeline.end_step()
            wdog.beat("train/fit")

        def stage(batches, when):
            # one window's staging on this thread.  Like "collect", the
            # interval is claimed as its "stage" stage by the trace of
            # the window it feeds, not the one in flight while it ran
            t_s0 = time.perf_counter()
            with timeline.lane("h2d_stage"):
                sbatch = mx_io._stage_window(batches, ctx, stage_host, when)
            state["stage"] = (t_s0, time.perf_counter())
            return sbatch

        def stage_ahead(pending):
            # a full window just collected while the dispatched scan
            # runs: put it on the device now, so that the copy lies
            # under the scan and not between two scans.  The window
            # feed has staged its own; a short group stays unstaged
            batches, tail, staged = pending
            if feed is None and len(batches) == W:
                staged = stage(batches, "ahead")
            return batches, tail, staged

        pending = collect()
        timeline.begin_step()
        try:
            while True:
                batches, tail, staged = pending
                is_window = staged is not None or \
                    (feed is None and len(batches) == W)
                outs = False
                wtrace = _telemetry.trace.NULL_TRACE
                if is_window:
                    # the SIGKILL-mid-scan-window scenario arms a kill
                    # here: deterministically between the last boundary's
                    # host control and the next window's dispatch
                    from .chaos.failpoints import failpoint as _chaos_fp
                    _chaos_fp("train/scan_window")
                    # window trace (ISSUE 12): collect -> stage ->
                    # [rendezvous, recorded by the multi-host step via the
                    # ambient trace] -> dispatch -> boundary_flush
                    wtrace = _telemetry.trace.start("train", "fit/window")
                    wtrace.add_stage(
                        "collect", *(state.get("collect")
                                     or (wtrace.t0, wtrace.t0)))
                    # staged while the previous window's scan ran (by
                    # stage_ahead, or off-thread by the window feed:
                    # zero train-thread staging time), else at need
                    sbatch = staged if staged is not None \
                        else stage(batches, "at_need")
                    if state.get("stage"):
                        wtrace.add_stage("stage", *state.pop("stage"))
                    _telemetry.trace.set_current(wtrace)
                    try:
                        with timeline.lane("step_dispatch"), \
                                wtrace.stage("dispatch"):
                            outs = self._run_scan_window(sbatch, plan)
                    except (PeerLostError, PreemptionError) as e:
                        # elastic events are NOT trace failures: a lost
                        # peer or a preemption notice must reach the
                        # elastic session (boundary checkpoint +
                        # survivor-mesh restore), never degrade into
                        # per-batch steps
                        wtrace.event("elastic_fault",
                                     cause=type(e).__name__)
                        wtrace.finish(status="elastic_fault")
                        raise
                    except NonFiniteError:
                        # numerics halt (MXNET_NUMERICS=halt) is a
                        # verdict, not a trace failure: propagate typed to
                        # the caller — never degrade into per-batch steps
                        # that would keep training on the poisoned carry
                        wtrace.event("nonfinite_halt")
                        wtrace.finish(status="nonfinite")
                        raise
                    except Exception:
                        # tracing, compiling or running the window
                        # failed: the caller of fit sees it.  Only an
                        # INELIGIBLE set-up chooses per-batch steps, and
                        # it does so up front (_scan_plan)
                        wtrace.finish(status="error")
                        raise
                    finally:
                        _telemetry.trace.set_current(None)
                if outs is not False:
                    # prefetch: collect and stage the next window while
                    # this scan is still in flight on device (dispatch
                    # was async)
                    pending = stage_ahead(collect())
                    # window boundary: the only host-control point —
                    # metric updates (stacked, one sync), batch
                    # callbacks, timeline, watchdog beat
                    with wtrace.stage("boundary_flush"):
                        self._window_update_metrics(eval_metric, sbatch,
                                                    outs)
                        if batch_end_callback is not None:
                            for j in range(W):
                                batch_end_params = BatchEndParam(
                                    epoch=epoch, nbatch=nbatch + j,
                                    eval_metric=eval_metric,
                                    locals=locals())
                                for callback in \
                                        _as_list(batch_end_callback):
                                    callback(batch_end_params)
                    wtrace.finish()
                    nbatch += W
                    timeline.end_step(steps=W)
                    wdog.beat("train/fit")
                    continue
                wtrace.finish(status="fallback")
                for b in batches:
                    per_batch(b)
                for b in tail:
                    per_batch(b)
                if state["exhausted"]:
                    break
                pending = collect()
        finally:
            if feed is not None:
                feed.close()
        return nbatch

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """Evaluate (parity: base_module.py score)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(
                    epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                    locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_params)
            actual_num_batch += 1
        self.flush_metric_updates()
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False,
                sparse_row_id_fn=None):
        """Run prediction, collect outputs (parity: base_module.py predict)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out.slice_axis(0, 0, out.shape[0] - (pad or 0))
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs
            output_list2 = [nd.concat(*[out[i] for out in output_list], dim=0)
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    # -- interface subclasses implement ------------------------------------
    @property
    def symbol(self):
        return self._symbol

    def bind(self, *args, **kwargs):
        raise NotImplementedError()

    def init_params(self, *args, **kwargs):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        raise NotImplementedError()

    def flush_metric_updates(self):
        """Drain metric updates buffered under MXNET_METRIC_SYNC_INTERVAL
        (no-op for modules that sync every batch)."""

    def install_monitor(self, mon):
        raise NotImplementedError()


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]


def _block_on_maps(*maps):
    """Block until every device array in the maps is ready.  Telemetry's
    ``device_block`` lane wraps this wait explicitly, so the metric math
    that follows reads as pure host time (deferred device errors surface
    here instead of inside the metric — same user-visible sync point)."""
    import jax
    bufs = [v._data for m in maps for v in m.values()
            if isinstance(v, NDArray)]
    if bufs:
        jax.block_until_ready(bufs)


class Module(BaseModule):
    """Module over (symbol, data_names, label_names)
    (parity: module/module.py:364)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = cpu()
        if isinstance(context, (list, tuple)):
            context = context[0]  # one XLA program covers the device set
        self._context = context
        self._symbol = symbol
        self._data_names = list(data_names) if data_names else []
        self._label_names = list(label_names) if label_names else []
        self._fixed_param_names = list(fixed_param_names or [])
        self._state_names = list(state_names or [])
        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names + self._state_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._arg_params = None
        self._aux_params = None
        self._optimizer = None
        self._kvstore = None
        self._updater = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._monitor = None
        self._fused = None
        self._fused_step_done = False
        self._scan = None
        self._scan_disabled = False
        self._mesh = None          # DeviceMesh when the mesh path engaged
        self._mesh_local_rows = None  # multi-process: this host's batch rows
        self._auto_mesh = None     # cached all-device dp mesh (False = n/a)
        self._batch_outs_ok = {}   # mesh eligibility: outputs carry batch
        self._zero_buf_cache = {}
        self._pending_metric = []

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a checkpoint (parity: module.py load)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        manager=None):
        """Save symbol + params (+ optimizer states)
        (parity: module.py save_checkpoint).

        With ``manager`` (a checkpoint.CheckpointManager), the save
        routes through the async/atomic subsystem instead — params +
        optimizer state + step land in a committed ``step-NNNN/`` dir,
        and the manager's ``legacy_prefix`` mirror (when configured)
        keeps the ``prefix-NNNN.params`` files readable."""
        if manager is not None:
            return manager.save_module(
                self, epoch, save_optimizer_states=save_optimizer_states,
                epoch=epoch)
        self._sync_params_from_exec()
        save_checkpoint(prefix, epoch, self.symbol, self._arg_params,
                        self._aux_params)
        if save_optimizer_states:
            states = self.get_optimizer_states()
            if states is not None:
                fname = f"{prefix}-{epoch:04d}.states"
                tmp = f"{fname}.tmp-{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(states)
                os.replace(tmp, fname)

    def get_optimizer_states(self, dump_optimizer=False):
        """Optimizer state as bytes (None when nothing to save).  Under
        update_on_kvstore the real state lives IN the store (server-side
        for dist) — the local updater never ran — so it is fetched from
        there (parity: module.py save_optimizer_states)."""
        if getattr(self, "_update_on_kvstore", False) and \
                self._kvstore is not None:
            return self._kvstore.get_optimizer_states(dump_optimizer)
        if self._updater is not None:
            return self._updater.get_states(dump_optimizer)
        return None

    def set_optimizer_states(self, states):
        """Install optimizer state bytes (inverse of
        ``get_optimizer_states``); requires init_optimizer first."""
        assert self.optimizer_initialized, \
            "call init_optimizer before restoring optimizer states"
        if getattr(self, "_update_on_kvstore", False) and \
                self._kvstore is not None:
            self._kvstore.set_optimizer_states(states)
        else:
            self._updater.set_states(states)

    # -- bind / params -----------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Allocate executors (parity: module.py bind → GraphExecutor)."""
        if self.binded and not force_rebind:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        self._data_shapes = [_as_data_desc(x) for x in data_shapes]
        self._label_shapes = [_as_data_desc(x) for x in label_shapes] \
            if label_shapes else []
        shape_kwargs = {d.name: d.shape for d in self._data_shapes}
        for l in self._label_shapes:
            shape_kwargs[l.name] = l.shape
        grad_req_dict = {}
        for name in self.symbol.list_arguments():
            if name in self._data_names:
                grad_req_dict[name] = "write" if inputs_need_grad else "null"
            elif name in self._label_names or name in self._fixed_param_names \
                    or name in self._state_names or not for_training:
                grad_req_dict[name] = "null"
            else:
                grad_req_dict[name] = grad_req
        self._exec = self.symbol.simple_bind(self._context,
                                             grad_req=grad_req_dict,
                                             **shape_kwargs)
        self._fused = None  # new executor: the fused step must re-trace
        self._scan = None
        if self._arg_params is not None:
            self._exec.copy_params_from(self._arg_params, self._aux_params,
                                        allow_extra_params=True)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False,
                    allow_extra=False):
        """Initialize parameters (parity: module.py init_params)."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None:
            initializer = Uniform(0.01)
        from .initializer import InitDesc
        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params is not None and name in arg_params:
                arr[:] = arg_params[name]
            elif self._arg_params is not None and name in self._arg_params \
                    and not force_init:
                arr[:] = self._arg_params[name]
            else:
                if initializer is None and not allow_missing:
                    raise MXNetError(f"no initializer for {name}")
                initializer(InitDesc(name), arr)
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params is not None and name in aux_params:
                arr[:] = aux_params[name]
            elif self._aux_params is not None and name in self._aux_params \
                    and not force_init:
                arr[:] = self._aux_params[name]
            else:
                initializer(InitDesc(name), arr)
        for name in self._state_names:
            # initial states (RNN hidden/cell): zeros until set_states
            self._exec.arg_dict[name][:] = 0
        self._sync_params_from_exec()
        self.params_initialized = True

    def set_states(self, states=None, value=None):
        """Set value of states (parity: module.py set_states). ``states``
        is a list of NDArrays ordered like state_names, or ``value`` is a
        scalar broadcast to every state. Exactly one must be given."""
        assert self.binded and self._state_names
        if (states is None) == (value is None):
            raise MXNetError(
                "set_states takes exactly one of states= or value=")
        if states is not None:
            if len(states) != len(self._state_names):
                raise MXNetError(
                    f"set_states got {len(states)} arrays for "
                    f"{len(self._state_names)} states {self._state_names}")
            for name, arr in zip(self._state_names, states):
                self._exec.arg_dict[name][:] = arr
        else:
            for name in self._state_names:
                self._exec.arg_dict[name][:] = value

    def get_states(self, merge_multi_context=True):
        assert self.binded and self._state_names
        return [self._exec.arg_dict[n].copy() for n in self._state_names]

    def get_params(self):
        """(arg_params, aux_params) on cpu (parity: module.py get_params)."""
        assert self.binded and self.params_initialized
        self._sync_params_from_exec()
        return self._arg_params, self._aux_params

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def _sync_params_from_exec(self):
        if self._exec is None:
            return
        self._arg_params = {n: self._exec.arg_dict[n].copy()
                            for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n].copy()
                            for n in self._aux_names}

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Install optimizer (parity: module.py init_optimizer →
        model.py _create_kvstore/_initialize_kvstore). A dist kvstore
        synchronizes gradients across workers in update(); the optimizer
        then runs server-side (update_on_kvstore)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            opt_kw = dict(optimizer_params or ())
            # loss-layer ops (SoftmaxOutput, *RegressionOutput) emit
            # batch-SUMMED gradients; the optimizer normalizes
            # (parity: module.py:503-506 — and a dist_sync server SUMS
            # worker pushes before updating, so the divisor is the
            # GLOBAL batch)
            if "rescale_grad" not in opt_kw and self._data_shapes:
                batch = self._data_shapes[0][1][0]
                kv_type = kvstore if isinstance(kvstore, str) else \
                    getattr(kvstore, "type", "")
                if "dist" in (kv_type or "") and "_async" not in kv_type:
                    nw = kvstore.num_workers if not isinstance(kvstore, str) \
                        else int(os.environ.get("DMLC_NUM_WORKER", 1))
                    batch *= nw
                # in-scan gradient accumulation sums M micro-batch
                # gradients per update: the divisor is the EFFECTIVE
                # batch, same precedent as the dist global batch above
                from . import config as _config
                batch *= max(1, int(_config.get("MXNET_SCAN_ACCUM")))
                if batch:
                    opt_kw["rescale_grad"] = 1.0 / batch
            optimizer = opt_mod.create(
                optimizer, param_idx2name=idx2name, **opt_kw)
        elif getattr(optimizer, "rescale_grad", 1.0) == 1.0 and \
                self._data_shapes and self._data_shapes[0][1][0] > 1:
            self.logger.warning(
                "Optimizer created manually outside Module but rescale_grad "
                "= 1.0. Is this intended? (gradients from loss layers are "
                "batch-summed; consider rescale_grad=1/batch_size)")
        self._optimizer = optimizer
        self._updater = opt_mod.get_updater(optimizer)
        self._fused = None  # optimizer changed: invalidate the fused trace
        self._scan = None
        self._scan_disabled = False
        self._mesh = None
        arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        kv, update_on_kvstore = _create_kvstore(kvstore, 1, arg_params)
        self._kvstore = kv
        self._update_on_kvstore = bool(kv is not None and update_on_kvstore)
        if kv is not None:
            _initialize_kvstore(
                kv, [[arg_params[n]] for n in self._param_names],
                arg_params, self._param_names, self._update_on_kvstore)
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
        self.optimizer_initialized = True
        if hasattr(self, "_preload_opt_states"):
            if self._update_on_kvstore and kv is not None:
                kv.load_optimizer_states(self._preload_opt_states)
            else:
                with open(self._preload_opt_states, "rb") as f:
                    self._updater.set_states(f.read())
            del self._preload_opt_states
        if hasattr(self, "_preload_opt_states_bytes"):
            # checkpoint.CheckpointManager.restore_module stashes the
            # optimizer blob here; it can only be applied once the
            # updater/kvstore exists
            self.set_optimizer_states(self._preload_opt_states_bytes)
            del self._preload_opt_states_bytes

    # -- compute -----------------------------------------------------------
    def _demesh_arrays(self):
        """Re-place parameter/optimizer-state buffers held as
        mesh-replicated ``jax.Array``s back onto the module's single
        context device.  After mesh-fused windows ran (parallel/
        fused.py), ``arg_dict``/``Updater.states`` hold multi-device
        arrays; the plain executor path (per-batch fallback steps,
        score/predict, direct forward) jits against the context device
        and would fail with incompatible-devices — this collapse runs
        once at the first such use, then the flag re-arms on the next
        mesh window."""
        if not getattr(self, "_mesh_arrays_live", False):
            return
        self._mesh_arrays_live = False
        import jax as _jax
        dev = self._context.jax_device

        def _fix(nd_arr):
            buf = getattr(nd_arr, "_data", None)
            if buf is not None and len(buf.devices()) > 1:
                nd_arr._set_data(_jax.device_put(buf, dev))

        for n in self._param_names:
            _fix(self._exec.arg_dict[n])
        for n in self._aux_names:
            _fix(self._exec.aux_dict[n])
        if self._updater is not None:
            def _walk(s):
                if isinstance(s, (tuple, list)):
                    for t in s:
                        _walk(t)
                elif isinstance(s, NDArray):
                    _fix(s)
            for s in self._updater.states.values():
                _walk(s)
        # the fused-step ownership ledgers point at the old buffers now
        if self._scan is not None:
            self._scan._owned = {}
        if self._fused is not None:
            self._fused._owned = {}

    def forward(self, data_batch, is_train=None):
        """Forward (parity: module.py forward; batch feeds the executor)."""
        assert self.binded and self.params_initialized
        self._demesh_arrays()
        if is_train is None:
            is_train = self.for_training
        # a manual forward supersedes any fused step still pending its
        # update() no-op: the next update() must run the loop
        self._fused_step_done = False
        feed = {}
        for desc, arr in zip(self._data_shapes, data_batch.data):
            feed[desc.name] = arr
        if self._label_shapes and data_batch.label:
            for desc, arr in zip(self._label_shapes, data_batch.label):
                feed[desc.name] = arr
        self._forward_pad = 0
        mismatch = any(
            tuple(arr.shape) != tuple(self._exec.arg_dict[name].shape)
            for name, arr in feed.items())
        if mismatch:
            pad = self._partial_batch_pad(feed) if not is_train else None
            if pad is not None:
                # serving-style bucketing on the predict path: a partial
                # final batch is zero-padded up to the bound batch and the
                # outputs sliced (get_outputs), reusing the compiled
                # program instead of rebinding a new executor shape
                # (MXNET_MODULE_PAD_PARTIAL_PREDICT; docs/serving.md)
                n, bound = pad
                self._forward_pad = bound - n
                self._pad_bound = bound
                self._pad_batch_outputs = self._infer_batch_outputs(
                    feed, n, bound)
                for name, arr in feed.items():
                    # one transfer per INPUT TENSOR: zero-padding the
                    # partial final batch requires the host copy anyway
                    # graftlint: disable=host-sync-in-hot-path -- per-input pad copy, once per partial batch
                    host = arr.asnumpy()
                    host = np.concatenate(
                        [host, np.zeros((bound - n,) + host.shape[1:],
                                        host.dtype)], axis=0)
                    self._exec.arg_dict[name][:] = host
                self._exec.forward(is_train=False)
                return
            # shape change (bucketing / train-mode partial batch):
            # reshape.  The module owns its data arrays, so growing back
            # to the full batch after a partial one is expected — opt
            # into both relaxations explicitly
            self._exec = self._exec.reshape(
                partial_shaping=True, allow_up_sizing=True,
                **{n: a.shape for n, a in feed.items()})
        for name, arr in feed.items():
            self._exec.arg_dict[name][:] = arr
        self._exec.forward(is_train=is_train)

    def _partial_batch_pad(self, feed):
        """(n, bound) when ``feed`` is the bound shapes short a few batch
        rows (pad-and-slice eligible), else None."""
        from . import config as _config
        if not _config.get("MXNET_MODULE_PAD_PARTIAL_PREDICT"):
            return None
        ns, bounds = set(), set()
        for name, arr in feed.items():
            tgt = self._exec.arg_dict[name]
            if tuple(arr.shape[1:]) != tuple(tgt.shape[1:]):
                return None
            ns.add(int(arr.shape[0]))
            bounds.add(int(tgt.shape[0]))
        if len(ns) != 1 or len(bounds) != 1:
            return None
        n, bound = ns.pop(), bounds.pop()
        return (n, bound) if 0 < n < bound else None

    def _infer_batch_outputs(self, feed, n, bound):
        """Which output indices actually carry the padded batch dim —
        exact, by inferring output shapes at batch ``n`` vs ``bound``
        (every non-feed argument keeps its bound shape): only outputs
        whose leading dim tracks the batch get pad-sliced.  Returns
        None when inference cannot decide (get_outputs then falls back
        to the leading-dim heuristic)."""
        cache = getattr(self, "_batch_out_cache", None)
        if cache is None:
            cache = self._batch_out_cache = {}
        key = (n, bound)
        if key not in cache:
            try:
                fixed = {name: tuple(a.shape)
                         for name, a in self._exec.arg_dict.items()
                         if name not in feed}
                fixed.update({name: tuple(a.shape) for name, a
                              in getattr(self._exec, "aux_dict",
                                         {}).items()})

                def outs_at(b):
                    shapes = dict(fixed)
                    shapes.update({name: (b,) + tuple(arr.shape[1:])
                                   for name, arr in feed.items()})
                    _, outs, _ = self.symbol.infer_shape_partial(**shapes)
                    return outs

                outs_n, outs_b = outs_at(n), outs_at(bound)
                if (len(outs_n) == len(outs_b)
                        and all(s is not None for s in outs_n)
                        and all(s is not None for s in outs_b)):
                    cache[key] = frozenset(
                        i for i, (sn, sb) in enumerate(zip(outs_n, outs_b))
                        if sn and sb and sn[0] == n and sb[0] == bound)
                else:
                    cache[key] = None
            except Exception as e:  # noqa: BLE001 — fall back to heuristic
                self.logger.debug(
                    "pad-slice output inference failed (%s: %s); falling "
                    "back to slicing every output", type(e).__name__, e)
                cache[key] = None
        return cache[key]

    def backward(self, out_grads=None):
        """Backward (parity: module.py backward)."""
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """Forward + backward; when the setup is eligible this runs the
        FUSED step instead — forward + VJP + optimizer update as one
        donated XLA dispatch (fused_step.py) — and the following
        ``update()`` becomes a no-op."""
        if self._maybe_fused_step(data_batch):
            return
        self.forward(data_batch, is_train=True)
        self.backward()

    def _fused_eligible(self):
        from . import config as _config
        if not _config.get("MXNET_FUSED_STEP"):
            return False
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized and self.for_training):
            return False
        if getattr(self, "_kvstore", None) is not None:
            return False  # grads must sync/update through the store
        if self.inputs_need_grad or self._monitor is not None:
            return False
        ex = self._exec
        if ex is None or ex._grouped is not None or \
                ex._monitor_callback is not None:
            return False
        if not callable(getattr(self._optimizer, "fused_update", None)):
            return False  # custom optimizer: per-param loop, silently
        if any(ex.grad_req.get(n, "null") not in ("write", "null")
               for n in ex._arg_names):
            return False  # "add" accumulation needs live grad buffers
        return True

    def _maybe_fused_step(self, data_batch):
        if not self._fused_eligible():
            return False
        fs = self._fused
        if fs is None or fs.stale(self):
            from .fused_step import FusedTrainStep
            fs = self._fused = FusedTrainStep(self)
        # an error raised by tracing, compiling or running the step
        # propagates; only a shape-mismatched batch returns False
        ran = fs.step(data_batch)
        if ran:
            self._fused_step_done = True
        return ran

    # -- mesh-fused distributed windows (parallel/fused.py) ----------------
    def _fit_mesh(self):
        """The DeviceMesh the mesh-fused fit path would run on: the
        ambient ``with mesh:`` mesh when one is active, else a cached
        all-device dp mesh (every mesh axis is data-parallel for a
        symbolic Module graph; docs/parallel.md)."""
        from .parallel import current_mesh
        m = current_mesh()
        if m is not None:
            return m
        if self._auto_mesh is None:
            import jax
            from .parallel.mesh import DeviceMesh
            devs = jax.devices()
            self._auto_mesh = DeviceMesh({"dp": len(devs)}, devs) \
                if len(devs) > 1 else False
        return self._auto_mesh or None

    def _mesh_batch_outputs_ok(self, n_shards, batch):
        """Every graph output must carry the batch on its leading dim
        (the window's out_specs shard/unshard dim0): infer output shapes
        at the bound batch AND at the per-shard batch and require dim0
        to track both.  Cached per (n_shards, batch)."""
        key = (n_shards, batch)
        if key not in self._batch_outs_ok:
            try:
                known = {d.name: d.shape for d in self._data_shapes}
                for l in (self._label_shapes or []):
                    known[l.name] = l.shape
                _, outs_b, _ = self.symbol.infer_shape_partial(**known)
                local = {k: (v[0] // n_shards,) + tuple(v[1:])
                         for k, v in known.items()}
                _, outs_s, _ = self.symbol.infer_shape_partial(**local)
                ok = bool(outs_b and outs_s
                          and all(o and o[0] == batch for o in outs_b)
                          and all(o and o[0] == batch // n_shards
                                  for o in outs_s))
            except Exception as e:  # noqa: BLE001 — ineligible, not fatal
                self.logger.debug(
                    "mesh batch-output inference failed (%s: %s); "
                    "keeping the per-param kvstore loop",
                    type(e).__name__, e)
                ok = False
            self._batch_outs_ok[key] = ok
        return self._batch_outs_ok[key]

    def _mesh_fused_eligible(self):
        """True when fit can trace forward + VJP + bucketed gradient
        collectives + optimizer update into one donated shard_map window
        per K steps (parallel/fused.MeshFusedTrainStep) instead of the
        per-param kvstore push/pull loop.  See docs/parallel.md for the
        full eligibility matrix."""
        from . import config as _config
        if not _config.get("MXNET_MESH_FUSED_STEP"):
            return False
        kv = getattr(self, "_kvstore", None)
        if kv is None or not getattr(kv, "mesh_fusible", False):
            return False  # no store, or a store the mesh cannot absorb
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized and self.for_training):
            return False
        if self.inputs_need_grad or self._monitor is not None:
            return False
        if self._aux_names:
            # per-replica aux mutation (BN running stats) would need
            # sync-BN; the loop path keeps reference semantics
            return False
        ex = self._exec
        if ex is None or ex._grouped is not None or \
                ex._monitor_callback is not None:
            return False
        opt = self._optimizer
        if not callable(getattr(opt, "fused_update", None)) or \
                getattr(opt, "multi_precision", False):
            return False
        if any(ex.grad_req.get(n, "null") not in ("write", "null")
               for n in ex._arg_names):
            return False
        mesh = self._fit_mesh()
        if mesh is None or mesh.size() < 2:
            return False
        n = mesh.size()
        shapes = list(self._data_shapes) + list(self._label_shapes or [])
        if not shapes or not shapes[0].shape:
            return False
        batch = shapes[0].shape[0]
        if not batch or batch % n:
            return False  # batch must shard evenly over the mesh
        if any((not d.shape) or d.shape[0] != batch for d in shapes):
            return False
        return self._mesh_batch_outputs_ok(n, batch)

    # -- scanned K-step windows (fused_step.ScanTrainStep) -----------------
    def _scan_plan(self):
        from . import config as _config
        if self._scan_disabled:
            return None
        K = max(1, int(_config.get("MXNET_SCAN_STEPS")))
        M = max(1, int(_config.get("MXNET_SCAN_ACCUM")))
        if self._mesh_fused_eligible():
            # mesh path: even K=1 windows win (one donated dispatch
            # replaces 2 host round-trips per parameter).  The in-store
            # updater retires from the hot path NOW — optimizer state
            # lives in the module's Updater, which the mesh step
            # maintains, so state fetch and any later loop fallback
            # read one consistent store.
            if self._update_on_kvstore:
                # a checkpoint restore may have preloaded optimizer
                # state into the STORE's updater (set_optimizer_states
                # ran while update_on_kvstore was still true) — hand
                # those states to the module updater, or a resumed fit
                # would silently restart momentum/Adam moments at zero
                kv_updater = getattr(self._kvstore, "_updater", None)
                if kv_updater is not None:
                    for idx, st in kv_updater.states.items():
                        if isinstance(idx, int) and \
                                idx not in self._updater.states:
                            self._updater.states[idx] = st
                            self._updater.states_synced[idx] = True
                self._update_on_kvstore = False
            return (K, M, self._fit_mesh())
        if K * M <= 1:
            return None
        if not self._fused_eligible():
            if M > 1:
                self.logger.warning(
                    "MXNET_SCAN_ACCUM=%d requested but the setup is not "
                    "fused-step eligible; per-batch updates run WITHOUT "
                    "gradient accumulation", M)
                self._scan_disabled = True
            return None
        return (K, M, None)

    def _scan_batch_ok(self, batch):
        """Window-eligible: every data/label array matches its bound
        shape exactly (partial tails and bucket switches go per-batch)."""
        exec_ = self._exec
        for desc, arr in zip(self._data_shapes, batch.data):
            bound = exec_.arg_dict.get(desc.name)
            if bound is None or \
                    tuple(arr.shape) != tuple(bound.shape):
                return False
        if self._label_shapes and batch.label:
            for desc, arr in zip(self._label_shapes, batch.label):
                bound = exec_.arg_dict.get(desc.name)
                if bound is None or \
                        tuple(arr.shape) != tuple(bound.shape):
                    return False
        return True

    def _run_scan_window(self, sbatch, plan):
        """Dispatch one staged super-batch through the scanned step
        (mesh-fused when the plan carries a DeviceMesh); returns the
        flattened per-batch output buffers or False."""
        K, M, mesh = plan
        fs = self._scan
        if fs is None or fs.stale(self) or fs.scan_steps != K \
                or fs.accum != M or getattr(fs, "mesh", None) is not mesh:
            if mesh is not None:
                from .parallel import multihost as _mh
                from .parallel.fused import MeshFusedTrainStep
                if _mh.runtime() is not None and mesh.is_multiprocess:
                    # the coordinated multi-host flavor: per-window
                    # rendezvous, peer-watching bounded result waits,
                    # progress reporting (parallel/elastic.py)
                    from .parallel.elastic import MultiHostFusedTrainStep
                    fs = self._scan = MultiHostFusedTrainStep(
                        self, mesh, K, M)
                else:
                    fs = self._scan = MeshFusedTrainStep(self, mesh, K, M)
                self._mesh = mesh
                self.logger.info(
                    "mesh fused train step engaged: %s, K=%d M=%d — the "
                    "per-param kvstore push/pull loop is off the hot "
                    "path (kvstore remains for init/broadcast + "
                    "optimizer-state fetch)", mesh, K, M)
            else:
                from .fused_step import ScanTrainStep
                fs = self._scan = ScanTrainStep(self, K, M)
        outs = fs.run_window(sbatch)
        if outs is not False:
            self._forward_pad = 0
            self._fused_step_done = False
            if mesh is not None:
                # arg_dict/updater.states now hold mesh-replicated
                # arrays; any plain-executor use collapses them first
                self._mesh_arrays_live = True
        return outs

    def update(self):
        """Apply optimizer to gradients (parity: module.py update →
        model.py _update_params_on_kvstore / local updater).  After a
        fused forward_backward the weights are already updated and this
        is a no-op."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        if self._fused_step_done:
            self._fused_step_done = False
            return
        kv = getattr(self, "_kvstore", None)
        if kv is not None and self._update_on_kvstore:
            # optimizer runs IN the store (server-side for dist).  This
            # is the residual per-param sync path (mesh-ineligible
            # setups and real multi-worker clients): its wall time IS
            # gradient-communication time, so reattribute it from the
            # enclosing step_dispatch lane to comm_collective — the
            # breakdown then shows blocking-% on collectives directly.
            st = _telemetry.current_step_timer()
            t0 = time.perf_counter()
            _update_params_on_kvstore(
                [[self._exec.arg_dict[n]] for n in self._param_names],
                [[self._exec.grad_dict.get(n)] for n in self._param_names],
                kv, self._param_names)
            if st.active:
                dt = time.perf_counter() - t0  # graftlint: disable=raw-phase-timing -- lane REattribution: the span is already timed inside the step_dispatch lane; this moves its share to comm_collective
                st.add("comm_collective", dt)
                st.add("step_dispatch", -dt)
            self._zero_grads()
            return
        for i, name in enumerate(self._param_names):
            grad = self._exec.grad_dict.get(name)
            if grad is None or \
                    self._exec.grad_req.get(name, "null") == "null":
                continue  # fixed/ungradded params take no optimizer step
            weight = self._exec.arg_dict[name]
            self._updater(i, grad, weight)
        self._zero_grads()

    def _zero_grads(self):
        """Write-mode semantics for the next backward, WITHOUT the old
        one-dispatch-per-param ``grad[:] = 0.0`` loop: every grad NDArray
        swaps to a cached immutable zero buffer (jax arrays are
        copy-on-write, sharing is safe), so steady-state zeroing costs no
        device dispatch at all.  Params with no grad buffer or grad_req
        "null" are skipped."""
        import jax as _jax
        import jax.numpy as _jnp
        cache = self._zero_buf_cache
        for name in self._param_names:
            g = self._exec.grad_dict.get(name)
            if g is None or \
                    self._exec.grad_req.get(name, "null") == "null":
                continue
            dev = next(iter(g._data.devices()))
            key = (tuple(g.shape), str(g._data.dtype), dev)
            z = cache.get(key)
            if z is None:
                z = cache[key] = _jax.device_put(  # graftlint: disable=per-param-collective -- cold zero-buffer cache fill, once per (shape, dtype, device); steady state is a dict hit
                    _jnp.zeros(g.shape, g._data.dtype), dev)
            g._set_data(z)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        outs = self._exec.outputs
        pad = getattr(self, "_forward_pad", 0)
        if pad:
            # slice off the zero-padding rows added by the partial-batch
            # predict path (only outputs carrying the padded batch dim)
            bound = self._pad_bound
            batch_outs = getattr(self, "_pad_batch_outputs", None)
            if batch_outs is not None:
                # exact membership from shape inference at both batch
                # sizes (_infer_batch_outputs)
                outs = [o.slice_axis(0, 0, bound - pad)
                        if i in batch_outs else o
                        for i, o in enumerate(outs)]
            else:
                # inference couldn't decide: leading-dim heuristic
                outs = [o.slice_axis(0, 0, bound - pad)
                        if len(o.shape) >= 1 and o.shape[0] == bound else o
                        for o in outs]
        return outs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        """Feed (labels, outputs) to the metric.  The metric math runs on
        host numpy, so every call forces a device->host sync; with
        MXNET_METRIC_SYNC_INTERVAL=N the pairs are buffered (device
        arrays, no copy) and flushed every N batches — the device races
        ahead and the N transfers amortize into one stall.  Buffering
        requires label arrays that are not reused by the iterator
        (NDArrayIter and staged fit batches qualify; see docs)."""
        from . import config as _config
        label_map = {name: l for name, l in
                     zip([d.name for d in self._label_shapes], labels)}
        pred_map = dict(zip(self.output_names, self.get_outputs()))
        if _config.get("MXNET_METRIC_SYNC_INTERVAL") <= 1:
            st = _telemetry.current_step_timer()
            if st.active:
                # split the fit-loop lanes where the sync actually is:
                # device_block = waiting for the step's outputs to land,
                # metric_flush = the host-side metric math afterwards
                with st.lane("device_block"):
                    _block_on_maps(label_map, pred_map)
            with st.lane("metric_flush"):
                eval_metric.update_dict(label_map, pred_map)
            return
        self._pending_metric.append((eval_metric, label_map, pred_map, 1))
        if self._pending_metric_steps() >= \
                _config.get("MXNET_METRIC_SYNC_INTERVAL"):
            self.flush_metric_updates()

    def _pending_metric_steps(self):
        """Train steps represented in the metric buffer (a scanned window
        contributes K*M at once, so the flush interval rounds up to
        window boundaries)."""
        return sum(entry[3] for entry in self._pending_metric)

    def _window_update_metrics(self, eval_metric, sbatch, outs_flat):
        """Queue one whole window's metric inputs as STACKED arrays —
        zero per-step device ops here; the flush does ONE sync + one
        host transfer per tensor position and feeds the metric zero-copy
        numpy views per step.  Flushes immediately when metric syncing
        is per-batch (MXNET_METRIC_SYNC_INTERVAL <= 1), else once the
        buffered step count reaches the interval (rounded up to this
        window's boundary)."""
        from . import config as _config
        # a 1-step window (mesh path at K=M=1) strips its leading window
        # dim: the flush's single-step branch expects per-batch arrays
        unstack = sbatch.count == 1
        label_map = {}
        if self._label_shapes and sbatch.label:
            rows = getattr(self, "_mesh_local_rows", None)
            labels = sbatch.label
            if rows is not None:
                # multi-process mesh: outputs carry only this host's
                # addressable batch rows — pair them with the same
                # label rows (metrics are per-host over the local shard)
                labels = [l[:, rows[0]:rows[1]] for l in labels]
            label_map = {d.name: NDArray(l[0] if unstack else l,
                                         self._context)
                         for d, l in zip(self._label_shapes, labels)}
        pred_map = {name: NDArray(o[0] if unstack else o, self._context)
                    for name, o in zip(self.output_names, outs_flat)}
        self._pending_metric.append(
            (eval_metric, label_map, pred_map, sbatch.count))
        interval = _config.get("MXNET_METRIC_SYNC_INTERVAL")
        if interval <= 1 or self._pending_metric_steps() >= interval:
            self.flush_metric_updates()

    def flush_metric_updates(self):
        """Drain metric updates buffered under MXNET_METRIC_SYNC_INTERVAL
        (and whole scanned windows); the deferred device->host transfers
        all happen here, exactly once per buffered entry."""
        pending = self._pending_metric
        if not pending:
            return
        self._pending_metric = []
        st = _telemetry.current_step_timer()
        if st.active:
            with st.lane("device_block"):
                for _metric, label_map, pred_map, _n in pending:
                    _block_on_maps(label_map, pred_map)
        with st.lane("metric_flush"):
            for metric, label_map, pred_map, n in pending:
                if n == 1:
                    metric.update_dict(label_map, pred_map)
                    continue
                # stacked window entry (leading dim n): one host copy
                # per tensor, then zero-copy numpy views per step —
                # metrics consume numpy through _as_np unchanged
                lm = {k: v.asnumpy() for k, v in label_map.items()}  # graftlint: disable=host-sync-in-hot-path -- ONE batched transfer per stacked window tensor, this is the flush point
                pm = {k: v.asnumpy() for k, v in pred_map.items()}  # graftlint: disable=host-sync-in-hot-path -- ONE batched transfer per stacked window tensor, this is the flush point
                for j in range(n):
                    metric.update_dict(
                        {k: v[j] for k, v in lm.items()},
                        {k: v[j] for k, v in pm.items()})

    @property
    def output_names(self):
        return self.symbol.list_outputs()

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def output_shapes(self):
        if self._exec is not None and self._exec.outputs:
            return [(n, o.shape) for n, o in zip(self.output_names,
                                                 self._exec.outputs)]
        # before the first forward the executor has no output arrays yet
        # (reference modules report inferred shapes straight from bind) —
        # infer from the bound data/label shapes instead
        known = {d.name: d.shape for d in (self._data_shapes or [])}
        for l in (self._label_shapes or []):
            known[l.name] = l.shape
        _, outs, _ = self.symbol.infer_shape_partial(**known)
        return list(zip(self.output_names, outs or []))

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)


class BucketingModule(BaseModule):
    """Bucketing over variable-length inputs (parity:
    module/bucketing_module.py). One Module per bucket key; parameters are
    shared by name; each bucket compiles its own XLA program (one-compile-
    per-bucket is the TPU analogue of shared-memory executors per bucket)."""

    def __init__(self, sym_gen, default_bucket_key=None, logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None, compression_params=None):
        super().__init__(logger=logger)
        assert default_bucket_key is not None
        self._default_bucket_key = default_bucket_key
        self._sym_gen = sym_gen
        self._context = context
        self._fixed_param_names = fixed_param_names
        self._state_names = list(state_names or [])
        self._buckets = {}
        self._curr_module = None
        self._curr_bucket_key = None
        self._initializer = None

    @property
    def default_bucket_key(self):
        return self._default_bucket_key

    @property
    def symbol(self):
        return self._curr_module.symbol

    def _gen_module(self, bucket_key):
        if bucket_key in self._buckets:
            return self._buckets[bucket_key]
        sym, data_names, label_names = self._sym_gen(bucket_key)
        mod = Module(sym, data_names, label_names, logger=self.logger,
                     context=self._context,
                     fixed_param_names=self._fixed_param_names,
                     state_names=self._state_names)
        self._buckets[bucket_key] = mod
        return mod

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        assert shared_module is None
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        mod = self._gen_module(self._default_bucket_key)
        mod.bind(data_shapes, label_shapes, for_training, inputs_need_grad,
                 force_rebind, None, grad_req)
        self._curr_module = mod
        self._curr_bucket_key = self._default_bucket_key
        self.binded = True

    def switch_bucket(self, bucket_key, data_shapes, label_shapes=None):
        """Switch to a bucket (parity: bucketing_module.py switch_bucket)."""
        assert self.binded
        if bucket_key == self._curr_bucket_key:
            return
        arg_params, aux_params = self._curr_module.get_params() \
            if self._curr_module.params_initialized else (None, None)
        mod = self._gen_module(bucket_key)
        if not mod.binded:
            mod.bind(data_shapes, label_shapes, self.for_training,
                     self.inputs_need_grad)
        if arg_params is not None and not mod.params_initialized:
            mod.init_params(self._initializer, arg_params=arg_params,
                            aux_params=aux_params, allow_missing=False)
        elif arg_params is not None:
            mod.set_params(arg_params, aux_params)
        if self.optimizer_initialized and not mod.optimizer_initialized:
            mod._optimizer = self._curr_module._optimizer
            mod._updater = self._curr_module._updater
            # the kvstore wiring must follow the optimizer — otherwise a
            # bucket switch silently drops dist synchronization
            mod._kvstore = getattr(self._curr_module, "_kvstore", None)
            mod._update_on_kvstore = getattr(
                self._curr_module, "_update_on_kvstore", False)
            mod.optimizer_initialized = True
        self._curr_module = mod
        self._curr_bucket_key = bucket_key

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if initializer is None:
            initializer = Uniform(0.01)
        self._initializer = initializer
        self._curr_module.init_params(initializer, arg_params, aux_params,
                                      allow_missing, force_init, allow_extra)
        self.params_initialized = True

    def get_params(self):
        return self._curr_module.get_params()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self._curr_module.set_params(arg_params, aux_params, allow_missing,
                                     force_init, allow_extra)
        self.params_initialized = True

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        self._curr_module.init_optimizer(kvstore, optimizer, optimizer_params,
                                         force_init)
        self.optimizer_initialized = True

    def forward(self, data_batch, is_train=None):
        assert self.binded
        bucket_key = getattr(data_batch, "bucket_key",
                             self._default_bucket_key)
        if bucket_key is None:
            bucket_key = self._default_bucket_key
        self.switch_bucket(bucket_key, data_batch.provide_data,
                           data_batch.provide_label)
        self._curr_module.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        self._curr_module.backward(out_grads)

    def update(self):
        self._curr_module.update()
        # propagate updated params so other buckets see them on switch

    def get_outputs(self, merge_multi_context=True):
        return self._curr_module.get_outputs(merge_multi_context)

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        self._curr_module.update_metric(eval_metric, labels, pre_sliced)

    def install_monitor(self, mon):
        for mod in self._buckets.values():
            mod.install_monitor(mon)


class SequentialModule(BaseModule):
    """Chain of modules (parity: module/sequential_module.py). Minimal
    implementation: forward feeds each module's outputs to the next."""

    META_TAKE_LABELS = "take_labels"
    META_AUTO_WIRING = "auto_wiring"

    def __init__(self, logger=logging):
        super().__init__(logger=logger)
        self._modules = []
        self._metas = []

    def add(self, module, **kwargs):
        self._modules.append(module)
        self._metas.append(kwargs)
        return self

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        my_data_shapes = data_shapes
        for i, module in enumerate(self._modules):
            meta = self._metas[i]
            my_label_shapes = label_shapes if meta.get(
                self.META_TAKE_LABELS) else None
            module.bind(my_data_shapes, my_label_shapes, for_training,
                        inputs_need_grad if i == 0 else True,
                        force_rebind, None, grad_req)
            my_data_shapes = [mx_io.DataDesc(name, shape) for name, shape
                              in module.output_shapes]
        self.binded = True

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        for module in self._modules:
            module.init_params(initializer, arg_params, aux_params,
                               allow_missing=True, force_init=force_init)
        self.params_initialized = True

    def init_optimizer(self, **kwargs):
        for module in self._modules:
            module.init_optimizer(**kwargs)
        self.optimizer_initialized = True

    def forward(self, data_batch, is_train=None):
        batch = data_batch
        for i, module in enumerate(self._modules):
            module.forward(batch, is_train)
            outs = module.get_outputs()
            batch = mx_io.DataBatch(data=outs, label=data_batch.label,
                                    pad=data_batch.pad)
        self._last_batch = batch

    def backward(self, out_grads=None):
        for i, module in reversed(list(enumerate(self._modules))):
            module.backward(out_grads)
            if i > 0:
                out_grads = module.get_input_grads()

    def update(self):
        for module in self._modules:
            module.update()

    def get_outputs(self, merge_multi_context=True):
        return self._modules[-1].get_outputs(merge_multi_context)

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        for meta, module in zip(self._metas, self._modules):
            if meta.get(self.META_TAKE_LABELS):
                module.update_metric(eval_metric, labels, pre_sliced)


def _as_data_desc(x):
    if isinstance(x, mx_io.DataDesc):
        return x
    name, shape = x[0], x[1]
    return mx_io.DataDesc(name, tuple(shape))


class PythonModule(BaseModule):
    """A module whose computation is written directly in Python
    (parity: module/python_module.py PythonModule) — no symbol, no
    parameters by default. Subclasses implement forward/backward and
    ``_compute_output_shapes``; everything parameter/optimizer-shaped is
    a no-op so the module slots into SequentialModule pipelines and
    the fit() loop unchanged."""

    def __init__(self, data_names, label_names, output_names,
                 logger=logging):
        super().__init__(logger=logger)
        self._data_names = tuple(data_names)
        self._label_names = tuple(label_names or ())
        self._output_names = tuple(output_names)
        self._data_shapes = None
        self._label_shapes = None
        self._output_shapes = None

    @property
    def data_names(self):
        return self._data_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        return self._output_shapes

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False,
             shared_module=None, grad_req="write"):
        if self.binded and not force_rebind:
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._data_shapes = [_as_data_desc(x) for x in data_shapes]
        self._label_shapes = ([_as_data_desc(x) for x in label_shapes]
                              if label_shapes else None)
        self._output_shapes = self._compute_output_shapes()
        self.binded = True

    def _compute_output_shapes(self):
        """[(name, shape)] of this module's outputs — subclass hook."""
        raise NotImplementedError()

    # -- parameters: none by default ---------------------------------------
    def get_params(self):
        return {}, {}

    def init_params(self, initializer=None, arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        self.params_initialized = True

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        self.optimizer_initialized = True

    def update(self):
        pass

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        if self._label_shapes is not None:
            eval_metric.update(labels, self.get_outputs())


class PythonLossModule(PythonModule):
    """A Python-defined loss head (parity: module/python_module.py
    PythonLossModule): forward caches the incoming scores, backward
    produces the input gradient from ``grad_func(scores, labels)`` —
    the escape hatch for losses that are awkward as symbols, typically
    as the last stage of a SequentialModule."""

    def __init__(self, name="pyloss", data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 grad_func=None):
        super().__init__([name + "_" + d for d in data_names],
                         label_names,
                         [name + "_output"], logger=logger)
        self._name = name
        self._scores = None
        self._labels = None
        self._scores_grad = None
        if grad_func is not None and not callable(grad_func):
            raise TypeError("grad_func must be callable")
        self._grad_func = grad_func

    def _compute_output_shapes(self):
        # loss passes scores through: one output, shaped like the input
        return [(self._name + "_output", self._data_shapes[0].shape)]

    def forward(self, data_batch, is_train=None):
        self._scores = data_batch.data[0]
        if data_batch.label:
            self._labels = data_batch.label[0]

    def get_outputs(self, merge_multi_context=True):
        return [self._scores]

    def backward(self, out_grads=None):
        assert out_grads is None, \
            "PythonLossModule is a loss head; it accepts no head grads"
        if self._grad_func is None:
            raise NotImplementedError(
                "PythonLossModule requires grad_func (the reference's "
                "fallback was an RTC CUDA kernel; provide the gradient "
                "of your loss w.r.t. the scores)")
        grad = self._grad_func(self._scores, self._labels)
        if not isinstance(grad, nd.NDArray):
            grad = nd.array(grad)
        self._scores_grad = grad

    def get_input_grads(self, merge_multi_context=True):
        return [self._scores_grad]
