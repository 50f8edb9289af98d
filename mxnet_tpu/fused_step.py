"""Fused train step: forward + backward + optimizer update as ONE donated
XLA computation per step.

The reference engine dispatches the train step as hundreds of engine pushes
(forward graph, backward graph, one optimizer op + one grad-zeroing write
PER PARAMETER — ~320 host-side dispatches/step for ResNet-50).  PyGraph
(arXiv 2503.19779) and μ-cuDNN (arXiv 1804.04806) both show that capturing
the whole step into one executable is the largest step-time win on
accelerator-bound loops; the TPU equivalent is one ``jax.jit`` over
forward + VJP + the whole-pytree optimizer update, with ``donate_argnums``
on weights, optimizer state and aux stats so XLA reuses the buffers
in place.

Contracts kept:

* **Parity** with the per-param loop for every optimizer exposing
  ``fused_update`` (SGD/momentum/multi-precision, Adam): the trace mirrors
  the executor's ``fwd_vjp`` formulation (same cotangents, same grad
  dtype casts) and the per-op update math, and consumes ONE
  ``random.next_key()`` per step like ``Executor.forward``.  The update
  math is bit for bit the per-op math.  A whole step agrees with the
  loop's bit for bit in its outputs and in the last layer only: the
  loop compiles forward and backward as two programs, this step as one,
  and XLA then sums the backward products of the layers below the last
  in another order (a few float32 spacings a step on the CPU;
  ``tests/test_fused_step.py::test_fused_parity_with_the_loop`` states
  the bounds).  The scanned window IS bit-equal to K of these steps, and
  the mesh window to the per-param kvstore loop (their tests).
* **Views stay consistent**: after a step the module's ``arg_dict`` /
  ``aux_dict`` NDArrays hold the new buffers, ``grad_dict`` reads as
  zeros (write-mode semantics, served from cached zero buffers — no
  dispatch), optimizer state lives in the SAME ``Updater.states``
  NDArrays, and ``exec.outputs`` carries the forward outputs — metrics,
  monitors-off checkpointing and ``get_optimizer_states`` work unchanged.
* **No recompiles across lr schedules**: lr/wd (and Adam's bias
  correction) are evaluated host-side once per step by
  ``Optimizer.fused_hyperparams`` and handed to the jitted step as two
  host ``float32`` arrays (``host_hyperparams``), one element per
  trainable tensor: two argument leaves whatever the model, indexed per
  parameter inside the trace (``hyper_scalars``).
* **Donation safety**: buffers that were not produced by this step's own
  jit output (externally set params, freshly restored optimizer state)
  are defensively copied before being donated, so arrays the user still
  holds are never invalidated.

Opt-out: ``MXNET_FUSED_STEP=0`` (config.py).  Ineligible setups (kvstore,
monitors, custom optimizers without ``fused_update``, grad_req "add",
group2ctx) silently keep the per-param loop; ``python -m
mxnet_tpu.fused_step`` is the CI smoke asserting <= 3 dispatches/step and
loop parity.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from . import profiler as _prof
from . import random as _random
from . import telemetry as _telemetry
from .base import MXNetError
from .ndarray import NDArray
from .telemetry import numerics as _numerics

log = logging.getLogger(__name__)


def _as_buf(x):
    return x._data if isinstance(x, NDArray) else x


def host_hyperparams(opt, indices, steps=None):
    """The learning rates and weight decays of the next step as two host
    ``float32`` arrays of shape ``(len(indices),)``, or of the next
    ``steps`` steps of a scanned window as ``(steps, len(indices))``.

    Two leaves of the jitted call however many tensors the model has: a
    Python float per tensor is a host-to-device transfer each, made
    inside the call with the device idle (ResNet-50: 322 of them, 60 ms
    a step).  The update counts are bumped before the hyperparameters are
    read, once a step, like each per-param ``update()`` does
    (``fused_window_hyperparams`` does the same row by row)."""
    if steps is None:
        for i in indices:
            opt._update_count(i)
        lrs, wds = opt.fused_hyperparams(indices)
    else:
        lrs, wds = opt.fused_window_hyperparams(indices, steps)
    return np.asarray(lrs, np.float32), np.asarray(wds, np.float32)


def hyper_scalars(lrs, wds, params, states):
    """Inside the trace: one row of each ``host_hyperparams`` array as
    the per-parameter ``(lr_t, wd_t)`` lists ``Optimizer.fused_update``
    takes.

    An element of a ``float32`` array is strongly typed, where the
    Python float it replaces was weak: against a ``float16``/``bfloat16``
    weight it would promote the whole update to ``float32``, and the new
    weight would no longer alias its donated input (nor fit a scan
    carry).  So each scalar is cast to the dtype its update computes in,
    the widest of the weight and its optimizer state (``float32`` under
    multi-precision, through the master copy) — the value a weak scalar
    is converted to.  ``float32`` weights take no cast."""
    lr_t, wd_t = [], []
    for i, (w, s) in enumerate(zip(params, states)):
        dtype = jnp.result_type(w, *jax.tree_util.tree_leaves(s))
        for row, out in ((lrs, lr_t), (wds, wd_t)):
            x = row[i]
            out.append(x if x.dtype == dtype else x.astype(dtype))
    return lr_t, wd_t


class FusedTrainStep:
    """One-dispatch train step bound to a Module's executor + optimizer."""

    def __init__(self, module):
        exec_ = module._exec
        self._module = module
        self._exec_ref = exec_
        self._opt_ref = module._optimizer
        self._arg_names = list(exec_._arg_names)
        self._aux_names = list(exec_._aux_names)
        # trainable = optimizer-updated: grad_req "write" (eligibility
        # already excluded "add"); fixed/"null" params are frozen on both
        # paths
        self._train = [(i, n) for i, n in enumerate(module._param_names)
                       if exec_.grad_req.get(n, "null") == "write"]
        if not self._train:
            raise MXNetError("fused step: no trainable parameters")
        self._train_names = [n for _, n in self._train]
        self._opt_indices = [i for i, _ in self._train]
        train_set = set(self._train_names)
        self._train_slots = [self._arg_names.index(n)
                             for n in self._train_names]
        self._other_names = [n for n in self._arg_names
                             if n not in train_set]
        self._other_slots = [self._arg_names.index(n)
                             for n in self._other_names]
        self._feed_names = set(module._data_names) | \
            set(module._label_names)
        self._device = module._context.jax_device
        # ownership ledger: buffers produced by OUR last jit call may be
        # donated freely; anything else could still be referenced outside
        # (user-held arg_params, restored optimizer state) and is copied
        # once before its first donation
        self._owned = {}
        self._static_sig = None
        self._jit = None
        self._trace_count = 0  # bumped at trace time; tests assert == 1
        self._just_built = False  # next dispatch carries the compile
        # numerics observatory (ISSUE 14): mode + stat bucket plan are
        # baked into the trace signature — arming retraces, never drifts
        self._num_mode = "off"
        self._num_poison = False
        self._num_groups = []
        self._num_labels = []
        self.steps = 0

    def _numerics_plan(self):
        """Freeze the observatory mode + stat buckets for the next
        trace (dtype-contiguous parameter groups, same rule as the
        collective planner, so a poisoned bucket names a model region).
        The poison-injection multiply is baked in only while the chaos
        ``train/poison_grad`` site is armed."""
        exec_ = self._module._exec
        self._num_mode = _numerics.trace_mode()
        self._num_poison = False
        if self._num_mode == "off":
            self._num_groups, self._num_labels = [], []
            return
        self._num_poison = _numerics.poison_armed()
        shapes = [tuple(exec_.arg_dict[n].shape)
                  for n in self._train_names]
        dtypes = [str(exec_.arg_dict[n]._data.dtype)
                  for n in self._train_names]
        self._num_groups, self._num_labels = _numerics.stat_groups(
            shapes, dtypes, names=self._train_names)

    def _numerics_sig(self):
        """The observatory's contribution to the trace signature."""
        return (_numerics.trace_mode(),
                _numerics.trace_mode() != "off" and
                _numerics.poison_armed())

    # -- trace -------------------------------------------------------------
    def _build_jit(self):
        # compilation lifecycle (ISSUE 7): artifacts persist across
        # processes, and every rebuild is a ledger event — a retrace
        # storm shows up in mxnet_compile_traces_total, not in step time
        from . import compile as _compile
        _compile.ensure_persistent_cache()
        _compile.record_trace(
            "fused_step",
            "build" if self._jit is None else "signature-change")
        self._just_built = True
        module = self._module
        fn = module._exec._build_fn(True)
        opt = module._optimizer
        n_args = len(self._arg_names)
        train_slots = tuple(self._train_slots)
        other_slots = tuple(self._other_slots)
        self._numerics_plan()
        num_mode = self._num_mode
        num_groups = self._num_groups
        num_poison = self._num_poison
        outer = self

        def step(key, train_vals, other_vals, aux_vals, states, lrs, wds,
                 poison):
            outer._trace_count += 1  # host side effect: runs at trace only

            def fwd(*tv):
                full = [None] * n_args
                for slot, v in zip(train_slots, tv):
                    full[slot] = v
                for slot, v in zip(other_slots, other_vals):
                    full[slot] = v
                return fn(key, tuple(full), aux_vals)

            # mirror Executor.forward(is_train=True)+backward(): vjp over
            # the trainable args, all-ones cotangents on the outputs,
            # zeros on the mutated aux, grads cast to the weight dtype
            (outs, new_aux), vjp_fn = jax.vjp(fwd, *train_vals)
            cts = tuple(jnp.ones_like(o) for o in outs)
            zero_aux = tuple(jnp.zeros_like(a) for a in new_aux)
            grads = vjp_fn((cts, zero_aux))
            grads = [g.astype(w.dtype) for g, w in zip(grads, train_vals)]
            if num_poison:
                # chaos train/poison_grad rides this scalar (1.0 = IEEE
                # identity, bitwise no-op; NaN/Inf poisons the window);
                # baked in only while the site is armed, so production
                # armed windows pay zero extra gradient traffic
                grads = [g * poison.astype(g.dtype) for g in grads]
            with jax.named_scope("step/optimizer"):
                new_params, new_states = opt.fused_update(
                    list(train_vals), grads, list(states),
                    *hyper_scalars(lrs, wds, train_vals, states))
            if num_mode != "off":
                # numerics observatory (ISSUE 14): health stats ride the
                # same donated dispatch; skip mode gates the poisoned
                # update on device (the loss-scaler idiom, no extra sync)
                new_params, (new_aux, new_states), stats = \
                    _numerics.trace_step(
                        num_mode, grads, list(outs), train_vals,
                        new_params, [(new_aux, aux_vals),
                                     (new_states, states)], num_groups)
                stats = _numerics.window_param_stats(
                    stats, new_params, train_vals)
                return outs, new_aux, tuple(new_params), new_states, stats
            return outs, new_aux, tuple(new_params), new_states, ()

        # donate weights (1), aux stats (3) and optimizer state (4):
        # XLA aliases them onto the matching outputs — in-place reuse,
        # and grad buffers never materialize between dispatches at all
        self._jit = jax.jit(step, donate_argnums=(1, 3, 4))

    # -- per-step host path ------------------------------------------------
    def _owned_or_copy(self, token, buf, sharding=None):
        if self._owned.get(token) is buf:
            return buf
        # not produced by our own last step: copy so donation cannot
        # invalidate an alias the caller still holds (set_params shares
        # buffers with the user's arg_params dict).  A mesh-fused
        # subclass passes its parameter ``sharding`` so externally-set
        # buffers (single-device restores, user arg_params) land
        # replicated/sharded on the mesh before their first donation.
        # The copy is also COMMITTED to its placement: initializers hand
        # over uncommitted arrays, every output of the step is committed,
        # and jit keys its executable on that — left alone, the second
        # step compiled the whole program a second time.
        return jax.device_put(
            buf.copy(), self._device if sharding is None else sharding)

    def _stage_carry(self, sharding=None):
        """Stage the donated carry: ``(train_vals, aux_vals, states,
        states_nd)`` with every buffer either produced by our own last
        dispatch (donate freely) or ledger-copied (and re-placed onto
        ``sharding`` when given).  Optimizer state is created lazily
        through the SAME ``Updater`` the loop path uses, so checkpoint
        get/set_optimizer_states and a later fallback to the loop see
        one state store."""
        module = self._module
        exec_ = module._exec
        updater = module._updater
        for i, name in self._train:
            updater._ensure_state(i, exec_.arg_dict[name])
        states_nd = [updater.states[i] for i in self._opt_indices]
        train_vals = tuple(
            self._owned_or_copy(("p", n), exec_.arg_dict[n]._data, sharding)
            for n in self._train_names)
        aux_vals = tuple(
            self._owned_or_copy(("a", n), exec_.aux_dict[n]._data, sharding)
            for n in self._aux_names)
        leaf_counter = [0]

        def stage_state(leaf):
            tok = ("s", leaf_counter[0])
            leaf_counter[0] += 1
            return self._owned_or_copy(tok, _as_buf(leaf), sharding)

        states = jax.tree_util.tree_map(stage_state, states_nd)
        return train_vals, aux_vals, states, states_nd

    def _writeback_carry(self, tv, av, st, states_nd):
        """Swap the NEW buffers into the existing NDArray views so
        arg_dict/aux_dict/updater.states stay the canonical handles
        (zero extra dispatches — these are reference swaps), and record
        them in the ownership ledger for the next donation."""
        exec_ = self._module._exec
        owned = {}
        for name, buf in zip(self._train_names, tv):
            exec_.arg_dict[name]._set_data(buf)
            owned[("p", name)] = buf
        for name, buf in zip(self._aux_names, av):
            exec_.aux_dict[name]._set_data(buf)
            owned[("a", name)] = buf
        leaf_counter = [0]

        def writeback_state(old, new):
            tok = ("s", leaf_counter[0])
            leaf_counter[0] += 1
            owned[tok] = new
            old._set_data(new)

        jax.tree_util.tree_map(writeback_state, states_nd, st)
        self._owned = owned

    def step(self, data_batch):
        """Run one fused step.  Returns False (caller falls back to the
        per-param loop) when the batch doesn't match the bound shapes —
        partial final batches take the reshape path like before."""
        module = self._module
        exec_ = module._exec
        feed = {}
        for desc, arr in zip(module._data_shapes, data_batch.data):
            feed[desc.name] = arr
        if module._label_shapes and data_batch.label:
            for desc, arr in zip(module._label_shapes, data_batch.label):
                feed[desc.name] = arr
        for name, arr in feed.items():
            bound = exec_.arg_dict.get(name)
            if bound is None or tuple(arr.shape) != tuple(bound.shape):
                return False

        with _telemetry.span("fit/step/prepare"):
            opt = module._optimizer
            sig = (opt.fused_static_signature(), self._numerics_sig())
            if self._jit is None or sig != self._static_sig:
                self._build_jit()
                self._static_sig = sig

            # stage the feed: device placement + the same dtype cast the
            # arg_dict[:]= path applies (no-ops when already staged/typed)
            dev = self._device
            feed_bufs = {}
            for name, arr in feed.items():
                buf = _as_buf(arr)
                if dev not in buf.devices():
                    buf = jax.device_put(buf, dev)
                bound = exec_.arg_dict[name]
                if buf.dtype != bound._data.dtype:
                    buf = buf.astype(bound._data.dtype)
                feed_bufs[name] = buf

            train_vals, aux_vals, states, states_nd = self._stage_carry()
            if self._just_built:
                # resource observatory (ISSUE 13): a (re)build re-states the
                # donated carry's device footprint — host shape math only,
                # never on the steady-state per-step path
                _telemetry.resources.account_train_step(
                    "fused_step", params=train_vals, opt_state=states,
                    aux=aux_vals)
            other_vals = tuple(
                feed_bufs[n] if n in feed_bufs else exec_.arg_dict[n]._data
                for n in self._other_names)

            # host-side hyperparameter evaluation ONCE per step (lr
            # schedules must not bake into the trace)
            lrs, wds = host_hyperparams(opt, self._opt_indices)

            key = _random.next_key()
            poison = _numerics.poison_value() if self._num_poison \
                else np.float32(1.0)
            args = (key, train_vals, other_vals, aux_vals, states,
                    lrs, wds, poison)
            host_args = _telemetry.host_arg_stats(args, {dev}) \
                if _telemetry.enabled() else None
        with _telemetry.span("fit/step/fused_dispatch"):
            _telemetry.record_step_host_args("fused", host_args)
            if self._just_built:
                # first dispatch after a (re)trace: charge its backend
                # compile to the fused step in the TraceLedger
                from . import compile as _compile
                with _compile.LEDGER.attribute("fused_step"):
                    outs, new_aux, new_params, new_states, stats = \
                        self._jit(*args)
                self._just_built = False
            else:
                outs, new_aux, new_params, new_states, stats = \
                    self._jit(*args)
        with _telemetry.span("fit/step/writeback"):
            _prof.record_dispatch("fused_step")

            self._writeback_carry(new_params, new_aux, new_states, states_nd)
            for name, buf in feed_bufs.items():
                exec_.arg_dict[name]._set_data(buf)

            module._zero_grads()
            exec_.outputs = [NDArray(o, module._context) for o in outs]
            exec_._vjp_holder = None
            exec_._last_is_train = True
            self.steps += 1
            _prof.record_counter("train:fused_step_total", self.steps)
        if self._num_mode != "off":
            # boundary check: one tiny host read; halt mode raises typed
            # NonFiniteError here, AFTER the views are consistent
            _numerics.observe_window(
                stats, kind="fused_step", first_step=self.steps,
                window=self.steps, group_labels=self._num_labels)
        return True

    def stale(self, module):
        return (module._exec is not self._exec_ref
                or module._optimizer is not self._opt_ref)


class ScanTrainStep(FusedTrainStep):
    """K fused train steps as ONE donated XLA dispatch (``jax.lax.scan``).

    The fused step body (forward + VJP + optimizer update) becomes the
    scan body; weights / optimizer state / aux stats are the carry, the
    staged super-batch (one stacked array per input, leading dims
    ``(K, M)``) and the host-evaluated per-step lr/wd vectors are the
    scanned inputs, and the per-step forward outputs come back stacked so
    metric updates at the window boundary see exactly what K sequential
    steps would have produced.  With ``accum`` M > 1 each scan step
    consumes M micro-batches sequentially (aux threads through, like M
    forwards would) and applies ONE update over their summed gradients —
    in-scan gradient accumulation for effective batches beyond HBM.

    Host control (metric flush, callbacks, checkpoint triggers, watchdog
    beats) happens only at window boundaries — the fit loop owns that
    contract (module._fit_epoch_scan).  What keeps the window equal to K
    sequential steps: ``Optimizer.fused_window_hyperparams`` bumps the
    update counts and evaluates lr/wd for all K steps up front, and they
    enter as ``(K, P)`` scanned inputs, never trace constants, so a
    schedule advances inside the window and never retraces; a
    checkpoint trigger aimed at a mid-window batch runs at the boundary
    with the boundary's ``num_update``; ``MXNET_METRIC_SYNC_INTERVAL``
    rounds up to boundaries; with ``accum`` the module's
    ``rescale_grad`` divides by the effective batch; and, because up to
    K*M batches are held before they are staged, the iterator must hand
    out fresh arrays per batch (``NDArrayIter`` does)."""

    def __init__(self, module, scan_steps, accum=1):
        super().__init__(module)
        self.scan_steps = max(1, int(scan_steps))
        self.accum = max(1, int(accum))
        self._scan_jit = None
        self._scan_sig = None
        self._feed_order = None
        self._rest_names = []
        self._scan_trace_count = 0  # tests assert == 1 across an epoch
        self.windows = 0

    @property
    def window_batches(self):
        return self.scan_steps * self.accum

    # -- trace -------------------------------------------------------------
    def _build_scan_jit(self):
        from . import compile as _compile
        _compile.ensure_persistent_cache()
        _compile.record_trace(
            "scan_step",
            "build" if self._scan_jit is None else "signature-change")
        self._just_built = True
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
        self._numerics_plan()
        num_mode = self._num_mode
        num_groups = self._num_groups
        num_poison = self._num_poison
        outer = self

        def window(keys, feeds, lrs, wds, train_vals, rest_vals,
                   aux_vals, states, poison):
            outer._scan_trace_count += 1  # host side: runs at trace only

            def micro(key, feed_vals, train_vals, aux_vals):
                # one forward+VJP, identical math to the single fused step
                def fwd(*tv):
                    full = [None] * n_args
                    for slot, v in zip(train_slots, tv):
                        full[slot] = v
                    for slot, v in zip(feed_slots, feed_vals):
                        full[slot] = v
                    for slot, v in zip(rest_slots, rest_vals):
                        full[slot] = v
                    return fn(key, tuple(full), aux_vals)

                (outs, new_aux), vjp_fn = jax.vjp(fwd, *train_vals)
                cts = tuple(jnp.ones_like(o) for o in outs)
                zero_aux = tuple(jnp.zeros_like(a) for a in new_aux)
                grads = vjp_fn((cts, zero_aux))
                grads = [g.astype(w.dtype)
                         for g, w in zip(grads, train_vals)]
                return outs, new_aux, grads

            def body(carry, xs):
                tv, av, st = carry
                av0 = av
                key_s, feed_s, lr_s, wd_s = xs
                grads_sum = None
                outs_micro = []
                for m in range(accum):
                    outs, av, grads = micro(
                        key_s[m], tuple(f[m] for f in feed_s), tv, av)
                    outs_micro.append(outs)
                    grads_sum = grads if grads_sum is None else \
                        [a + b for a, b in zip(grads_sum, grads)]
                if num_poison:
                    grads_sum = [g * poison.astype(g.dtype)
                                 for g in grads_sum]
                if num_mode != "off":
                    # fusion fence: grads now have two consumers (the
                    # optimizer update AND the stat reductions); without
                    # it XLA CPU duplicates batch-sized backward chains
                    # into each consumer's fusion — measured at >10% of
                    # step wall.  The barrier materializes grads once.
                    grads_sum = list(jax.lax.optimization_barrier(
                        tuple(grads_sum)))
                with jax.named_scope("step/optimizer"):
                    new_params, new_states = opt.fused_update(
                        list(tv), grads_sum, list(st),
                        *hyper_scalars(lr_s, wd_s, tv, st))
                ys = tuple(jnp.stack([o[i] for o in outs_micro])
                           for i in range(len(outs_micro[0])))
                if num_mode != "off":
                    # in-scan health stats: one extra scanned output, no
                    # extra dispatch; skip mode gates THIS step's update
                    new_params, (av, new_states), stats = \
                        _numerics.trace_step(
                            num_mode, grads_sum, [ys[0]], tv, new_params,
                            [(av, av0), (new_states, st)], num_groups)
                    ys = ys + (stats,)
                return (tuple(new_params), av, new_states), ys

            carry, ys = jax.lax.scan(
                body, (train_vals, aux_vals, states),
                (keys, feeds, lrs, wds))
            tv, av, st = carry
            if num_mode != "off":
                stats = _numerics.window_param_stats(
                    ys[-1], tv, train_vals)
                return tv, av, st, ys[:-1], stats
            return tv, av, st, ys, ()

        # donate the carry inputs (weights / aux / optimizer state): the
        # scan's final carry aliases them in place, exactly like the
        # single-step donation — one buffer set for the whole window
        self._scan_jit = jax.jit(window, donate_argnums=(4, 6, 7))

    # -- per-window host path ----------------------------------------------
    def run_window(self, sbatch):
        """Dispatch one K-step (x M micro-batch) window.  ``sbatch`` is an
        ``io.SuperBatch`` whose data/label arrays are stacked buffers
        with leading dim K*M — device arrays, or host numpy stacks when
        the streaming window feed pre-staged them off-thread
        (``stage_super_batch(host=True)``); jit placement makes the two
        bitwise-equivalent.  Returns the list of per-position output
        buffers flattened to leading dim K*M (for boundary metric
        updates), or False when the window is short or the stacked
        shapes don't match the bound executor (caller falls back to
        per-batch steps)."""
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

        with _telemetry.span("fit/window/prepare"):
            opt = module._optimizer
            sig = (opt.fused_static_signature(), K, M,
                   self._numerics_sig(),
                   tuple(sorted((n, tuple(a.shape), str(a.dtype))
                                for n, a in feed.items())))
            if self._scan_jit is None or sig != self._scan_sig:
                self._feed_order = sorted(feed)
                self._build_scan_jit()
                self._scan_sig = sig

            # stage the stacked feeds: (K, M, *batch_shape), bound dtype
            feed_bufs = []
            for name in self._feed_order:
                buf = feed[name]
                bound = exec_.arg_dict[name]
                if buf.dtype != bound._data.dtype:
                    buf = buf.astype(bound._data.dtype)
                feed_bufs.append(buf.reshape((K, M) + tuple(bound.shape)))

            train_vals, aux_vals, states, states_nd = self._stage_carry()
            if self._just_built:
                _telemetry.resources.account_train_step(
                    "scan_step", params=train_vals, opt_state=states,
                    aux=aux_vals)
            rest_vals = tuple(exec_.arg_dict[n]._data
                              for n in self._rest_names)

            # host-side hyperparameters for the WHOLE window: K rows of
            # lr/wd, update counts bumped per step exactly like K sequential
            # fused steps — schedules advance inside the scan, no retrace
            lrs, wds = host_hyperparams(opt, self._opt_indices, K)
            # one key per micro forward, same counter stream as W sequential
            # steps (bitwise-identical randomness)
            keys = np.stack([np.asarray(_random.next_key())
                             for _ in range(W)])
            keys = keys.reshape((K, M) + keys.shape[1:])

            poison = _numerics.poison_value() if self._num_poison \
                else np.float32(1.0)
            args = (keys, tuple(feed_bufs), lrs, wds, train_vals,
                    rest_vals, aux_vals, states, poison)
            host_args = _telemetry.host_arg_stats(args, {self._device}) \
                if _telemetry.enabled() else None
        with _telemetry.span("fit/step/scan_dispatch"):
            _telemetry.record_step_host_args("scan", host_args)
            if self._just_built:
                from . import compile as _compile
                with _compile.LEDGER.attribute("scan_step"):
                    tv, av, st, ys, stats = self._scan_jit(*args)
                self._just_built = False
            else:
                tv, av, st, ys, stats = self._scan_jit(*args)
        with _telemetry.span("fit/window/writeback"):
            _prof.record_dispatch("scan_window")

            self._writeback_carry(tv, av, st, states_nd)

            module._zero_grads()
            # (K, M, *out) -> (K*M, *out): position j is micro-batch j's
            # forward outputs, computed with that step's pre-update weights —
            # the boundary metric sees what W sequential steps produced
            outs_flat = [y.reshape((W,) + tuple(y.shape[2:])) for y in ys]
            exec_.outputs = [NDArray(y[W - 1], module._context)
                             for y in outs_flat]
            exec_._vjp_holder = None
            exec_._last_is_train = True
            self.steps += K
            self.windows += 1
            _prof.record_counter("train:fused_step_total", self.steps)
        if self._num_mode != "off":
            # window-boundary check: the host's only read of the stats
            # (one tiny transfer); halt raises typed NonFiniteError here
            _numerics.observe_window(
                stats, kind="scan_window",
                first_step=self.steps - K + 1, window=self.windows,
                group_labels=self._num_labels)
        return outs_flat


def _smoke():
    """CI gate: the fused path must issue <= 3 framework dispatches per
    step and match the per-param loop bitwise (run via
    ``python -m mxnet_tpu.fused_step``; see ci/run.sh)."""
    import os
    import sys

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio

    def build():
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=64, name="fc1")
        h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    x = rng.randn(32, 50).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.float32)
    batch = mxio.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    init = {"fc1_weight": mx.nd.array(rng.randn(64, 50) * 0.1),
            "fc1_bias": mx.nd.zeros((64,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 64) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}

    def run(fused, steps=5):
        os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
        mx.random.seed(0)
        mod = mx.mod.Module(build(), context=mx.cpu())
        mod.bind(data_shapes=[("data", x.shape)],
                 label_shapes=[("softmax_label", y.shape)])
        mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        mod.forward_backward(batch)
        mod.update()  # warm: compiles outside the counted window
        mx.profiler.reset_dispatch_counts()
        for _ in range(steps):
            mod.forward_backward(batch)
            mod.update()
        counts = mx.profiler.dispatch_counts()
        params, _ = mod.get_params()
        return counts, {k: v.asnumpy() for k, v in params.items()}

    counts_f, params_f = run(True)
    counts_l, params_l = run(False)
    per_step = counts_f.get("total", 0) / 5
    print(f"fused: {per_step:.1f} dispatches/step {counts_f}; "
          f"loop: {counts_l.get('total', 0) / 5:.1f} {counts_l}")
    if per_step > 3:
        print("FAIL: fused path exceeds 3 dispatches/step", file=sys.stderr)
        sys.exit(1)
    if counts_f.get("fused_step", 0) != 5:
        print("FAIL: fused step did not engage", file=sys.stderr)
        sys.exit(1)
    for k in params_f:
        if not np.array_equal(params_f[k], params_l[k]):
            print(f"FAIL: fused/loop parity broke on {k}", file=sys.stderr)
            sys.exit(1)
    print("fused step smoke OK: <=3 dispatches/step, bitwise loop parity")


def _scan_smoke():
    """CI gate for the scanned window: at K=8 a fit epoch must issue
    <= (1+eps)/K dispatches per train step and stay bitwise identical to
    the sequential fused loop (run via ``python -m mxnet_tpu.fused_step``
    after the single-step smoke; see ci/run.sh)."""
    import os
    import sys

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio

    K, NB, BS = 8, 16, 32  # two full windows per epoch

    def build():
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=64, name="fc1")
        h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    x = rng.randn(NB * BS, 50).astype(np.float32)
    y = rng.randint(0, 10, NB * BS).astype(np.float32)
    init = {"fc1_weight": mx.nd.array(rng.randn(64, 50) * 0.1),
            "fc1_bias": mx.nd.zeros((64,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 64) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}

    def run(scan_k):
        os.environ["MXNET_FUSED_STEP"] = "1"
        os.environ["MXNET_SCAN_STEPS"] = str(scan_k)
        mx.random.seed(0)
        it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y),
                              batch_size=BS, label_name="softmax_label")
        mod = mx.mod.Module(build(), context=mx.cpu())
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                arg_params={k: v.copy() for k, v in init.items()})
        mx.profiler.reset_dispatch_counts()
        it.reset()
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        counts = mx.profiler.dispatch_counts()
        params, _ = mod.get_params()
        return counts, {k: v.asnumpy() for k, v in params.items()}

    counts_s, params_s = run(K)
    counts_q, params_q = run(1)
    os.environ["MXNET_SCAN_STEPS"] = "1"
    per_step = counts_s.get("total", 0) / NB
    budget = (1 + 0.25) / K
    print(f"scan K={K}: {per_step:.3f} dispatches/step {counts_s}; "
          f"sequential: {counts_q.get('total', 0) / NB:.2f} {counts_q}; "
          f"budget {budget:.3f}")
    if counts_s.get("scan_window", 0) != NB // K:
        print("FAIL: scanned window did not engage", file=sys.stderr)
        sys.exit(1)
    if per_step > budget:
        print(f"FAIL: scan path exceeds {budget:.3f} dispatches/step",
              file=sys.stderr)
        sys.exit(1)
    for k in params_s:
        if not np.array_equal(params_s[k], params_q[k]):
            print(f"FAIL: scan/sequential parity broke on {k}",
                  file=sys.stderr)
            sys.exit(1)
    print(f"scan smoke OK: <= {budget:.3f} dispatches/step at K={K}, "
          "bitwise parity with the sequential fused loop")


if __name__ == "__main__":
    _smoke()
    _scan_smoke()
