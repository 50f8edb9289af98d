"""mxnet_tpu.telemetry — unified observability layer (ISSUE 5 tentpole).

One import gives four subsystems one set of eyes:

* **spans** (:func:`span`) — nestable, thread-safe timed regions that
  merge into the profiler's chrome-trace stream, jax xplane traces, and
  the ``mxnet_span_seconds`` histogram; ~zero-cost while disabled.
* **registry** (:data:`REGISTRY`) — process-wide counters / gauges /
  histograms plus pull-collectors that absorb ``serving.stats()``,
  ``CheckpointManager.stats()``, profiler dispatch lanes, kvstore wire
  bytes and io staging waits behind ONE :func:`snapshot` and a
  Prometheus :func:`prometheus_dump` / HTTP endpoint
  (``MXNET_TELEMETRY_PORT``).
* **step breakdown** (:mod:`steps`) — ``Module.fit`` attributes each
  train step's wall time to lanes (``data_wait`` / ``h2d_stage`` /
  ``step_dispatch`` / ``device_block`` / ``metric_flush`` /
  ``ckpt_block``), surfaced by ``callback.StepTimeline``.
* **watchdog** (:mod:`watchdog`) — ``MXNET_WATCHDOG_S``: all-thread
  stack + snapshot dumps when the train loop or a serving batcher stops
  making progress.

Enable spans + step lanes with ``MXNET_TELEMETRY=1`` or
:func:`enable`; the registry and collectors are always live (they cost
nothing until read).  See docs/observability.md for the metric catalog,
span naming convention, and the watchdog runbook.
"""
from __future__ import annotations

import sys
import weakref

from . import registry as _registry_mod
from . import spans as _spans
from . import steps as _steps
from . import alerts
from . import fleet
from . import flight
from . import numerics
from . import resources
from . import trace
from . import watchdog
from .exporter import exporter_port, start_exporter, stop_exporter
from .registry import MetricsRegistry, exponential_buckets
from .spans import (count_in_span, current_span, current_step, disable,
                    enable, enabled, host_arg_stats, next_step,
                    reset_span_records, span, span_records, span_stack)
from .steps import (LANES, current_step_timer, reset_step_stats,
                    step_breakdown, step_timer)

heartbeat = watchdog.beat

#: the process-wide registry every subsystem reports into
REGISTRY = MetricsRegistry()

counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram
register_collector = REGISTRY.register_collector

# -- built-in instruments ----------------------------------------------------
_spans._span_hist = REGISTRY.histogram(
    "mxnet_span_seconds", "telemetry.span durations by span name")
_steps._lane_hist = REGISTRY.histogram(
    "mxnet_train_step_lane_seconds",
    "per-train-step time attributed to each breakdown lane")
_steps._step_hist = REGISTRY.histogram(
    "mxnet_train_step_seconds", "train step wall time (fit loop)")
trace._stage_hist = REGISTRY.histogram(
    "mxnet_trace_stage_seconds",
    "per-trace stage durations (end-to-end request/window tracing), "
    "by trace kind and stage name")
trace._e2e_hist = REGISTRY.histogram(
    "mxnet_trace_e2e_seconds",
    "end-to-end latency of finished traces, by trace kind")

_KV_BYTES = REGISTRY.counter(
    "mxnet_kvstore_bytes_total",
    "payload bytes moved through kvstore push/pull, by op")
_KV_OPS = REGISTRY.counter(
    "mxnet_kvstore_ops_total", "kvstore push/pull calls, by op")
_IO_STAGE = REGISTRY.histogram(
    "mxnet_io_stage_seconds",
    "host time spent staging a DataBatch host->device (io.stage_batch)")
_IO_STAGE_BYTES = REGISTRY.counter(
    "mxnet_io_stage_bytes_total", "bytes staged host->device by io")
_SPMD_BATCH_ARRAYS = REGISTRY.counter(
    "mxnet_spmd_batch_arrays_total",
    "arrays parallel.spmd.shard_batch placed on a mesh, by path: host "
    "(shard by shard from host memory, each device sent its own rows) or "
    "device (an array already on a device, resharded)")
_IO_STAGE_WINDOWS = REGISTRY.counter(
    "mxnet_io_stage_windows_total",
    "scanned-fit windows staged by io.stage_super_batch, by when: "
    "ahead (while the previous window's scan was in flight: the fit "
    "loop after a dispatch, or the WindowFeed thread) or at_need (on "
    "the train thread with nothing dispatched: the first window of an "
    "epoch, the first after a per-batch fallback)")
_STEP_HOST_ARG_LEAVES = REGISTRY.counter(
    "mxnet_step_host_arg_leaves",
    "leaves of a train step call's arguments that were not arrays "
    "already on the step's device or mesh (Python and numpy scalars, "
    "numpy arrays, host-CPU arrays): each is copied inside the call; by "
    "step (fused/scan/spmd); counted only while telemetry is enabled")
_STEP_HOST_ARG_BYTES = REGISTRY.counter(
    "mxnet_step_host_arg_bytes",
    "bytes of the leaves mxnet_step_host_arg_leaves counts, by step")
_TRAINER_UPDATE_CALLS = REGISTRY.counter(
    "mxnet_trainer_update_calls_total",
    "optimizer programs launched by gluon.Trainer._update: one per "
    "context for every dense tensor of the step, plus one per tensor that "
    "took the per-tensor updater call; counted only while telemetry is "
    "enabled")
_CACHED_OP_AUX_OUTPUTS = REGISTRY.counter(
    "mxnet_cached_op_aux_outputs_total",
    "parameters a hybridized block's recorded forward mutated (BatchNorm's "
    "running statistics) and returned as auxiliary outputs of its jax.vjp: "
    "the pullback takes no cotangent for them; counted only while "
    "telemetry is enabled")
_CACHED_OP_RESIDUAL_LEAVES = REGISTRY.counter(
    "mxnet_cached_op_residual_leaves_total",
    "residual arrays the pullback of a hybridized block's recorded forward "
    "holds (the leaves of its jax.vjp); counted only while telemetry is "
    "enabled")
_CACHED_OP_RESIDUAL_BUFFERS = REGISTRY.counter(
    "mxnet_cached_op_residual_buffers_total",
    "buffers those residuals crossed the host in, from the forward program "
    "to the pullback program: one per leaf of 64 KiB or more, one per dtype "
    "for all the smaller ones; counted only while telemetry is enabled")
_DATA_WAIT = REGISTRY.histogram(
    "mxnet_data_wait_seconds",
    "train-thread time blocked waiting on the streaming data plane "
    "(io_pipeline assembler/window feed); the data_wait step lane's "
    "registry twin — rising _sum rate means training is data-bound "
    "(docs/data.md runbook)")
_DATA_QUEUE_DEPTH = REGISTRY.gauge(
    "mxnet_data_queue_depth",
    "batches currently buffered in the streaming data plane "
    "(io_pipeline shard queues + window feed), by pipeline role")
_DATA_BATCHES = REGISTRY.counter(
    "mxnet_data_batches_total",
    "batches produced by streaming-data-plane reader workers "
    "(reader throughput; rate vs the fit loop's step rate says "
    "whether the readers keep up)")
_DATA_REBALANCE = REGISTRY.counter(
    "mxnet_data_rebalance_total",
    "shard rebalances after a reader worker died mid-epoch "
    "(remaining shards were requeued onto the survivors)")
_SCAN_WINDOW = REGISTRY.gauge(
    "mxnet_scan_window_steps",
    "train steps per scanned fit-window dispatch (MXNET_SCAN_STEPS; "
    "1 = one dispatch per step)")
_SCAN_WINDOW.set(1)
_REMAT_BOUNDARIES = REGISTRY.gauge(
    "mxnet_step_remat_boundaries",
    "rematerialisation boundaries (jax.checkpoint regions) the last "
    "traced parallel.spmd.TrainStep program holds: one per declared "
    "layer, 1 for a whole-forward wrap, 0 without remat")
_REMAT_SAVED = REGISTRY.gauge(
    "mxnet_step_remat_saved_residuals",
    "named residuals the rematerialisation boundaries of the last traced "
    "parallel.spmd.TrainStep program keep (gluon.block.KERNEL_RESIDUALS): "
    "the flash kernel's out and lse (ops.pallas_attention.FLASH_RESIDUALS) "
    "and the KDA kernel's o and entering states "
    "(ops.pallas_kda.KDA_RESIDUALS), two for each call of either kernel "
    "inside one, whose forward kernel the backward then does not run "
    "again; 0 without remat")
_FLASH_BWD_LOWERED = REGISTRY.counter(
    "mxnet_flash_attention_bwd_lowered_total",
    "times the backward rule of ops.pallas_attention.flash_attention was "
    "traced, by the implementation it put in the program: impl=pallas "
    "(the mx_flash_attention_bwd_* kernels) or impl=xla; one per attention "
    "layer a traced train step, none when a cached program runs")
_KDA_SCAN_LOWERED = REGISTRY.counter(
    "mxnet_kda_scan_lowered_total",
    "calls of the op _contrib_kda_scan traced into a program, by the "
    "implementation it put there: impl=pallas (the kernels mx_kda_fwd and "
    "mx_kda_bwd, ops.pallas_kda) or impl=xla (the chunked form of "
    "ops._op_linear_attention.kda_scan); one per KDA layer each time a "
    "program that holds the layer is traced, none when a cached program "
    "runs")
_FLASH_TILES = REGISTRY.gauge(
    "mxnet_flash_attention_tiles",
    "(query tile, key tile) pairs a head of the last traced "
    "ops.pallas_attention.flash_attention call, by its mask's kind and by "
    "kind=empty (not in the grid), partial (computed and masked inside) "
    "or full (computed with no mask arithmetic)")
_FLASH_GRID_STEPS = REGISTRY.gauge(
    "mxnet_flash_attention_grid_steps",
    "grid steps a query head that a kernel of the last traced "
    "ops.pallas_attention.flash_attention call was built with, by its "
    "mask's kind and by kernel=fwd, bwd_dq or bwd_dkv: the length of the "
    "kernel's tile table, which equals partial + full of "
    "mxnet_flash_attention_tiles (no grid step for an empty tile)")
_DIFFUSION_POSITIONS = REGISTRY.counter(
    "mxnet_diffusion_positions_total",
    "positions of the per-position loss weights handed to "
    "parallel.spmd.TrainStep as its third batch array (block-diffusion "
    "training: the noisy copy's positions)")
_DIFFUSION_MASKED = REGISTRY.counter(
    "mxnet_diffusion_masked_positions_total",
    "of those, the positions whose weight is not zero: the masked ones, "
    "which carry loss")
_MOE_ASSIGNMENTS = REGISTRY.gauge(
    "mxnet_moe_assignments_held",
    "(token, expert) assignments the routed-expert layers sent to the "
    "experts held here, all layers summed, a step: the mean over the steps "
    "last recorded from the block's auxiliary state (record_moe_load)")
_MOE_ROWS = REGISTRY.gauge(
    "mxnet_moe_rows_computed",
    "rows the routed-expert layers' grouped products ran, all layers "
    "summed, every expert's last tile counted whole, a step (the same "
    "mean): what exceeds mxnet_moe_assignments_held is padding")
_MOE_LOAD_SKEW = REGISTRY.gauge(
    "mxnet_moe_expert_load_max_over_mean",
    "the busiest held expert's assignments over the mean of the held "
    "experts, summed over the recorded steps, in the layer where that "
    "ratio is largest (1 = even; 0 when no layer routed anything here)")
_MOE_BIAS = REGISTRY.gauge(
    "mxnet_moe_router_bias_abs_mean",
    "mean |b| of the routers' selection biases (all layers, all experts) "
    "as the last recorded step left them: how far the balancing rule has "
    "moved the choice from the scores (0 for a router without a bias)")
_CCA_LATENT = REGISTRY.gauge(
    "mxnet_cca_latent_channels",
    "channels of the latent the last traced compressed convolutional "
    "attention layer works in, by part=q (query heads x head size) or kv "
    "(key/value heads x head size): what the projections go to, the "
    "convolutions mix and the kernel reads, against the hidden size")
_MLA_LATENT = REGISTRY.gauge(
    "mxnet_mla_latent_channels",
    "channels of what the last traced multi-head latent attention layer "
    "keeps a token, by part=kv (the joint key/value latent every head's "
    "keys and values are expanded from) or rope (the key channels shared "
    "by all heads): what a cache of the layer would hold")
_ROUTER_EDA_GAMMA = REGISTRY.gauge(
    "mxnet_router_eda_gamma_abs_mean",
    "mean |gamma| over the layers of a router that carries its state from "
    "layer to layer (exponential depth averaging, r <- W m + gamma r), as "
    "the last recorded step left it: 0 while every layer routes alone")
_COLLECTIVE_BYTES = REGISTRY.counter(
    "mxnet_collective_bytes_total",
    "logical payload bytes moved by gradient-synchronization "
    "collectives, by kind (psum/reduce_scatter/all_gather for the mesh "
    "fused step; kvstore_push/kvstore_pull for the residual per-param "
    "store path)")
_COLLECTIVE_SECONDS = REGISTRY.counter(
    "mxnet_collective_seconds",
    "seconds attributed to gradient-synchronization collectives, by "
    "kind (wall time for the kvstore path; calibrated standalone cost "
    "for collectives fused inside the mesh step program)")
_COLLECTIVE_OPS = REGISTRY.counter(
    "mxnet_collective_ops_total",
    "gradient-synchronization collective operations issued, by kind "
    "(one per bucket per step for the mesh fused step — NOT one per "
    "parameter; that is the point)")


def record_kvstore(op, nbytes, n_ops=1):
    """Account one kvstore push/pull: wire/device payload byte volume."""
    labels = {"op": op}
    _KV_BYTES.inc(int(nbytes), labels=labels)
    _KV_OPS.inc(int(n_ops), labels=labels)


def record_collective(kind, nbytes, seconds=0.0, n=1):
    """Account gradient-synchronization collectives: ``kind`` is the
    collective flavor (``psum``/``reduce_scatter``/``all_gather`` inside
    the mesh fused step, ``kvstore_push``/``kvstore_pull`` on the
    residual store path).  Byte counts are host shape arithmetic — never
    a device sync."""
    labels = {"kind": kind}
    _COLLECTIVE_BYTES.inc(int(nbytes), labels=labels)
    if seconds:
        _COLLECTIVE_SECONDS.inc(float(seconds), labels=labels)
    _COLLECTIVE_OPS.inc(int(n), labels=labels)


def record_io_stage(seconds, nbytes=0):
    """Account one io.stage_batch call (the input-feed staging wait)."""
    _IO_STAGE.observe(seconds)
    record_io_stage_bytes(nbytes)


def record_io_stage_bytes(nbytes):
    """Bytes handed to ``jax.device_put`` by input staging; they also land
    in the record of the span open around the copy."""
    if nbytes:
        count_in_span(_IO_STAGE_BYTES, int(nbytes))


def record_spmd_batch_array(path):
    """Account one array ``shard_batch`` placed, by ``path`` (host or
    device); it lands in the record of ``spmd/step/shard_batch``."""
    count_in_span(_SPMD_BATCH_ARRAYS, 1, {"path": path})


def record_io_stage_window(when):
    """Account one staged scanned window from inside ``io/stage_super``:
    ``when`` is ``ahead`` or ``at_need``."""
    count_in_span(_IO_STAGE_WINDOWS, 1, {"when": when})


def record_step_host_args(step, stats):
    """Account one train step call's host arguments from inside its
    dispatch span: ``stats`` is what ``host_arg_stats`` gave, or None
    where telemetry was off and nothing was counted; ``step`` is fused,
    scan or spmd."""
    if stats is not None:
        labels = {"step": step}
        count_in_span(_STEP_HOST_ARG_LEAVES, stats[0], labels)
        count_in_span(_STEP_HOST_ARG_BYTES, stats[1], labels)


def record_trainer_update_calls(n):
    """Account the updater calls of one ``gluon.Trainer._update``."""
    count_in_span(_TRAINER_UPDATE_CALLS, n)


def record_cached_op_aux_outputs(n):
    """Account one recorded forward of a hybridized block from inside
    ``gluon/cached_op/dispatch``: ``n`` mutated parameters left it as
    auxiliary outputs (0 for a block that mutates nothing)."""
    count_in_span(_CACHED_OP_AUX_OUTPUTS, n)


def record_cached_op_residuals(leaves, buffers):
    """Account, beside ``record_cached_op_aux_outputs``, the residuals of
    one recorded forward: the pullback's ``leaves`` and the ``buffers``
    they left the forward program in."""
    count_in_span(_CACHED_OP_RESIDUAL_LEAVES, leaves)
    count_in_span(_CACHED_OP_RESIDUAL_BUFFERS, buffers)


def record_scan_window(steps):
    """Record the active scanned-window size (Module._fit_epoch_scan)."""
    _SCAN_WINDOW.set(int(steps))


def record_remat_boundaries(n, saved_residuals):
    """Record how many rematerialisation boundaries a train step program
    was traced with (parallel.spmd.TrainStep), and how many named
    residuals they keep."""
    _REMAT_BOUNDARIES.set(int(n))
    _REMAT_SAVED.set(int(saved_residuals))


def record_flash_attention_bwd_lowered(impl):
    """Account one trace of flash attention's backward rule; ``impl`` is
    ``pallas`` or ``xla``."""
    _FLASH_BWD_LOWERED.inc(1, labels={"impl": impl})


def record_kda_scan_lowered(impl):
    """Account one traced call of ``_contrib_kda_scan``; ``impl`` is
    ``pallas`` or ``xla``."""
    _KDA_SCAN_LOWERED.inc(1, labels={"impl": impl})


def record_flash_attention_tiles(mask, counts):
    """Record the tile pairs of one traced flash attention call: ``mask``
    its mask's kind, ``counts`` ``{"empty", "partial", "full"}``."""
    for kind, n in counts.items():
        _FLASH_TILES.set(n, labels={"mask": mask, "kind": kind})


def record_flash_attention_grid_steps(mask, kernel, steps):
    """Record the grid steps a query head that one of a traced flash
    attention call's kernels (``fwd``, ``bwd_dq``, ``bwd_dkv``) walks."""
    _FLASH_GRID_STEPS.set(steps, labels={"mask": mask, "kernel": kernel})


def record_loss_weights(weights):
    """Account the per-position loss weights of one train step from inside
    ``spmd/step/shard_batch``: a host array as the caller handed it."""
    count_in_span(_DIFFUSION_POSITIONS, int(weights.size))
    count_in_span(_DIFFUSION_MASKED, int((weights != 0).sum()))


def record_moe_load(load, rows, steps=1, bias=None):
    """Record the routed-expert load of ``steps`` steps from host copies of
    a block's auxiliary state, which sums over them: ``load`` (layers,
    experts held) assignments, ``rows`` (layers,) rows the grouped products
    ran.  The two counts are set as means a step, the skew is of the sums.
    ``bias`` (layers, experts) is the routers' selection bias, where the
    model has one."""
    _MOE_BIAS.set(0.0 if bias is None else float(abs(bias).mean()))
    _MOE_ASSIGNMENTS.set(float(load.sum()) / steps)
    _MOE_ROWS.set(float(rows.sum()) / steps)
    mean = load.mean(axis=1)
    busy = mean > 0
    _MOE_LOAD_SKEW.set(
        float((load.max(axis=1)[busy] / mean[busy]).max()) if busy.any()
        else 0.0)


def record_cca_latent_channels(q, kv):
    """Record the latent widths of one traced compressed convolutional
    attention call."""
    _CCA_LATENT.set(int(q), labels={"part": "q"})
    _CCA_LATENT.set(int(kv), labels={"part": "kv"})


def record_mla_latent_channels(kv, rope):
    """Record the latent widths of one traced multi-head latent attention
    call."""
    _MLA_LATENT.set(int(kv), labels={"part": "kv"})
    _MLA_LATENT.set(int(rope), labels={"part": "rope"})


def record_router_eda_gamma(gamma):
    """Record the depth-averaging coefficients of a model's routers from
    host copies: ``gamma`` (layers, 1)."""
    _ROUTER_EDA_GAMMA.set(float(abs(gamma).mean()))


def record_data_wait(seconds):
    """Account one blocking wait on the streaming data plane (the
    consumer side: assembler ``next()`` or window-feed ``get()``)."""
    _DATA_WAIT.observe(seconds)


def record_data_batches(n=1):
    """Account batches produced by reader workers (throughput)."""
    _DATA_BATCHES.inc(int(n))


def record_data_queue_depth(depth, role="shards"):
    """Publish the current buffered-batch count for one pipeline role
    (``shards`` = reader output queues, ``feed`` = staged windows)."""
    _DATA_QUEUE_DEPTH.set(float(depth), labels={"role": role})


def record_data_rebalance(n=1):
    """Account one dead-reader shard rebalance."""
    _DATA_REBALANCE.inc(int(n))


# -- checkpoint manager registration (weak: managers come and go) ------------
_ckpt_managers = weakref.WeakSet()


def register_checkpoint_manager(manager):
    """Called by CheckpointManager.__init__ so its stats() joins the
    ``checkpoint`` collector (weakly held; close() needs no unhook)."""
    _ckpt_managers.add(manager)


# -- collectors --------------------------------------------------------------
def _serving_snapshot():
    # pull, never import: a process that never served has no serving keys
    mod = sys.modules.get("mxnet_tpu.serving.metrics")
    return mod.stats() if mod is not None else {}


def _serving_samples():
    out = []
    for name, snap in sorted(_serving_snapshot().items()):
        labels = {"server": name}
        lat = snap.get("latency_ms") or {}
        for q in ("p50", "p90", "p99"):
            if lat.get(q) is not None:
                out.append(("mxnet_serving_latency_ms", "gauge",
                            "serving request latency percentile",
                            {**labels, "quantile": q}, lat[q]))
        for key, value in sorted(snap.items()):
            if not isinstance(value, (int, float)) or \
                    isinstance(value, bool):
                continue
            mtype = "counter" if key.endswith("_total") else "gauge"
            out.append((f"mxnet_serving_{key}", mtype,
                        f"serving.stats() {key}", labels, value))
    return out


def _checkpoint_snapshot():
    return {m.directory: m.stats() for m in list(_ckpt_managers)}


def _checkpoint_samples():
    renames = {"saves": "saves_total", "failures": "failures_total",
               "gc_removed": "gc_removed_total"}
    out = []
    for directory, stats in sorted(_checkpoint_snapshot().items()):
        labels = {"directory": directory}
        for key, value in sorted(stats.items()):
            if not isinstance(value, (int, float)) or \
                    isinstance(value, bool):
                continue
            name = renames.get(key, key)
            mtype = "counter" if name.endswith("_total") else "gauge"
            out.append((f"mxnet_checkpoint_{name}", mtype,
                        f"CheckpointManager.stats() {key}", labels, value))
    return out


def _profiler_snapshot():
    from .. import profiler
    return {"dispatch": profiler.dispatch_counts(),
            "counters": profiler.last_counters()}


def _profiler_samples():
    from .. import profiler
    out = []
    for kind, n in sorted(profiler.dispatch_counts().items()):
        out.append(("mxnet_dispatch_total", "counter",
                    "framework-issued XLA computation launches, by kind",
                    {"kind": kind}, n))
    for name, value in sorted(profiler.last_counters().items()):
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            out.append(("mxnet_profiler_counter", "gauge",
                        "last value of each profiler counter lane",
                        {"counter": name}, value))
    return out


def _step_samples():
    bd = _steps.step_breakdown()
    out = [("mxnet_train_steps_total", "counter",
            "fit-loop train steps timed by the step breakdown", {},
            bd["steps"]),
           ("mxnet_train_step_wall_seconds_total", "counter",
            "total fit-loop step wall time", {}, bd["wall_s"]),
           ("mxnet_train_step_lane_seconds_total", "counter",
            "total step time attributed to each lane",
            {"lane": "other"}, bd["other_s"])]
    for lane, total in sorted(bd["lanes"].items()):
        out.append(("mxnet_train_step_lane_seconds_total", "counter",
                    "total step time attributed to each lane",
                    {"lane": lane}, total))
    return out


def _watchdog_samples():
    return [("mxnet_watchdog_fires_total", "counter",
             "hang-watchdog stall dumps written", {}, watchdog.fires())]


REGISTRY.register_collector("serving", _serving_snapshot, _serving_samples)
REGISTRY.register_collector("checkpoint", _checkpoint_snapshot,
                            _checkpoint_samples)
REGISTRY.register_collector("profiler", _profiler_snapshot,
                            _profiler_samples)
REGISTRY.register_collector("step", _steps.step_breakdown, _step_samples)
REGISTRY.register_collector(
    "watchdog",
    lambda: {"fires": watchdog.fires(), "last_dump": watchdog.last_dump()},
    _watchdog_samples)
REGISTRY.register_collector("trace", trace.exemplars)
REGISTRY.register_collector("fleet", fleet._collector_snapshot,
                            fleet._collector_samples)
REGISTRY.register_collector(
    "flight",
    lambda: {"enabled": flight.enabled(),
             "ring_events": len(flight.events()),
             "dumps": flight.dump_count()})
REGISTRY.register_collector("resources", resources._collector_snapshot,
                            resources._collector_samples)
REGISTRY.register_collector("numerics", numerics._collector_snapshot)


def _alerts_collector():
    # summary only (rule pack + full history live at /alerts.json);
    # built lazily so an unarmed process pays one dict
    if not alerts.enabled():
        return {"enabled": False}
    snap = alerts.alerts_json()
    return {"enabled": True, "ticks": snap["ticks"],
            "firing": snap["firing"], "pages": snap["pages"],
            "states": {r["name"]: r["state"] for r in snap["rules"]}}


REGISTRY.register_collector("alerts", _alerts_collector)


def snapshot():
    """Everything, one call: local metric families + serving +
    checkpoint + profiler dispatch lanes + step breakdown + watchdog."""
    return REGISTRY.snapshot()


def prometheus_dump():
    """Prometheus text exposition of :func:`snapshot`'s numeric surface."""
    return REGISTRY.prometheus_dump()


# -- env autostart -----------------------------------------------------------
def _autostart():
    from .. import config as _config
    if _config.get("MXNET_TELEMETRY"):
        enable()
    if _config.get("MXNET_TRACE"):
        trace.enable()
    flight.configure()
    numerics.configure()
    if float(_config.get("MXNET_RESOURCE_SAMPLE_S")) > 0:
        resources.start()
    if float(_config.get("MXNET_ALERTS")) > 0:
        alerts.start()
    port = int(_config.get("MXNET_TELEMETRY_PORT"))
    if port > 0:
        start_exporter(port)


_autostart()
