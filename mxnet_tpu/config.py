"""Environment-variable configuration tier.

Parity: the reference reads ~79 documented MXNET_* variables via
dmlc::GetEnv at use sites (docs/faq/env_var.md; SURVEY.md §5 config
tiers).  This module is the single typed registry for every variable
the TPU framework consumes: each entry declares type, default, and doc,
``get()`` parses with validation, and ``describe()`` renders the
env_var.md-style table so the surface is discoverable
(mx.config.describe()).

Variables the reference defines but XLA/PJRT makes moot (memory-pool
knobs, engine thread counts, cudnn autotune) are intentionally absent —
XLA owns those decisions; see SURVEY.md §7 architecture stance.
"""
from __future__ import annotations

import os

from .base import MXNetError

_REGISTRY = {}


class _Var:
    __slots__ = ("name", "vtype", "default", "doc")

    def __init__(self, name, vtype, default, doc):
        self.name = name
        self.vtype = vtype
        self.default = default
        self.doc = doc


def _register(name, vtype, default, doc):
    _REGISTRY[name] = _Var(name, vtype, default, doc)


def get(name):
    """Typed value of a registered env var (default when unset)."""
    var = _REGISTRY.get(name)
    if var is None:
        raise MXNetError(f"unknown config variable {name!r}; see "
                         "mxnet_tpu.config.describe()")
    raw = os.environ.get(name)
    if raw is None:
        return var.default
    try:
        if var.vtype is bool:
            low = raw.strip().lower()
            if low in ("1", "true", "yes", "on"):
                return True
            if low in ("0", "false", "no", "off", ""):
                return False
            raise ValueError(raw)
        return var.vtype(raw)
    except (TypeError, ValueError) as e:
        raise MXNetError(
            f"config variable {name}={raw!r} is not a valid "
            f"{var.vtype.__name__}") from e


def list_vars():
    return sorted(_REGISTRY)


def describe():
    """env_var.md-style table of every registered variable."""
    lines = [f"{'Variable':<40}{'Type':<8}{'Default':<18}Description"]
    for name in list_vars():
        v = _REGISTRY[name]
        lines.append(f"{name:<40}{v.vtype.__name__:<8}"
                     f"{str(v.default):<18}{v.doc}")
    return "\n".join(lines)


# -- engine / execution ------------------------------------------------------
_register("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
          "NaiveEngine blocks after every op (serial debugging, parity: "
          "src/engine/naive_engine.cc)")
_register("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", int, 15,
          "bulking hint kept for API parity; XLA fuses regardless")
_register("MXNET_BACKWARD_DO_MIRROR", bool, False,
          "rematerialize forward activations during backward (memory for "
          "FLOPs; parity: gradient.cc mirror fn) — TrainStep jax.checkpoint")
_register("MXNET_SUBGRAPH_BACKEND", str, "",
          "graph-rewrite backend applied at bind time (parity: "
          "src/operator/subgraph/; e.g. 'dense_act'); empty disables")
_register("MXNET_NATIVE_IO", bool, True,
          "load the native data-plane library (src/io_native.cc); "
          "0 forces the pure-Python paths")
# -- kvstore / distributed ---------------------------------------------------
_register("MXNET_KVSTORE_AUTH_TOKEN", str, "",
          "HMAC key for dist kvstore frames (REQUIRED for non-loopback "
          "binds)")
_register("MXNET_KVSTORE_ALLOW_INSECURE", bool, False,
          "allow non-loopback kvstore bind without auth token (trusted "
          "networks only)")
_register("MXNET_KVSTORE_MAX_FRAME", int, 1 << 30,
          "maximum kvstore wire frame size in bytes")
_register("MXNET_KVSTORE_HEARTBEAT_INTERVAL", float, 5.0,
          "worker heartbeat period in seconds (0 disables); feeds "
          "get_num_dead_node")
_register("MXNET_KVSTORE_RETRIES", int, 3,
          "bounded retry budget for kvstore client RPCs on transport "
          "failures (reconnect + resend with exponential backoff and "
          "jitter); 0 fails on the first error.  Sync pushes retried "
          "after a lost REPLY are at-least-once — see docs/chaos.md")
_register("MXNET_KVSTORE_RETRY_BACKOFF_S", float, 0.05,
          "base backoff for kvstore client RPC retries; attempt i "
          "sleeps base * 2^i * (1 + jitter)")
_register("MXNET_KVSTORE_PEER_TIMEOUT_S", float, 30.0,
          "kvstore server dead-peer threshold: a rank that has "
          "heartbeated at least once and then goes silent this long is "
          "marked lost, and every in-flight sync pull/barrier that "
          "needs it fails with typed PeerLostError instead of timing "
          "out against a corpse (docs/parallel.md)")
_register("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
          "arrays larger than this many elements are pushed/pulled in "
          "row chunks (parity: kvstore_dist.h:243 key sharding)")
_register("DMLC_ROLE", str, "worker",
          "process role: worker/server (ps-lite contract)")
_register("DMLC_RANK", int, 0, "worker rank")
_register("DMLC_WORKER_ID", int, 0, "alias of DMLC_RANK")
_register("DMLC_NUM_WORKER", int, 1, "number of workers")
_register("DMLC_NUM_SERVER", int, 1, "number of servers (always 1 here)")
_register("DMLC_PS_ROOT_URI", str, "",
          "kvstore server host; empty = single-process degradation")
_register("DMLC_PS_ROOT_PORT", int, 9091, "kvstore server port")
_register("DMLC_PS_BIND_ADDR", str, "127.0.0.1",
          "kvstore server bind address (loopback by default — frames "
          "are pickle)")
# -- multihost runtime -------------------------------------------------------
_register("MXNET_COORDINATOR_URI", str, "",
          "jax.distributed coordinator host for parallel.multihost; "
          "takes precedence over DMLC_PS_ROOT_URI (which is never "
          "borrowed when DMLC_ROLE marks a PS deployment — the PS "
          "socket is not a jax.distributed endpoint)")
_register("MXNET_COORDINATOR_PORT", int, 8476,
          "port for MXNET_COORDINATOR_URI")
# -- data pipeline -----------------------------------------------------------
_register("MXNET_MP_START_METHOD", str, "forkserver",
          "multiprocessing start method for DataLoader worker pools; "
          "'fork' restores zero-pickle datasets but deadlocks once "
          "jax's XLA thread pools are live (gluon/data/dataloader.py)")
# -- fused train step --------------------------------------------------------
_register("MXNET_FUSED_STEP", bool, True,
          "Module train steps: trace forward+backward+optimizer update "
          "into ONE donated jax.jit computation (1 dispatch/step) when "
          "the optimizer exposes fused_update; 0 restores the per-param "
          "dispatch loop (fused_step.py)")
_register("MXNET_METRIC_SYNC_INTERVAL", int, 1,
          "Module.update_metric: flush buffered (label, output) pairs "
          "into the metric every N batches instead of forcing a "
          "device->host sync per batch; 1 = sync every batch (exact "
          "legacy behaviour). N>1 requires the data iterator to hand "
          "out fresh label arrays per batch (NDArrayIter does; staged "
          "fit batches always do)")
_register("MXNET_SCAN_STEPS", int, 1,
          "Module.fit: run this many fused train steps as ONE donated "
          "jax.lax.scan dispatch (a K-step window); host control "
          "(metrics, callbacks, watchdog beats) happens at window "
          "boundaries only. 1 = one dispatch per step (PR-4 behaviour); "
          "requires the fused-step eligibility (fused_step.py)")
_register("MXNET_SCAN_ACCUM", int, 1,
          "in-scan gradient accumulation: each scanned train step "
          "consumes this many micro-batches and applies ONE optimizer "
          "update over their summed gradients (effective batch = "
          "M x bound batch; Module-computed rescale_grad accounts for "
          "it). 1 disables; >1 requires MXNET_SCAN_STEPS mode")
_register("MXNET_MESH_FUSED_STEP", bool, True,
          "Module.fit with an in-process kvstore: trace forward + VJP + "
          "bucketed gradient collectives + optimizer update into ONE "
          "donated shard_map computation per K-step window over the "
          "DeviceMesh (parallel/fused.py), retiring the per-param "
          "push/pull loop from the hot path; 0 keeps the sequential "
          "kvstore loop (docs/parallel.md eligibility matrix)")
_register("MXNET_COLLECTIVE_BUCKET_MB", float, 4.0,
          "mesh fused step: gradients are flattened into buckets of at "
          "most this many MB and reduced with ONE psum/reduce-scatter "
          "per bucket, so XLA can overlap communication with remaining "
          "backward compute instead of issuing one tiny collective per "
          "parameter (docs/parallel.md bucket sizing)")
_register("MXNET_COLLECTIVE_MODE", str, "bucketed",
          "mesh fused step collective formulation: 'bucketed' (default) "
          "or 'off' (skip gradient collectives entirely — WRONG results, "
          "debug only)")
_register("MXNET_COLLECTIVE_COMPRESSION", str, "none",
          "mesh fused step per-bucket gradient codec: 'none' (exact "
          "dense psum), 'fp16' (halved wire bytes, ~1e-3 relative "
          "tolerance), or '2bit' (error-feedback quantization to "
          "+-threshold/0, packed 4 codes/byte and exchanged with one "
          "all_gather per bucket — 32/R x fewer wire bytes per rank on "
          "an R-way mesh; residuals ride the donated scan carry and "
          "reset at elastic restore).  Changes training numerics: "
          "opt-in, replicated layout only (docs/parallel.md)")
_register("MXNET_COLLECTIVE_COMPRESSION_THRESHOLD", float, 0.5,
          "2bit collective codec emission threshold (parity: reference "
          "gradient_compression kTwoBit default)")
_register("MXNET_MULTIHOST_COORD", str, "",
          "host:port of the jax.distributed coordinator for a "
          "multi-process mesh (empty = single process unless a TPU pod "
          "autodetects); every process of one job must agree")
_register("MXNET_MULTIHOST_NUM_PROCS", int, 1,
          "process count of the multi-host job (1 = single process)")
_register("MXNET_MULTIHOST_PROC_ID", int, 0,
          "this process's rank in the multi-host job")
_register("MXNET_MULTIHOST_CONTROL_URI", str, "",
          "host of the multi-host control-plane kvstore server "
          "(heartbeats, peer states, window rendezvous); empty "
          "disables the liveness layer")
_register("MXNET_MULTIHOST_CONTROL_PORT", int, 0,
          "port of the multi-host control-plane server")
_register("MXNET_MULTIHOST_HEARTBEAT_S", float, 1.0,
          "multi-host runtime heartbeat period to the control server "
          "(0 disables; peers read as lost after "
          "MXNET_MULTIHOST_PEER_TIMEOUT_S of silence)")
_register("MXNET_MULTIHOST_PEER_TIMEOUT_S", float, 10.0,
          "multi-host dead-peer threshold: a rank silent this long is "
          "lost — survivors get typed PeerLostError at the next window "
          "rendezvous/in-flight wait instead of hanging")
_register("MXNET_MULTIHOST_BARRIER_TIMEOUT_S", float, 60.0,
          "deadline for the per-window multi-host rendezvous and for "
          "the survivors' exit barrier; every coordination wait in the "
          "elastic runtime is bounded by a deadline derived from this")
_register("MXNET_MULTIHOST_MAX_RESTARTS", int, 3,
          "elastic launcher: maximum world restarts (preemption "
          "recoveries/resizes) before the job fails typed")
# -- streaming data plane (io_pipeline.py) -----------------------------------
_register("MXNET_DATA_WORKERS", int, 0,
          "streaming data plane: reader worker threads per "
          "DataPipeline (decode/augment off the train thread) and the "
          "switch for the fit loop's off-thread super-batch assembler; "
          "0 = serial in-thread reads (bitwise-identical batch "
          "sequence, no overlap)")
_register("MXNET_DATA_QUEUE_DEPTH", int, 4,
          "streaming data plane: bounded per-shard output queue depth "
          "(batches); with the in-flight shard window this caps host "
          "RSS — total buffered batches <= depth x max in-flight "
          "shards")
_register("MXNET_DATA_SHARD_SEED", int, 0,
          "streaming data plane: seed for the per-epoch shard order "
          "permutation; the SAME order is produced for any worker "
          "count (the load-bearing determinism contract, docs/data.md)")
# -- fused kernels -----------------------------------------------------------
_register("MXNET_KERNELS", str, "off",
          "kernels subsystem mode: off (legacy per-op gates only), "
          "reference (pure-XLA references, bitwise = off for op paths), "
          "tuned (gated Pallas kernels at the best known config; "
          "reference fallback on gate failure)")
_register("MXNET_KERNELS_OVERRIDES", str, "",
          "per-kernel mode overrides, e.g. "
          "'layernorm=tuned,attention=off'; unlisted kernels follow "
          "MXNET_KERNELS")
_register("MXNET_KERNELS_TUNE_REPEATS", int, 3,
          "autotuner: timed repeats per candidate config (best-of)")
_register("MXNET_KERNELS_TUNE_BUDGET", int, 8,
          "autotuner: max configs measured per (kernel, shape); 0 = "
          "unlimited")
_register("MXNET_FUSED_LAYERNORM", str, "auto",
          "fused Pallas LayerNorm: 1 forces on, 0 forces plain XLA, "
          "auto takes the kernel where the shape rule holds (trailing "
          "width fits the VMEM tile budget); a Mosaic refusal inside "
          "the rule is an error")
# -- test harness ------------------------------------------------------------
_register("MXNET_TEST_EXAMPLES", bool, False,
          "run the full examples/ suite in tests/test_examples.py "
          "(ci/run.sh sets it; tier-1 runs only the fastest example)")
# -- profiler ---------------------------------------------------------------
_register("MXNET_PROFILER_XPLANE_DIR", str, "",
          "directory for jax.profiler xplane traces (TensorBoard/"
          "perfetto); empty disables the device trace")
_register("MXNET_FUSED_SOFTMAX_CE", str, "auto",
          "fused Pallas softmax-cross-entropy kernel: 1 forces on, 0 "
          "forces plain XLA, auto takes the kernel where the shape rule "
          "holds (class count fits the VMEM tile budget)")
_register("MXNET_PROFILER_AUTOSTART", bool, False,
          "start the profiler at import (parity: reference "
          "env_var.md MXNET_PROFILER_AUTOSTART)")
_register("MXNET_PROFILER_MODE", str, "",
          "with AUTOSTART: 'all'/'1' also enables profile_all + "
          "profile_api (parity: reference MXNET_PROFILER_MODE)")
# -- chaos / fault injection -------------------------------------------------
_register("MXNET_CHAOS", str, "",
          "failpoint arm spec: ';'-separated "
          "site=action[(value)][:hits=N][:count=M][:prob=P] arms "
          "(actions: raise/delay/wedge/corrupt/kill; docs/chaos.md "
          "grammar + site catalog); empty disables every failpoint "
          "with zero behavior change")
_register("MXNET_CHAOS_SEED", int, 0,
          "seed for the per-site chaos random streams (prob triggers, "
          "corrupt-byte positions) — same spec + same seed replays the "
          "same fault schedule")
_register("MXNET_CHAOS_WEDGE_TIMEOUT_S", float, 60.0,
          "a wedge failpoint left unreleased raises ChaosInjectedError "
          "after this long instead of hanging forever (the no-scenario-"
          "ends-in-a-hang contract)")
# -- soak harness ------------------------------------------------------------
_register("MXNET_SOAK_SECONDS", float, 90.0,
          "chaos.soak harness: wall-clock length of the train + "
          "checkpoint + serving-hot-reload + Poisson-traffic loop "
          "(python -m mxnet_tpu.chaos.soak; --seconds overrides)")
_register("MXNET_SOAK_QPS", float, 40.0,
          "chaos.soak harness: Poisson arrival rate of the serving "
          "traffic generator (req/s)")
_register("MXNET_SOAK_CHAOS", bool, True,
          "chaos.soak harness: arm the seeded benign fault mix "
          "(transient router-dispatch raises the spill path heals, "
          "io-stage and checkpoint-gc delays) while the loop runs; "
          "0 soaks the stack fault-free")
_register("MXNET_SOAK_RSS_SLOPE_MAX", float, 4e6,
          "chaos.soak harness: maximum acceptable RSS leak slope "
          "(bytes/s, least-squares over the sampler window) at soak "
          "exit — above it the soak fails")
# -- telemetry ---------------------------------------------------------------
_register("MXNET_TELEMETRY", bool, False,
          "enable the telemetry span tracer + per-train-step lane "
          "breakdown (telemetry.span / callback.StepTimeline); the "
          "metrics registry, collectors and exporter work regardless — "
          "this knob only arms the timed instrumentation "
          "(docs/observability.md)")
_register("MXNET_TELEMETRY_PORT", int, 0,
          "serve telemetry.prometheus_dump() on "
          "http://127.0.0.1:<port>/metrics (plus /snapshot.json and "
          "/healthz) from a daemon thread; 0 disables the endpoint")
_register("MXNET_WATCHDOG_S", float, 0.0,
          "hang watchdog: when an armed section (fit loop, serving "
          "batcher) makes no progress for this many seconds, dump "
          "all-thread stacks + the telemetry snapshot to stderr and a "
          "mxnet-watchdog-<pid>-<n>.txt file; 0 disables "
          "(docs/observability.md runbook)")
_register("MXNET_WATCHDOG_DIR", str, "",
          "directory for hang-watchdog dump files (empty = cwd)")
_register("MXNET_WATCHDOG_KEEP", int, 8,
          "retention for watchdog stall dumps AND flight-recorder dumps "
          "in their target directory: newest N kept, oldest pruned at "
          "each new dump; 0 keeps everything")
_register("MXNET_TRACE", bool, False,
          "end-to-end tracing: thread a trace context (trace_id + stage "
          "spans) through every serving request (submit -> queue_wait -> "
          "stage -> dispatch -> resolve, surviving spill hops) and every "
          "scanned training window (collect -> stage -> rendezvous -> "
          "dispatch -> boundary_flush); stage durations fan out to the "
          "span sinks and finished traces feed the sampled exemplar "
          "store (docs/observability.md trace taxonomy); the disabled "
          "path is one global check, < 1 us")
_register("MXNET_TRACE_SAMPLE", str, "head=8,tail=64",
          "trace exemplar sampling policy per trace kind: keep the "
          "first `head` finished traces (startup behaviour) plus the "
          "`tail` slowest by end-to-end latency (the p99 outliers you "
          "actually decompose); exemplars surface in "
          "telemetry.snapshot()['trace'] and /snapshot.json")
_register("MXNET_FLIGHT", bool, True,
          "crash flight recorder: a lock-cheap bounded ring of "
          "structured events (sheds, spills, chaos injections, restarts, "
          "rendezvous outcomes, checkpoint commits) recorded at every "
          "subsystem's decision points and dumped atomically on "
          "watchdog fire / typed-fatal error / SIGTERM / chaos kill; "
          "0 reduces every record to one global check (< 1 us)")
_register("MXNET_FLIGHT_RING", int, 1024,
          "flight recorder ring capacity in events (oldest evicted)")
_register("MXNET_FLIGHT_DIR", str, "",
          "directory for flight-recorder dump files "
          "(empty = MXNET_WATCHDOG_DIR, then cwd); the elastic launcher "
          "points each worker generation at its postmortem harvest dir")
_register("MXNET_ALERTS", float, 0.0,
          "in-process SLO alert engine: evaluate the rule pack "
          "(telemetry/alerts.py; default pack codifies the doc alarm "
          "table — watchdog stall, corrupt ckpt, spill storm, shed "
          "burn-rate, retrace ratchet, RSS slope, snapshot staleness) "
          "every this many seconds on a daemon thread; firing "
          "page-severity rules flip /healthz to 503 and every "
          "transition lands in the flight ring + /alerts.json; "
          "0 disables (the disabled tick is one global check, < 1 us)")
_register("MXNET_ALERT_RULES", str, "",
          "extra alert rules appended to the default pack: "
          "';'-separated name=family<op>value[:for=S][:cooldown=S]"
          "[:severity=warn|page][:reduce=sum|max|min]"
          "[:kind=threshold|rate|absence][:window=S] arms "
          "(docs/observability.md rule grammar); a name collision "
          "replaces the default rule")
_register("MXNET_RESOURCE_SAMPLE_S", float, 0.0,
          "host resource sampler: sample RSS / open fds / thread count "
          "/ checkpoint-dir disk usage into a sliding window every this "
          "many seconds (feeds the mxnet_resource_* families and the "
          "least-squares RSS leak-slope estimator the rss_slope alert "
          "rule and the soak harness gate on); 0 disables the thread "
          "(the resources collector still takes one on-demand sample "
          "per scrape)")
_register("MXNET_NUMERICS", str, "off",
          "numerics observatory mode for train windows: 'off' (default; "
          "the boundary check is one global read, < 1 us), 'warn' (log + "
          "flight event + forensic dump on a non-finite or rule-breaching "
          "window, training continues), 'skip' (additionally gate each "
          "poisoned step's update on device — the dynamic loss-scaler "
          "idiom, no extra sync — and continue bit-identically to a "
          "manual skip), 'halt' (raise typed NonFiniteError at the "
          "boundary).  Stats (grad/param norms, update ratio, loss "
          "proxy, per-bucket non-finite counts) are computed INSIDE the "
          "donated jit/shard_map window: dispatches/step unchanged, "
          "weights bitwise-identical to off (docs/observability.md)")
_register("MXNET_NUMERICS_GRAD_NORM_MAX", float, 0.0,
          "numerics host-side rule: a window whose global gradient L2 "
          "norm exceeds this is treated like a non-finite window "
          "(warn/skip-record/halt per MXNET_NUMERICS); 0 disables the "
          "rule (the grad_norm_explosion alert rate-rule still watches "
          "the exported gauge)")
_register("MXNET_NUMERICS_HISTORY", int, 512,
          "numerics observatory: per-step stat entries kept in the "
          "in-process history ring (forensic dumps embed it; "
          "numerics.monitor_summary() reads it)")
_register("MXNET_NUMERICS_DUMP_DIR", str, "",
          "directory for mxnet-numerics-<pid>-<n>.json forensic dumps "
          "(empty = MXNET_FLIGHT_DIR, then MXNET_WATCHDOG_DIR, then "
          "cwd); retention shared with MXNET_WATCHDOG_KEEP")
_register("MXNET_NUMERICS_SERVING", bool, True,
          "serving output-health guard: screen each executed batch's "
          "float outputs and fail requests whose rows contain NaN/Inf "
          "with typed NonFiniteError (bumping "
          "mxnet_numerics_serving_nonfinite_total) instead of serving "
          "them; healthy cohort members still resolve.  0 disables the "
          "screen")
_register("MXNET_FLEET_INTERVAL_S", float, 0.0,
          "cross-rank telemetry aggregation: every rank pushes its "
          "registry snapshot to the control-plane kvstore server this "
          "often so the leader can merge a fleet snapshot "
          "(/fleet.json, rank-labelled Prometheus families; dead ranks "
          "keep their last snapshot tagged state=lost); 0 disables the "
          "reporter (the elastic launcher arms it for its workers)")
_register("MXNET_FLEET_DELTA", bool, True,
          "delta-encode fleet telemetry pushes against the last "
          "server-acked snapshot (unchanged families cost ~0 wire "
          "bytes and ~0 leader merge work; a forgotten baseline "
          "resyncs with one full push); 0 forces every push to carry "
          "the full family snapshot")
_register("MXNET_FLEET_HISTORY", int, 8,
          "elastic world generations of per-rank telemetry the fleet "
          "leader retains and serves in /fleet.json?detail=rank; older "
          "generations are pruned (an absence-safe 'history' "
          "truncation marker appears in the detail view once pruning "
          "happened) so a long-lived leader's scrape size plateaus")
_register("MXNET_FLEET_SIM_RANKS", int, 1000,
          "default synthetic rank count for the in-process fleet "
          "simulator (python -m mxnet_tpu.telemetry.fleet_sim); the "
          "--ranks flag overrides")
_register("MXNET_FLEET_SIM_CYCLES", int, 50,
          "default push cycles per fleet-simulator run (virtualized "
          "time: one cycle = one push interval); the --cycles flag "
          "overrides")
_register("MXNET_FLEET_SIM_SEED", int, 0,
          "base seed for the fleet simulator's per-rank metric-family "
          "generators and anomaly schedule (same seed, same fleet); "
          "the --seed flag overrides")
# -- compilation lifecycle ---------------------------------------------------
_register("MXNET_COMPILE_CACHE", bool, True,
          "persistent XLA compilation artifacts: serving executor-cache "
          "misses, ladder warmup and fused/scanned train-step builds "
          "activate jax's persistent compilation cache so a restarted "
          "process deserializes executables instead of recompiling "
          "(docs/compile.md); 0 keeps every compile in-process only")
_register("MXNET_COMPILE_CACHE_DIR", str, "",
          "root directory for persistent compilation artifacts when "
          "JAX_COMPILATION_CACHE_DIR is NOT set (that directory is used "
          "exactly as given and outranks this knob); artifacts live "
          "under a per-(jax, jaxlib, mxnet_tpu) version subdirectory so "
          "stack upgrades invalidate cleanly; empty = "
          "<checkout>/.jax_cache, unversioned")
_register("MXNET_COMPILE_CACHE_MIN_COMPILE_S", float, 1.0,
          "only persist programs whose backend compile took at least "
          "this long (tiny programs recompile cheaper than they "
          "hash+stat); tests and smokes set 0 so toy models persist")
_register("MXNET_COMPILE_CACHE_SALT", str, "",
          "extra salt mixed into the artifact version key (forces a "
          "fresh cache namespace without touching the directory; tests "
          "use it to prove versioned invalidation)")
_register("MXNET_COMPILE_WARMUP", bool, True,
          "AOT-compile a model version's full bucket ladder at publish "
          "time via the repository warm hooks — synchronously BEFORE "
          "the served-version pointer flips on checkpoint hot-reload, "
          "on a background thread after a hot-reload load(); 0 keeps "
          "first-request-pays-compile")
_register("MXNET_COMPILE_LADDER_MAX", int, 8,
          "BucketPlanner budget: max compiled bucket boundaries per "
          "model ladder (each boundary is one compiled program)")
_register("MXNET_COMPILE_PLAN_MIN_SAMPLES", int, 256,
          "formed batches that must be observed before the planner "
          "replaces the power-of-two ladder with a measured one")
# -- serving ----------------------------------------------------------------
_register("MXNET_SERVING_MAX_BATCH", int, 32,
          "DynamicBatcher flush size: a batch runs as soon as this many "
          "requests coalesce (upper bound of the bucketed batch dim)")
_register("MXNET_SERVING_MAX_LATENCY_MS", float, 5.0,
          "DynamicBatcher deadline: a partial batch flushes once the "
          "oldest queued request has waited this long (throughput vs "
          "p99 knob; docs/serving.md)")
_register("MXNET_SERVING_QUEUE_DEPTH", int, 256,
          "bounded serving queue capacity (requests)")
_register("MXNET_SERVING_SHED_WATERMARK", int, 0,
          "queue depth at which submits fail fast with "
          "ServingOverloadError; 0 = at queue capacity")
_register("MXNET_SERVING_NUM_WORKERS", int, 1,
          "batch-execution worker threads per batcher replica (each "
          "worker is a stage/dispatch thread pair: micro-batch N+1 "
          "coalesces and stacks while N executes)")
_register("MXNET_SERVING_REPLICAS", int, 1,
          "DynamicBatcher replicas per model endpoint, behind the "
          "load-aware ReplicaPool router (occupancy x drain-time EWMA "
          "routing, graceful spill, drain-on-removal); 1 = single "
          "batcher (docs/serving.md replica pools)")
_register("MXNET_SERVING_SLO_P99_MS", float, 0.0,
          "SLO admission control: shed (ServingOverloadError) once the "
          "router's PREDICTED p99 — pool occupancy / service-rate EWMA "
          "— exceeds this many ms, so the shed point self-tunes to the "
          "model's measured speed; 0 disables (watermark shedding "
          "still applies per replica)")
_register("MXNET_SERVING_SLO_EWMA_ALPHA", float, 0.2,
          "smoothing factor for the admission controller's service-"
          "rate EWMA (higher = faster adaptation, noisier predictions)")
_register("MXNET_SERVING_TIMEOUT_MS", float, 0.0,
          "default per-request timeout (queued past this -> "
          "RequestTimeoutError); 0 disables")
_register("MXNET_SERVING_WORKER_RESTARTS", int, 8,
          "DynamicBatcher: how many times a crashed batch worker thread "
          "is restarted in place (its in-flight batch fails with a "
          "retryable ServingWorkerError) before the batcher gives up "
          "and fails fast instead of hanging; 0 = never restart")
_register("MXNET_SERVING_EXECUTOR_CACHE", int, 32,
          "LRU capacity of the compiled-executor cache, in (model, "
          "version, bucketed-shape) entries")
_register("MXNET_GENERATION_SLOTS", int, 8,
          "KV-cache slots per generation engine = concurrent sessions "
          "one fixed-shape decode micro-batch serves; a full pool "
          "sheds new sessions typed (docs/serving.md generation)")
_register("MXNET_GENERATION_MAX_LEN", int, 512,
          "generation KV arena length cap (prompt + generated tokens "
          "per session; the decode step's fixed sequence dimension)")
_register("MXNET_GENERATION_PAGE_TOKENS", int, 64,
          "KV-cache page granularity in tokens: session reservations "
          "charge the resource ledger in whole pages, and the prefix "
          "cache stores/hits page-aligned prompt prefixes")
_register("MXNET_GENERATION_KV_BUDGET_MB", int, 64,
          "HBM budget for one engine's committed KV pages; admission "
          "sheds typed (ServingOverloadError) rather than commit past "
          "it — the generation analogue of the queue watermark")
_register("MXNET_GENERATION_PREFIX_CACHE", int, 32,
          "prefix-cache capacity in entries (page-aligned prompt-"
          "prefix activations, LRU, content-hash keyed per model "
          "version); 0 disables prefix reuse")
_register("MXNET_GENERATION_LOOP_RESTARTS", int, 2,
          "how many times a crashed generation loop restarts (active "
          "sessions fail typed-retryable and can resume on a sibling) "
          "before the engine fails fast; 0 = never restart")
_register("MXNET_MODULE_PAD_PARTIAL_PREDICT", bool, True,
          "Module.forward(is_train=False): pad a partial final batch up "
          "to the bound batch and slice outputs, instead of rebinding a "
          "new executor shape (serving-style bucketing on the module "
          "predict path)")
# -- checkpoint --------------------------------------------------------------
_register("MXNET_CKPT_ASYNC", bool, True,
          "CheckpointManager: serialize/fsync on a background writer so "
          "save() blocks the train loop only for the device->host "
          "snapshot; 0 makes every save synchronous")
_register("MXNET_CKPT_KEEP_LAST", int, 5,
          "retention: committed checkpoint steps kept (older steps are "
          "garbage-collected after each commit; 0 keeps everything)")
_register("MXNET_CKPT_KEEP_EVERY", int, 0,
          "retention: additionally keep every Nth step forever "
          "(step %% N == 0); 0 disables")
_register("MXNET_CKPT_VERIFY_ON_LOAD", bool, True,
          "verify per-file sha256 checksums on restore; a mismatch "
          "raises CheckpointCorruptError (auto-latest restores fall "
          "back to the previous committed step)")
_register("MXNET_CKPT_WRITE_DELAY_MS", float, 0.0,
          "test/debug: sleep this long between tensor writes and before "
          "the manifest, widening the step-NNNNNN.tmp window for "
          "crash-during-save tests (ci checkpoint smoke)")
_register("MXNET_CKPT_WATCH_INTERVAL_S", float, 1.0,
          "serving ModelRepository.watch poll period for newly "
          "committed checkpoint steps")
_register("MXNET_CKPT_COMMIT_TIMEOUT_S", float, 60.0,
          "multi-host commit: how long host 0 waits for every host's "
          "shard manifest before failing the save")
# -- driver ------------------------------------------------------------------
_register("MX_DRYRUN_TIMEOUT", float, 900.0,
          "subprocess timeout for __graft_entry__.dryrun_multichip")
