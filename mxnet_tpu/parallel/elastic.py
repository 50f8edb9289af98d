"""Elastic multi-host training: preemption-tolerant cross-process fit
with automatic survivor-mesh restore (ISSUE 11 tentpole).

PR 9 fused the distributed step inside one process; on a real pod the
dominant failure mode is a HOST vanishing mid-step.  This module makes
host loss a *handled event*:

* :class:`MultiHostFusedTrainStep` — the coordinated flavor of the mesh
  fused window: a **deadline-bounded rendezvous** before every window
  dispatch (no survivor ever enters a collective a dead peer can't
  join), a peer-watching bounded wait on the in-flight window, and
  progress reporting for recovery measurement.  Preemption/peer loss
  surface as typed :class:`PreemptionError` / :class:`PeerLostError`
  at window boundaries — never mid-trace, never a hang.
* :class:`ElasticSession` — the worker-side self-heal hook
  ``Module.fit`` calls on an elastic fault: boundary checkpoint
  (leader-elected among alive ranks, skip-if-committed so concurrent
  survivors converge on ONE step directory), then the typed error
  propagates to the worker main which exits with a restart/leave code.
* :class:`ElasticLauncher` — the supervisor: owns the control-plane
  kvstore server (heartbeats, dead-peer propagation, window barriers —
  it outlives any worker), spawns the world as N processes × fake/real
  devices, reaps fault generations with a deadline (stragglers are
  killed, never waited on forever), and respawns the SURVIVOR world
  from the latest boundary checkpoint — the PR 2/PR 9 elastic-restore
  resize mechanism, now automatic.  A re-joining host is the same
  mechanism pointed the other way: ``respawn="full"`` restores the
  checkpoint onto the bigger mesh at the next generation.

Continuing bit-identically to a planned resize is the contract the CI
smoke pins: SIGKILL of host 1-of-2 at window 3 must produce the exact
final weights of a run that *planned* to shrink dp/2 at that boundary.

``python -m mxnet_tpu.parallel.elastic`` is the CI smoke (2 subprocess
hosts × 4 fake CPU devices each, kill-and-recover + parity + dispatch
budget).
"""
from __future__ import annotations

import json
import logging
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from ..base import MXNetError, PeerLostError, PreemptionError
from .fused import MeshFusedTrainStep
from . import multihost as _mh

log = logging.getLogger("mxnet_tpu.elastic")

# worker exit codes the launcher's respawn policy reads
ELASTIC_RESTART = 77   # "I survived an elastic event: respawn me"
ELASTIC_LEAVE = 78     # "I was preempted / planned out: do not respawn"

_SESSION = None


# -- the coordinated mesh step ------------------------------------------------
class MultiHostFusedTrainStep(MeshFusedTrainStep):
    """MeshFusedTrainStep + the multi-host coordination contract.

    Window lifecycle: boundary probe (typed preemption/peer-loss) →
    deadline-bounded rendezvous of all alive ranks → donated shard_map
    dispatch → peer-watching bounded wait on the in-flight window →
    progress report.  Every wait proves a deadline: the rendezvous is
    server-side deadline-bounded with dead-peer propagation, and the
    result wait polls peer liveness instead of blocking blind.
    """

    def run_window(self, sbatch):
        from ..chaos.failpoints import failpoint as _failpoint
        from ..telemetry import trace as _trace
        rt = _mh.runtime()
        # the preemption/peer-loss injection point: kill here is the
        # host-vanishes-at-a-boundary scenario, raise is a typed probe
        # fault, wedge exercises the watchdog over a stalled boundary
        _failpoint("multihost/peer_loss")
        if rt is not None:
            # the window trace's rendezvous stage (the fit loop set the
            # ambient trace; NULL_TRACE when tracing is off)
            with _trace.current().stage("rendezvous"):
                rt.check()
                rt.window_rendezvous()
        outs = super().run_window(sbatch)
        if outs is not False and rt is not None:
            # global training progress (num_update resumes across an
            # elastic restore, unlike the per-process window counter)
            rt.report_progress(int(self._module._optimizer.num_update))
        return outs

    def _post_dispatch(self, tv, st, res, ys):
        rt = _mh.runtime()
        if rt is not None:
            rt.wait_ready(list(ys) + list(tv))


# -- worker-side session (the Module.fit self-heal hook) ---------------------
class ElasticSession:
    """Registers this process as an elastic worker: SIGTERM becomes a
    boundary-preemption flag, and an elastic fault inside ``fit`` runs
    the boundary checkpoint before the typed error reaches the worker
    main.  Use as a context manager around the training loop."""

    def __init__(self, manager):
        self.manager = manager
        self.fault = None
        self.saved_step = None

    def __enter__(self):
        global _SESSION
        _SESSION = self
        rt = _mh.runtime()
        if rt is not None:
            rt.install_sigterm()
        return self

    def __exit__(self, *exc):
        global _SESSION
        _SESSION = None
        return False

    # called by Module.fit's elastic except-clause via on_fit_fault
    def handle_fault(self, module, exc):
        from ..telemetry import flight as _flight
        self.fault = exc
        step = int(module._optimizer.num_update)
        rt = _mh.runtime()
        _flight.record("elastic", "fault", severity="error",
                       cause=type(exc).__name__, step=step,
                       rank=getattr(rt, "rank", None))
        if rt is not None and isinstance(exc, PeerLostError):
            # leader election among ALIVE ranks: exactly one survivor
            # writes the boundary step (they all hold the replicated
            # state, any one copy is the truth)
            try:
                states = rt.peer_states()
                alive = [r for r, info in states.items()
                         if info["state"] != "lost"]
            except Exception as e:  # noqa: BLE001 — control plane gone: save unconditionally, skip-if-committed dedupes
                log.warning("elastic: peer-state probe failed during "
                            "fault handling (%s: %s); saving "
                            "unconditionally", type(e).__name__, e)
                alive = [rt.rank]
            if rt.rank != min(alive or [rt.rank]):
                log.info("elastic: rank %d defers boundary save to the "
                         "leader", rt.rank)
                return
        self.saved_step = self._boundary_save(module, step)
        try:
            from .. import telemetry as _telemetry
            _telemetry.REGISTRY.counter(
                "mxnet_multihost_restores_total",
                "elastic events handled (boundary checkpoint + "
                "survivor-mesh restore requested)").inc(
                labels={"cause": type(exc).__name__})
        except Exception:  # graftlint: disable=swallowed-error -- telemetry must never mask the elastic event itself
            pass

    def _boundary_save(self, module, step):
        """Commit the boundary checkpoint unless a peer already did —
        concurrent survivors converge on one committed directory."""
        latest = self.manager.latest()
        if latest is not None and latest >= step:
            return latest
        try:
            self.manager.save_module(module, step, block=True)
            log.warning("elastic: boundary checkpoint committed at "
                        "step %d", step)
            from ..telemetry import flight as _flight
            _flight.record("elastic", "boundary_checkpoint", step=step)
            return step
        except Exception as e:  # noqa: BLE001 — a racing peer's commit is success
            latest = self.manager.latest()
            if latest is not None and latest >= step:
                return latest
            raise MXNetError(
                f"elastic boundary checkpoint at step {step} failed "
                f"({type(e).__name__}: {e}) and no peer committed "
                "it") from e


def on_fit_fault(module, exc):
    """Module.fit's elastic hook: route the fault to the registered
    session (no-op when this process is not an elastic worker)."""
    if _SESSION is not None:
        _SESSION.handle_fault(module, exc)


def exit_code_for(exc):
    """The worker exit code the launcher's respawn policy expects."""
    if isinstance(exc, PreemptionError):
        return ELASTIC_LEAVE
    return ELASTIC_RESTART


# -- the supervisor ----------------------------------------------------------
def _free_port():
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ElasticLauncher:
    """Spawn, watch, and elastically respawn a multi-host world.

    ``worker_argv(generation, world, rank)`` returns the child argv;
    the launcher supplies the MXNET_MULTIHOST_* env contract (fresh
    jax.distributed coordinator port per generation, the shared
    control-plane server address) plus ``XLA_FLAGS`` fake devices.

    Every wait carries a deadline: generation monitoring polls child
    exits against ``gen_timeout_s``; once a fault is detected the
    remaining children get ``exit_deadline_s`` to take their own typed
    exit (the survivors' barrier-with-a-deadline), then are killed.
    """

    def __init__(self, worker_argv, world, devices_per_proc=4,
                 max_restarts=None, respawn="survivors",
                 peer_timeout_s=2.0, env_extra=None, rank_env=None,
                 gen_timeout_s=300.0, exit_deadline_s=None,
                 postmortem_dir=None):
        from .. import config as _config
        from ..kvstore_server import KVServer
        if respawn not in ("survivors", "full"):
            raise MXNetError("respawn policy must be 'survivors' "
                             "(shrink to the alive set) or 'full' "
                             "(re-join replacements at full world)")
        self.worker_argv = worker_argv
        self.world = int(world)
        self.devices_per_proc = int(devices_per_proc)
        self.max_restarts = int(
            max_restarts if max_restarts is not None
            else _config.get("MXNET_MULTIHOST_MAX_RESTARTS"))
        self.respawn = respawn
        self.peer_timeout_s = float(peer_timeout_s)
        self.env_extra = dict(env_extra or {})
        self.rank_env = dict(rank_env or {})  # rank -> extra env
        self.gen_timeout_s = float(gen_timeout_s)
        self.exit_deadline_s = float(
            exit_deadline_s if exit_deadline_s is not None
            else _config.get("MXNET_MULTIHOST_BARRIER_TIMEOUT_S"))
        self.server = KVServer(port=0, num_workers=self.world,
                               peer_timeout_s=self.peer_timeout_s)
        self._server_thread = threading.Thread(
            target=self.server.run, daemon=True, name="elastic-control")
        self._server_thread.start()
        if not self.server.started.wait(timeout=30):
            raise MXNetError("elastic control server failed to start")
        self.history = []       # per-generation {world, exits, ...}
        self.recovery_s = []    # fault-detected -> progress-advanced
        # observability plane (ISSUE 12): the launcher IS the fleet
        # leader — its control server holds every rank's pushed registry
        # snapshot, so /fleet.json on this process serves the merged
        # cross-rank view (lost ranks tagged, per-generation history)
        from ..telemetry import fleet as _fleet
        _fleet.set_provider(
            lambda detail=None: _fleet.merge_server(self.server,
                                                    detail=detail))
        # postmortem harvest: each generation's workers dump their
        # flight rings (chaos-kill/typed-fatal/SIGTERM) + watchdog
        # files into gen<N>/; after a fault the launcher folds them +
        # the final fleet snapshot into ONE bundle file
        self.postmortem_dir = postmortem_dir
        self.postmortems = []   # bundle paths, in generation order
        if postmortem_dir:
            os.makedirs(postmortem_dir, exist_ok=True)

    # -- child management ---------------------------------------------------
    def _child_env(self, generation, world, rank, coord_port):
        env = dict(os.environ)
        env.pop("MXNET_CHAOS", None)  # each child gets its own spec
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        # observability (ISSUE 12), assigned BEFORE env_extra/rank_env so
        # callers can still override: fleet pushes must outpace the peer
        # timeout or every rank reads as stale, and each generation's
        # flight/watchdog dumps land in its postmortem harvest dir
        # (ambient values — e.g. the test conftest's hermetic dump dir —
        # must NOT divert them away from the harvest)
        env["MXNET_FLEET_INTERVAL_S"] = str(
            max(0.1, self.peer_timeout_s / 5.0))
        if self.postmortem_dir:
            gen_dir = os.path.join(self.postmortem_dir,
                                   f"gen{generation}")
            os.makedirs(gen_dir, exist_ok=True)
            env["MXNET_FLIGHT_DIR"] = gen_dir
            env["MXNET_WATCHDOG_DIR"] = gen_dir
        env.update(self.env_extra)
        env.update(self.rank_env.get((generation, rank),
                                     self.rank_env.get(rank, {})
                                     if generation == 0 else {}))
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count="
                         f"{self.devices_per_proc}",
            "MXNET_MULTIHOST_COORD": f"127.0.0.1:{coord_port}",
            "MXNET_MULTIHOST_NUM_PROCS": str(world),
            "MXNET_MULTIHOST_PROC_ID": str(rank),
            "MXNET_MULTIHOST_CONTROL_URI": "127.0.0.1",
            "MXNET_MULTIHOST_CONTROL_PORT": str(self.server.bound_port),
            "MXNET_MULTIHOST_PEER_TIMEOUT_S": str(self.peer_timeout_s),
            "MXNET_MULTIHOST_HEARTBEAT_S": str(
                max(0.05, self.peer_timeout_s / 5.0)),
        })
        return env

    def _spawn_generation(self, generation, world):
        from ..telemetry import flight as _flight
        coord_port = _free_port()
        self.server.reset_world(world, generation=generation)
        _flight.record("elastic", "generation_start",
                       generation=generation, world=world)
        procs = []
        for rank in range(world):
            argv = self.worker_argv(generation, world, rank)
            procs.append(subprocess.Popen(
                argv,
                env=self._child_env(generation, world, rank, coord_port)))
        return procs

    def _max_progress(self):
        with self.server._lock:
            return max(self.server._progress.values(), default=0)

    def _watch_generation(self, procs, generation):
        """Poll children until the generation resolves.  Returns the
        list of exit codes (signal deaths negative, killed stragglers
        forced to -9)."""
        deadline = time.monotonic() + self.gen_timeout_s
        fault_at = None
        while time.monotonic() < deadline:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                return codes, fault_at
            if fault_at is None and any(
                    c is not None and c != 0 for c in codes):
                fault_at = time.monotonic()
            if fault_at is not None and \
                    time.monotonic() - fault_at > self.exit_deadline_s:
                # survivors' exit barrier blew its deadline: kill the
                # stragglers rather than wait on them forever
                for p in procs:
                    if p.poll() is None:
                        log.error("elastic: killing straggler pid %d "
                                  "past the exit deadline", p.pid)
                        p.kill()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        pass
                return [p.poll() if p.poll() is not None else -9
                        for p in procs], fault_at
            time.sleep(0.05)
        # generation timeout: a hang the workers' own deadlines failed
        # to break (e.g. a wedged native collective setup).  Kill the
        # world and report it as a FAULT — the restart budget decides
        # whether to respawn from the checkpoint, so even this class of
        # failure recovers instead of propagating a hang upward.
        log.error("elastic: generation exceeded gen_timeout_s=%s "
                  "(exits so far %s); killing the world",
                  self.gen_timeout_s, [p.poll() for p in procs])
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        return [p.poll() if p.poll() is not None else -9
                for p in procs], time.monotonic()

    def _harvest_postmortem(self, generation, world, codes):
        """Fold a faulted generation's story into ONE bundle file:
        every rank's dumped flight ring, every watchdog stall dump, the
        launcher's own ring, and the final fleet snapshot (dead ranks
        tagged ``lost`` with their last registry state).  Best-effort:
        a failed harvest must never block the respawn."""
        if not self.postmortem_dir:
            return None
        from ..telemetry import fleet as _fleet
        from ..telemetry import flight as _flight
        gen_dir = os.path.join(self.postmortem_dir, f"gen{generation}")
        rings, watchdogs = {}, {}
        try:
            names = sorted(os.listdir(gen_dir)) \
                if os.path.isdir(gen_dir) else []
        except OSError:
            names = []
        for name in names:
            path = os.path.join(gen_dir, name)
            try:
                if name.startswith("mxnet-flight-") and \
                        name.endswith(".json"):
                    with open(path, encoding="utf-8") as f:
                        rings[name] = json.load(f)
                elif name.startswith("mxnet-watchdog-") and \
                        name.endswith(".txt"):
                    with open(path, encoding="utf-8") as f:
                        watchdogs[name] = f.read()[-20000:]
            except (OSError, ValueError) as e:
                log.warning("postmortem: unreadable %s (%s)", path, e)
        try:
            # postmortems always want the full per-rank view,
            # whatever the world size's auto scrape mode is
            fleet_snap = _fleet.merge_server(self.server, detail="rank")
        except Exception as e:  # noqa: BLE001 — a half-dead control plane must not block the bundle
            fleet_snap = {"error": f"{type(e).__name__}: {e}"}
        anomaly = _flight.first_anomaly(rings.values())
        bundle = {
            "generation": generation,
            "world": world,
            "exits": codes,
            "time": time.time(),
            "first_anomaly": anomaly,
            "rings": rings,
            "launcher_ring": _flight.events(),
            "watchdog_dumps": watchdogs,
            "fleet": fleet_snap,
        }
        path = os.path.join(self.postmortem_dir,
                            f"postmortem-gen{generation}.json")
        try:
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(bundle, f, indent=1, sort_keys=True,
                          default=str)
            os.replace(tmp, path)
        except OSError as e:
            log.error("postmortem: bundle write failed: %s", e)
            return None
        self.postmortems.append(path)
        log.warning("elastic: postmortem bundle for generation %d -> %s"
                    " (%d ring(s), first anomaly: %s)", generation,
                    path, len(rings),
                    (anomaly or {}).get("event", "none"))
        return path

    def _next_world(self, codes):
        survivors = sum(1 for c in codes if c == ELASTIC_RESTART)
        if self.respawn == "full":
            return self.world
        if survivors == 0:
            # everyone died hard (e.g. coordinator loss): full restart
            # from the checkpoint at the previous world size
            return len(codes)
        return survivors

    def run(self):
        """Drive generations until one completes cleanly (all exit 0)
        or the restart budget is exhausted.  Returns a summary dict."""
        from .. import telemetry as _telemetry
        recovery_hist = _telemetry.REGISTRY.histogram(
            "mxnet_multihost_recovery_seconds",
            "elastic recovery wall: fault detected -> respawned world "
            "advanced training progress",
            buckets=tuple(0.5 * 2 ** i for i in range(12)))
        restores = _telemetry.REGISTRY.counter(
            "mxnet_multihost_restores_total",
            "elastic events handled (boundary checkpoint + "
            "survivor-mesh restore requested)")
        world = self.world
        restarts = 0
        generation = 0
        pending_recovery = None  # (t0, progress mark before the fault)
        while True:
            log.warning("elastic: generation %d, world=%d", generation,
                        world)
            procs = self._spawn_generation(generation, world)
            if pending_recovery is not None:
                # recovery clock: fault (or SIGTERM delivery) ->
                # respawned world advances training progress past the
                # pre-fault mark; bounded by the generation timeout
                t0, mark = pending_recovery
                pending_recovery = None
                rec_deadline = time.monotonic() + self.gen_timeout_s
                while time.monotonic() < rec_deadline:
                    if self._max_progress() > mark:
                        recovered = time.monotonic() - t0
                        self.recovery_s.append(recovered)
                        recovery_hist.observe(recovered)
                        log.warning("elastic: recovered in %.1fs "
                                    "(training progress advanced)",
                                    recovered)
                        break
                    if all(p.poll() is not None for p in procs):
                        break
                    time.sleep(0.05)
            codes, fault_at = self._watch_generation(procs, generation)
            self.history.append({"generation": generation,
                                 "world": world, "exits": codes})
            if all(c == 0 for c in codes) or (
                    any(c == 0 for c in codes)
                    and all(c in (0, ELASTIC_LEAVE) for c in codes)):
                # clean finish (a leaver alongside finishers is a
                # completed planned shrink)
                return {"ok": True, "restarts": restarts,
                        "history": self.history,
                        "recovery_s": self.recovery_s,
                        "postmortems": self.postmortems}
            from ..telemetry import flight as _flight
            _flight.record("elastic", "generation_fault", severity="warn",
                           generation=generation, world=world,
                           exits=codes)
            self._harvest_postmortem(generation, world, codes)
            restarts += 1
            if restarts > self.max_restarts:
                raise MXNetError(
                    f"elastic: restart budget exhausted after "
                    f"{restarts - 1} recoveries; history "
                    f"{self.history}")
            restores.inc(labels={"role": "launcher"})
            mark = self._max_progress()
            t0 = fault_at if fault_at is not None else time.monotonic()
            pending_recovery = (t0, mark)
            world = self._next_world(codes)
            generation += 1
            log.warning(
                "elastic: exits %s — respawning world=%d from the "
                "latest boundary checkpoint",
                self.history[-1]["exits"], world)

    def close(self):
        self.server._stop.set()


# -- worker main + smoke -----------------------------------------------------
# The worker trains the same seeded MLP as the chaos mesh scenarios:
# deterministic data, boundary checkpoints every window, resumable from
# the latest committed step — the elastic continuation is bit-comparable
# to a planned resize by construction.
_N_FEAT = 20


def _worker_build():
    import mxnet_tpu as mx
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _worker_init_params(seed=5):
    import mxnet_tpu as mx
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, _N_FEAT) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}


def _worker_dataset(n_batches, batch):
    rng = np.random.RandomState(3)
    x = rng.randn(n_batches * batch, _N_FEAT).astype(np.float32)
    y = rng.randint(0, 10, n_batches * batch).astype(np.float32)
    return x, y


def _worker_main(argv):
    """argv: ckdir out_json n_batches batch [leave_at_step]"""
    ckdir, out_json = argv[0], argv[1]
    n_batches, batch = int(argv[2]), int(argv[3])
    leave_at = int(argv[4]) if len(argv) > 4 else 0

    import mxnet_tpu as mx
    import mxnet_tpu.chaos  # noqa: F401 — arms MXNET_CHAOS from env
    from mxnet_tpu import io as mxio
    from mxnet_tpu import profiler as _prof
    from mxnet_tpu import telemetry as _telemetry
    from mxnet_tpu.checkpoint import CheckpointManager, latest_step
    from mxnet_tpu.parallel.mesh import DeviceMesh

    _mh.init_multihost()
    rt = _mh.init_runtime()
    K = int(os.environ.get("MXNET_SCAN_STEPS", "2"))
    mgr = CheckpointManager(ckdir, async_save=False, keep_last=0)
    resume = latest_step(ckdir) or 0
    if rt is not None:
        rt.progress_base = resume

    x, y = _worker_dataset(n_batches, batch)
    x, y = x[resume * batch:], y[resume * batch:]
    mx.random.seed(0)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y),
                          batch_size=batch, label_name="softmax_label")
    if resume:
        mod, _ckpt = mgr.restore_module(resume)
    else:
        mod = mx.mod.Module(_worker_build(), context=mx.cpu())
    saved = set()

    def boundary_save(param):
        m = param.locals["self"]
        step = m._optimizer.num_update
        if rt is not None and leave_at and step >= leave_at:
            rt.request_preemption()
        if step % K == 0 and step not in saved:
            saved.add(step)
            mgr.save_module(m, step, block=True)
            if rt is not None:
                # progress also flows from here so a single-process
                # survivor world (no rendezvous path) still feeds the
                # launcher's recovery clock
                rt.report_progress(step)

    import jax
    mesh = DeviceMesh({"dp": len(jax.devices())}, jax.devices())
    kwargs = {} if resume else {
        "arg_params": {k: v.copy()
                       for k, v in _worker_init_params().items()}}
    code = 0
    try:
        with ElasticSession(mgr):
            with mesh:
                mod.fit(it, num_epoch=1, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.05,
                                          "momentum": 0.9},
                        kvstore="dist_device_sync",
                        batch_end_callback=boundary_save, **kwargs)
            assert mod._mesh is not None, "mesh fused path not engaged"
        params, _ = mod.get_params()
        payload = {"finished": True,
                   "params": {k: np.asarray(v.asnumpy()).tolist()
                              for k, v in params.items()}}
    except (PeerLostError, PreemptionError) as e:
        code = exit_code_for(e)
        payload = {"finished": False, "fault": type(e).__name__}
        # typed-fatal: land this rank's event ring for the launcher's
        # postmortem bundle before taking the elastic exit
        _telemetry.flight.auto_dump(f"typed-fatal:{type(e).__name__}")
    counts = _prof.dispatch_counts()
    snap = _telemetry.REGISTRY.snapshot()["metrics"]
    coll = snap.get("mxnet_collective_bytes_total", {}).get("values", [])
    payload.update({
        "rank": int(os.environ.get("MXNET_MULTIHOST_PROC_ID", 0)),
        "world": int(os.environ.get("MXNET_MULTIHOST_NUM_PROCS", 1)),
        "dispatch_counts": counts,
        # steps THIS process ran this generation (resume-sliced data)
        "steps_run": len(x) // batch if payload.get("finished") else None,
        "collective_bytes": {str(v["labels"].get("kind")): v["value"]
                             for v in coll},
    })
    tmp = f"{out_json}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, f"{out_json}.rank{payload['rank']}")
    if rt is not None:
        rt.shutdown()
    mgr.close()
    if code:
        # elastic exit: skip atexit — jax.distributed.shutdown() blocks
        # waiting for the DEAD peer to disconnect (an unbounded wait on
        # a corpse, exactly what this runtime exists to prevent).  The
        # boundary checkpoint is committed and the payload file is
        # os.replace'd: nothing left to flush.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(code)
    sys.exit(0)


def _launch(workdir, world, n_batches, batch, K, rank_env=None,
            leave_at=0):
    """One elastic training job; returns (summary, per-rank payloads of
    the FINAL generation, launcher)."""
    os.makedirs(workdir, exist_ok=True)
    ckdir = os.path.join(workdir, "ckpt")
    out = os.path.join(workdir, "out.json")

    def argv(generation, w, rank):
        a = [sys.executable, "-m", "mxnet_tpu.parallel.elastic",
             "--worker", ckdir, out, str(n_batches), str(batch)]
        if leave_at and generation == 0 and rank == w - 1:
            a.append(str(leave_at))
        return a

    launcher = ElasticLauncher(
        argv, world, rank_env=rank_env or {},
        env_extra={"MXNET_SCAN_STEPS": str(K),
                   "MXNET_MESH_FUSED_STEP": "1"},
        gen_timeout_s=120.0,
        postmortem_dir=os.path.join(workdir, "postmortem"))
    try:
        summary = launcher.run()
    finally:
        launcher.close()
    payloads = {}
    for rank in range(world):
        path = f"{out}.rank{rank}"
        if os.path.exists(path):
            with open(path) as f:
                payloads[rank] = json.load(f)
    return summary, payloads, launcher


def _final_params(payloads):
    for rank in sorted(payloads):
        p = payloads[rank]
        if p.get("finished") and p.get("params"):
            return {k: np.asarray(v, np.float32)
                    for k, v in p["params"].items()}
    raise MXNetError(f"no finishing worker wrote final params: "
                     f"{ {r: p.get('finished') for r, p in payloads.items()} }")


def _scrape_fleet_and_postmortem(launcher):
    """The ISSUE-12 observability assertions for a faulted elastic run:
    HTTP-scrape /fleet.json off the leader's exporter and validate the
    lost-rank tagging, the per-generation family history, and the
    postmortem bundle's contents.  Returns (fleet snapshot, bundle)."""
    import urllib.request

    from .. import telemetry as _telemetry_mod

    port = _telemetry_mod.start_exporter(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet.json", timeout=10) as r:
            fleet_view = json.loads(r.read().decode("utf-8"))
    finally:
        _telemetry_mod.stop_exporter()
    ranks = fleet_view["ranks"]
    assert "0" in ranks and "1" in ranks, sorted(ranks)
    assert ranks["1"]["state"] == "lost", \
        f"killed rank not tagged lost: {ranks['1']['state']}"
    assert ranks["1"]["families"], \
        "lost rank's last registry snapshot was dropped"
    assert fleet_view["generations"], "no generation history"
    for gen, gen_ranks in fleet_view["generations"].items():
        assert gen_ranks, f"generation {gen} has no ranks"
        for rank, v in gen_ranks.items():
            assert v["families"], \
                f"generation {gen} rank {rank} has no families"
    assert launcher.postmortems, "fault generation left no postmortem"
    with open(launcher.postmortems[0], encoding="utf-8") as f:
        bundle = json.load(f)
    assert len(bundle["rings"]) >= 2, \
        f"expected every rank's flight ring: {sorted(bundle['rings'])}"
    assert bundle["fleet"]["ranks"]["1"]["state"] == "lost", bundle["fleet"]
    anomaly = bundle.get("first_anomaly") or {}
    site = str((anomaly.get("fields") or {}).get("site", ""))
    assert "multihost/peer_loss" in site, \
        f"first anomalous event does not name the injected site: {anomaly}"
    return fleet_view, bundle


def _smoke():
    """CI gate (ISSUE 11): a 2-process × 4-fake-device elastic fit whose
    rank-1 host is SIGKILLed at window 3 must (a) recover — survivors
    checkpoint the boundary, the launcher respawns the dp/2 world, and
    training finishes — and (b) produce final weights BITWISE identical
    to a planned resize that shrank at the same boundary; plus the
    per-process dispatch budget <= (1+eps)/K."""
    import shutil
    import tempfile

    base = tempfile.mkdtemp(prefix="mx-elastic-smoke-")
    K, NB, BS = 2, 8, 32  # 4 windows; kill before window 3
    try:
        t0 = time.perf_counter()
        # run A: rank 1 killed at its 3rd window boundary probe
        sa, pa, la = _launch(
            os.path.join(base, "faulted"), 2, NB, BS, K,
            rank_env={1: {"MXNET_CHAOS":
                          "multihost/peer_loss=kill:hits=3"}})
        # observability plane (ISSUE 12): scrape the leader's
        # /fleet.json while THIS launcher is still the provider — the
        # killed rank must be tagged lost with its last registry
        # snapshot (never silently dropped), every generation must
        # carry per-rank families, and the fault generation must have
        # left ONE postmortem bundle holding all ranks' flight rings +
        # the final fleet snapshot, with the injected site as the
        # first anomalous event
        fleet_view, bundle = _scrape_fleet_and_postmortem(la)
        # run B: the planned resize — rank 1 leaves at the same boundary
        sb, pb, _lb = _launch(
            os.path.join(base, "planned"), 2, NB, BS, K,
            leave_at=2 * K)
        wall = time.perf_counter() - t0
        assert sa["ok"] and sa["restarts"] >= 1, sa
        assert sb["ok"], sb
        gen0 = sa["history"][0]
        assert -signal.SIGKILL in gen0["exits"], \
            f"kill arm did not fire: {gen0}"
        assert ELASTIC_RESTART in gen0["exits"], \
            f"survivor did not take the typed restart exit: {gen0}"
        assert sa["history"][-1]["world"] == 1, sa["history"]
        p_fault = _final_params(pa)
        p_plan = _final_params(pb)
        diverged = [k for k in p_plan
                    if not np.array_equal(p_fault[k], p_plan[k])]
        assert not diverged, f"faulted != planned resize on {diverged}"
        # dispatch budget: the finishing worker ran windows only
        fin = next(p for p in pa.values() if p.get("finished"))
        total = fin["dispatch_counts"].get("total", 0)
        steps = fin["steps_run"] or (NB - 2 * K)
        budget = (1 + 0.25) / K
        assert total / max(1, steps) <= budget, \
            f"{total}/{steps} dispatches/step > {budget}"
        rec = (sa.get("recovery_s") or [None])[0]
        print(f"elastic smoke OK: SIGKILL host 1/2 at window 3 -> "
              f"survivor checkpointed, world respawned at dp/2, "
              f"recovery {rec and round(rec, 1)}s, final weights "
              f"BITWISE identical to the planned resize; "
              f"{total}/{steps} dispatches/step <= {budget:.3f}; "
              f"/fleet.json tagged the lost rank across "
              f"{len(fleet_view['generations'])} generation(s), "
              f"postmortem bundle has {len(bundle['rings'])} ring(s) "
              f"with first anomaly at "
              f"{(bundle['first_anomaly'] or {}).get('fields', {}).get('site')} "
              f"(total {wall:.0f}s)")
    finally:
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker_main(sys.argv[sys.argv.index("--worker") + 1:])
    else:
        _smoke()
