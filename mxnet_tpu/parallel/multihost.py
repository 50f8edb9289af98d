"""Multi-host initialization (parity: ps-lite rendezvous — DMLC_ROLE /
DMLC_PS_ROOT_URI env contract, SURVEY §2.4; and the reference's
dist_device_sync scaling path).

TPU redesign: multi-host data/model parallelism is ONE jax.distributed
job — every host runs the same SPMD program over the global mesh and XLA
routes collectives over ICI within a slice and DCN across slices. This
module adapts the reference's env-variable rendezvous contract onto
jax.distributed.initialize so launcher scripts keep working:

    DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT -> coordinator address
    DMLC_NUM_WORKER                      -> num_processes
    DMLC_RANK / DMLC_WORKER_ID           -> process_id

On Cloud TPU pods, call init_multihost() with no args — jax.distributed
autodetects the coordinator from the TPU metadata. After initialization,
`jax.devices()` spans the whole pod and every DeviceMesh built from it is
a global mesh.
"""
from __future__ import annotations

import logging
import os
import threading
import time

import jax

from ..base import MXNetError, PeerLostError, PreemptionError

log = logging.getLogger("mxnet_tpu.multihost")

_initialized = False
_RUNTIME = None


def _enable_cpu_collectives():
    """Cross-process computations on the CPU backend need a collectives
    implementation; gloo ships with jaxlib.  Must run BEFORE
    jax.distributed.initialize — harmless on TPU (ICI/DCN collectives
    are native) and on jax versions without the option."""
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception as e:  # noqa: BLE001 — absent option on old jax: TPU paths don't need it
        log.debug("cpu collectives config unavailable: %s", e)


def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None):
    """Initialize the multi-host runtime (idempotent).

    With no arguments, resolves from the ``MXNET_MULTIHOST_*`` contract
    (the elastic launcher's env), then the DMLC_* contract, else defers
    to jax.distributed autodetection (TPU pod metadata).
    Single-process setups (num_processes == 1) are a no-op.
    """
    global _initialized
    if _initialized:
        return
    if coordinator_address is None:
        from .. import config as _config
        coord = _config.get("MXNET_MULTIHOST_COORD")
        if coord:
            coordinator_address = coord
            if num_processes is None:
                num_processes = _config.get("MXNET_MULTIHOST_NUM_PROCS")
            if process_id is None:
                process_id = _config.get("MXNET_MULTIHOST_PROC_ID")
    if coordinator_address is None:
        root = os.environ.get("MXNET_COORDINATOR_URI")
        if root:
            port = os.environ.get("MXNET_COORDINATOR_PORT", "8476")
            coordinator_address = f"{root}:{port}"
        elif "DMLC_ROLE" not in os.environ:
            # DMLC_PS_ROOT_URI:PORT addresses the TCP parameter server in a
            # PS launch (DMLC_ROLE set); rendezvousing jax.distributed
            # against that socket would hang.  Only borrow it when no PS
            # deployment is indicated.
            root = os.environ.get("DMLC_PS_ROOT_URI")
            if root:
                port = os.environ.get("DMLC_PS_ROOT_PORT", "8476")
                coordinator_address = f"{root}:{port}"
    if coordinator_address is not None or "DMLC_ROLE" not in os.environ:
        # in a PS deployment (DMLC_ROLE set) borrow worker count/rank only
        # once a coordinator address is actually in play — otherwise all
        # three stay None and the PS no-op below applies instead of the
        # all-or-none check misfiring on a half-borrowed DMLC contract
        if num_processes is None and os.environ.get("DMLC_NUM_WORKER"):
            num_processes = int(os.environ["DMLC_NUM_WORKER"])
        if process_id is None:
            rank = os.environ.get("DMLC_RANK",
                                  os.environ.get("DMLC_WORKER_ID"))
            if rank is not None:
                process_id = int(rank)
    if num_processes is not None and num_processes <= 1:
        _initialized = True
        return  # single host: nothing to rendezvous
    if (coordinator_address is None and num_processes is None
            and process_id is None and "DMLC_ROLE" in os.environ):
        # PS deployment with no explicit multihost config: the parameter
        # server owns cross-process coordination; a jax.distributed
        # rendezvous here would target the PS socket and hang
        _initialized = True
        return
    provided = (coordinator_address, num_processes, process_id)
    if any(v is not None for v in provided) and \
            any(v is None for v in provided):
        raise MXNetError(
            "init_multihost: coordinator_address, num_processes and "
            "process_id must be given together (DMLC_PS_ROOT_URI[:PORT] "
            "+ DMLC_NUM_WORKER + DMLC_RANK) — or none of them on a TPU "
            "pod, where jax.distributed autodetects")
    if jax.distributed.is_initialized():
        _initialized = True
        return  # someone else initialized the runtime: honor idempotence
    _enable_cpu_collectives()
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # multi-process CPU (gloo) executables do NOT round-trip the
        # persistent compile cache: a serialized cross-process
        # collective program reloaded by another rank (or a later
        # world generation) computes garbage — observed as all-NaN
        # gradients and glibc heap aborts.  Real TPU pods keep the
        # cache (that serialization path is proven upstream).
        os.environ.setdefault("MXNET_COMPILE_CACHE", "0")
    try:
        # the rendezvous itself is a coordination wait: bound it, so a
        # stolen coordinator port / dead peer at startup becomes a
        # child ERROR exit the elastic launcher can respawn, never a
        # silent multi-minute stall
        kw = {}
        if os.environ.get("MXNET_MULTIHOST_COORD"):
            kw["initialization_timeout"] = 60
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id, **kw)
    except RuntimeError as e:
        msg = str(e).lower()
        # jax wordings across versions: "...already initialized" /
        # "distributed.initialize should only be called once."
        if "already initialized" in msg or "only be called once" in msg:
            _initialized = True
            return
        raise
    _initialized = True


def process_index():
    return jax.process_index()


def process_count():
    return jax.process_count()


def is_coordinator():
    return jax.process_index() == 0


# -- the coordinated runtime (ISSUE 11) --------------------------------------
class MultiHostRuntime:
    """Peer liveness + window coordination for a multi-process mesh job.

    Rides the existing kvstore_server transport: every process holds a
    :class:`~mxnet_tpu.kvstore_server.KVClient` to a control-plane
    server (owned by the elastic launcher, so it outlives any worker),
    heartbeats its liveness + training progress on a dedicated thread,
    and coordinates each fused window through a **deadline-bounded
    rendezvous** — the control server's dead-peer propagation turns a
    vanished host into a typed :class:`PeerLostError` at the next
    rendezvous instead of a survivor hanging inside a doomed collective.

    SIGTERM (the preemption notice) sets a flag the window-boundary
    probe turns into a typed :class:`PreemptionError`; both errors reach
    the elastic session (``parallel/elastic.py``), which checkpoints at
    the boundary and hands the world back to the launcher for the
    survivor-mesh restore.  Every wait here is bounded: heartbeat-aged
    peer detection, explicit barrier deadlines, socket timeouts.
    """

    def __init__(self, rank, world, control_host, control_port,
                 heartbeat_s=None, peer_timeout_s=None,
                 barrier_timeout_s=None):
        from .. import config as _config
        from ..kvstore_server import KVClient
        self.rank = int(rank)
        self.world = int(world)
        self.heartbeat_s = float(
            heartbeat_s if heartbeat_s is not None
            else _config.get("MXNET_MULTIHOST_HEARTBEAT_S"))
        self.peer_timeout_s = float(
            peer_timeout_s if peer_timeout_s is not None
            else _config.get("MXNET_MULTIHOST_PEER_TIMEOUT_S"))
        self.barrier_timeout_s = float(
            barrier_timeout_s if barrier_timeout_s is not None
            else _config.get("MXNET_MULTIHOST_BARRIER_TIMEOUT_S"))
        # the control client's own socket timeout bounds every RPC;
        # keep it above the barrier deadline so the server's typed
        # reply (not a socket timeout) is what the caller sees
        self._client = KVClient(control_host, int(control_port),
                                rank=self.rank, num_workers=self.world,
                                timeout=self.barrier_timeout_s + 30,
                                heartbeat_interval=0)
        self._preempted = threading.Event()
        self._stop = threading.Event()
        self._step = 0
        # global-progress offset: an elastically-restored worker's
        # local step counters restart at 0; the worker sets this to the
        # restored boundary step so reported progress stays monotonic
        # across generations (the launcher's recovery clock needs that)
        self.progress_base = 0
        self._lock = threading.Lock()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True,
            name="multihost-heartbeat")
        self._client.heartbeat(step=0)
        self._hb_thread.start()
        # cross-rank telemetry aggregation (ISSUE 12): push this rank's
        # registry snapshot to the control plane so the leader's fleet
        # merge always has a (possibly last) snapshot to tag.  Own
        # connection: a barrier blocking the main RPC socket for 100s
        # must not stall telemetry.
        self._fleet = None
        fleet_interval = float(_config.get("MXNET_FLEET_INTERVAL_S"))
        if fleet_interval > 0:
            from ..telemetry.fleet import FleetReporter
            self._fleet = FleetReporter(
                control_host, int(control_port), self.rank, self.world,
                fleet_interval)

    # -- liveness -----------------------------------------------------------
    def _heartbeat_loop(self):
        from ..chaos.failpoints import failpoint as _failpoint
        while not self._stop.wait(self.heartbeat_s):
            try:
                _failpoint("multihost/heartbeat")
                with self._lock:
                    step = self._step
                self._client.heartbeat(step=step)
            except Exception as e:  # noqa: BLE001 — a missed beat ages this rank toward "lost"; dying here would hide that
                log.warning("multihost rank %d heartbeat failed (%s: "
                            "%s); peer will age toward lost",
                            self.rank, type(e).__name__, e)
                if self._stop.is_set() or self._client._closed:
                    return

    def peer_states(self):
        """{rank: {"state", "age_s", "step"}} from the control server
        (one bounded RPC); exports the peer-state gauge."""
        states = self._client.peer_states()
        try:
            from .. import telemetry as _telemetry
            gauge = _telemetry.REGISTRY.gauge(
                "mxnet_multihost_peers",
                "multi-host peers by liveness state")
            counts = {}
            for info in states.values():
                counts[info["state"]] = counts.get(info["state"], 0) + 1
            for state in ("alive", "lost", "unknown"):
                gauge.set(counts.get(state, 0), labels={"state": state})
        except Exception:  # graftlint: disable=swallowed-error -- telemetry must never fail a liveness probe
            pass
        return states

    def lost_peers(self):
        return sorted(r for r, info in self.peer_states().items()
                      if info["state"] == "lost" and r != self.rank)

    def preempted(self):
        return self._preempted.is_set()

    def request_preemption(self):
        """Mark this host as leaving (SIGTERM handler / planned
        resize): the next window-boundary probe raises typed."""
        self._preempted.set()

    def install_sigterm(self):
        import signal

        def _on_term(_signum, _frame):
            log.warning("multihost rank %d: SIGTERM — leaving at the "
                        "next window boundary", self.rank)
            self._preempted.set()
            from ..telemetry import flight as _flight
            _flight.record("multihost", "sigterm", severity="warn",
                           rank=self.rank)
            _flight.auto_dump("sigterm")

        signal.signal(signal.SIGTERM, _on_term)

    # -- coordination -------------------------------------------------------
    def check(self):
        """The window-boundary probe: typed errors for elastic events,
        silence otherwise."""
        from ..telemetry import flight as _flight
        if self._preempted.is_set():
            _flight.record("multihost", "preempted", severity="error",
                           rank=self.rank)
            raise PreemptionError(
                f"rank {self.rank}: preemption notice received — "
                "leaving the mesh at this window boundary")
        if self.world > 1:
            lost = self.lost_peers()
            if lost:
                _flight.record("multihost", "peer_lost",
                               severity="error", rank=self.rank,
                               lost=lost)
                raise PeerLostError(lost)

    def window_rendezvous(self):
        """All alive ranks agree to dispatch the next window, or the
        wait fails typed within the barrier deadline — a survivor never
        enters a collective a dead peer can't join."""
        if self.world <= 1:
            return
        self._client.barrier_deadline(self.barrier_timeout_s)

    def report_progress(self, step):
        step = int(step) + int(self.progress_base)
        with self._lock:
            self._step = step
        try:
            self._client.report_progress(step)
        except PeerLostError:
            raise
        except Exception as e:  # noqa: BLE001 — progress is advisory; liveness rides the heartbeat thread
            log.debug("progress report failed: %s", e)

    def wait_ready(self, arrays, poll_s=0.02, peer_check_s=0.5):
        """Block until every array's in-flight computation lands — but
        watch the peers while blocked: if a rank dies mid-dispatch the
        collective inside can never complete, so raise typed instead of
        waiting forever.  The wait is bounded by peer-death detection
        (heartbeat timeout), not by an arbitrary compute deadline — a
        slow healthy window is never failed."""
        if self.world <= 1 or not arrays:
            return
        done = threading.Event()

        def _block():
            try:
                jax.block_until_ready(arrays)
            except Exception:  # graftlint: disable=swallowed-error -- the waiter only signals; the main thread re-blocks and surfaces the real error
                pass
            done.set()

        t = threading.Thread(target=_block, daemon=True,
                             name="multihost-wait-ready")
        t.start()
        last_check = time.monotonic()
        while not done.wait(poll_s):
            if time.monotonic() - last_check >= peer_check_s:
                last_check = time.monotonic()
                lost = self.lost_peers()
                if lost:
                    from ..telemetry import flight as _flight
                    _flight.record("multihost", "peer_lost_in_flight",
                                   severity="error", rank=self.rank,
                                   lost=lost)
                    raise PeerLostError(
                        lost, "peer died while a mesh window was in "
                        "flight; abandoning the doomed collective")

    def shutdown(self):
        self._stop.set()
        if self._fleet is not None:
            # final push: the fleet snapshot keeps this rank's last
            # registry state even after a clean exit
            self._fleet.stop(final_push=True)
        try:
            self._client.close()
        except Exception:  # graftlint: disable=swallowed-error -- best-effort teardown on a possibly-dead transport
            pass


def runtime():
    """The process-wide MultiHostRuntime (None when not launched as an
    elastic multi-host worker)."""
    return _RUNTIME


def init_runtime():
    """Create the process-wide runtime from the MXNET_MULTIHOST_*
    contract (no-op without a control server configured)."""
    global _RUNTIME
    if _RUNTIME is not None:
        return _RUNTIME
    from .. import config as _config
    host = _config.get("MXNET_MULTIHOST_CONTROL_URI")
    port = _config.get("MXNET_MULTIHOST_CONTROL_PORT")
    if not host or not port:
        return None
    _RUNTIME = MultiHostRuntime(
        rank=_config.get("MXNET_MULTIHOST_PROC_ID"),
        world=_config.get("MXNET_MULTIHOST_NUM_PROCS"),
        control_host=host, control_port=port)
    return _RUNTIME


def shutdown_runtime():
    global _RUNTIME
    if _RUNTIME is not None:
        _RUNTIME.shutdown()
        _RUNTIME = None
