"""Composed chaos scenarios — the outages unit tests cannot see.

Each scenario function is self-contained, deterministic (failpoints are
hit-count triggered, subprocess fault schedules ride in ``MXNET_CHAOS``
env specs), and returns a plain result dict; ``tests/test_chaos.py``
asserts on the dicts and ``python -m mxnet_tpu.chaos.smoke`` replays
them in CI.  The four scenarios compose faults that PRs 1-7 only ever
tested alone:

1. **worker kill/revive** — a dist kvstore worker SIGKILLs itself
   mid-epoch (chaos ``kill`` at the Nth client RPC); a replacement
   attaches, restores the rank-0 checkpoint, heals two injected
   transient RPC faults through the bounded retry, and training commits
   steps past the kill.
2. **corrupt checkpoint under serving load** — a corrupt step commits
   into a watched checkpoint directory while clients hammer the server;
   the poller quarantines it (alarm counter), the old version keeps
   serving with zero non-shed failures, and the next good step hot-
   reloads normally.
3. **wedged batcher worker** — one of two workers wedges; the watchdog
   fires naming the wedged section, ``/healthz`` flips to 503 (and back
   after release), the in-flight sweep resolves the wedged batch as
   typed timeouts, and the surviving worker keeps p99 bounded.
4. **SIGKILL mid-scan-window** — a K-step scanned fit dies between
   window boundaries; restore continues from the last boundary
   checkpoint bit-identically to an uninterrupted run.
5. **mesh collective stall + kill-resize** — the mesh fused step's
   ``parallel/collective`` boundary wedges (watchdog names the stalled
   mesh step, the fit self-heals through the wedge timeout), then a
   dp=4 mesh fit SIGKILLs mid-run and a boundary-checkpoint restore
   onto a RESIZED dp=2 mesh continues bit-identically to a planned
   resize (elastic restore as the resize mechanism).
6. **replica kill mid-burst** (ISSUE 10) — injected
   ``serving/router/dispatch`` faults spill to sibling replicas, then
   one replica of the pool is removed under load: it drains everything
   it admitted, the survivors absorb the traffic, and zero non-shed
   requests are dropped or hung.
7. **replica kill mid-generation** (ISSUE 16) — an injected
   ``serving/generation/decode`` fault kills one of two generation
   engines past its restart budget mid-stream: every victim session
   fails typed-retryable (never hangs) and resumes on the sibling from
   ``prompt + tokens-so-far``, survivor sessions stream untouched, and
   both engines' KV slots and ledger pages are provably released
   (zero-leak asserted).
8. **reader death mid-epoch** (ISSUE 19) — one reader worker of the
   streaming data plane dies at the Nth ``io/reader/read``: the
   pipeline rebalances its shards onto the survivors, the epoch
   completes with every sample delivered exactly once in the seeded
   shard order, zero stalls; a slow reader (delay arm) is absorbed the
   same way; killing ALL readers raises a typed ``DataReaderError`` —
   never a hang.

Every scenario ends in recovery or a typed error — the assertions
include "no hang" (bounded waits everywhere) and "no silent loss"
(every request/save is accounted for).  docs/chaos.md is the runbook.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

from . import failpoints as chaos

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _child_env(**extra):
    env = dict(os.environ)
    env.pop("MXNET_CHAOS", None)           # each child gets its own spec
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


# ---------------------------------------------------------------------------
# scenario 1: kvstore worker kill/revive mid-epoch
# ---------------------------------------------------------------------------
_KV_WORKER = """
import os, sys, time
import numpy as np
import mxnet_tpu as mx
import mxnet_tpu.chaos  # arms MXNET_CHAOS from this child's environment
from mxnet_tpu import kvstore as kvs
from mxnet_tpu import nd
from mxnet_tpu.checkpoint import CheckpointManager, restore

rank = int(os.environ["DMLC_RANK"])
steps = int(sys.argv[1])
ckdir = sys.argv[2]
out = sys.argv[3]
resume = int(sys.argv[4])
target = np.array([0.5, -1.25, 2.0, 0.125], np.float32)

kv = kvs.create("dist_async")
start = 0
if resume:
    kv.attach("w", nd.zeros((4,)))
    ck = restore(ckdir)
    start = ck.step
    blob = ck.blobs.get("optimizer_states")
    if blob is not None:
        kv.set_optimizer_states(blob)
else:
    kv.init("w", nd.zeros((4,)))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.05))

mgr = CheckpointManager(ckdir, keep_last=3) if rank == 0 else None
w = nd.zeros((4,))
for step in range(start, steps):
    kv.pull("w", out=w)
    grad = 2.0 * (w.asnumpy() - target)
    kv.push("w", nd.array(grad))
    if rank == 0:
        blobs = {"optimizer_states": kv.get_optimizer_states()}
        mgr.save(step + 1, arrays={"w": w}, blobs=blobs, block=True)
    time.sleep(0.02)
kv.pull("w", out=w)
np.save(out, w.asnumpy())
if mgr is not None:
    mgr.close()
"""


def scenario_worker_kill_revive(workdir, port=19733, steps=30,
                                timeout=180.0):
    """Kill a kvstore worker mid-epoch via a chaos ``kill`` arm at its
    Nth client RPC; revive it with an elastic attach + checkpoint
    restore (its retry path additionally heals two injected transient
    RPC faults); assert training commits steps PAST the kill."""
    import numpy as np

    from ..checkpoint import latest_step
    from ..kvstore_server import KVServer

    workdir = str(workdir)
    os.makedirs(workdir, exist_ok=True)
    script = os.path.join(workdir, "kv_worker.py")
    with open(script, "w") as f:  # graftlint: disable=torn-write -- ephemeral scenario script, single consumer
        f.write(_KV_WORKER)
    ckdir = os.path.join(workdir, "ckpt")
    outs = [os.path.join(workdir, f"w{r}.npy") for r in range(2)]

    server = KVServer(port=port, num_workers=2)
    threading.Thread(target=server.run, daemon=True).start()
    time.sleep(0.2)

    def spawn(rank, resume, chaos_spec=""):
        env = _child_env(
            DMLC_RANK=rank, DMLC_NUM_WORKER=2,
            DMLC_PS_ROOT_URI="127.0.0.1", DMLC_PS_ROOT_PORT=port,
            MXNET_KVSTORE_HEARTBEAT_INTERVAL="0.2",
            MXNET_KVSTORE_RETRY_BACKOFF_S="0.02")
        if chaos_spec:
            env["MXNET_CHAOS"] = chaos_spec
        return subprocess.Popen(
            [sys.executable, script, str(steps), ckdir, outs[rank],
             str(int(resume))], env=env)

    result = {"ok": False}
    deadline = time.time() + timeout
    # rank 1 SIGKILLs itself deterministically at its 25th client RPC
    # (mid-epoch: each train step is at least 2 RPCs)
    procs = [spawn(0, False),
             spawn(1, False, chaos_spec="kvstore/client/rpc=kill:hits=25")]
    try:
        procs[1].wait(timeout=max(10.0, timeout / 2))
        result["victim_exit"] = procs[1].returncode
        kill_step = None
        while kill_step is None and time.time() < deadline:
            kill_step = latest_step(ckdir)
            time.sleep(0.1)
        result["kill_step"] = kill_step
        # revive: elastic attach + restore, WITH two transient RPC
        # faults injected — the bounded retry must absorb them
        procs[1] = spawn(
            1, True,
            chaos_spec="kvstore/client/rpc=raise(ConnectionError)"
                       ":hits=10:count=2")
        for p in procs:
            p.wait(timeout=max(1.0, deadline - time.time()))
        result["exit_codes"] = [p.returncode for p in procs]
        final_step = latest_step(ckdir)
        result["final_step"] = final_step
        finals = [np.load(o) for o in outs if os.path.exists(o)]
        target = np.array([0.5, -1.25, 2.0, 0.125], np.float32)
        result["n_finished"] = len(finals)
        result["converged"] = bool(
            len(finals) == 2
            and all(np.allclose(f, target, atol=0.05) for f in finals))
        result["ok"] = bool(
            result["victim_exit"] == -9          # the kill arm fired
            and result["exit_codes"] == [0, 0]   # both survivors finished
            and final_step == steps              # committed past the kill
            and kill_step is not None and final_step > kill_step
            and result["converged"])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server._stop.set()
    return result


# ---------------------------------------------------------------------------
# scenario 2: corrupt checkpoint during a serving hot-reload under load
# ---------------------------------------------------------------------------
def _tiny_model(seed=0, scale=0.05, in_dim=16, width=32, classes=10):
    import numpy as np

    import mxnet_tpu as mx
    h = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(h, num_hidden=width, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    sym = mx.sym.FullyConnected(h, num_hidden=classes, name="out")
    rng = np.random.RandomState(seed)
    params = {
        "fc1_weight": mx.nd.array(
            rng.randn(width, in_dim).astype(np.float32) * scale),
        "fc1_bias": mx.nd.zeros((width,)),
        "out_weight": mx.nd.array(
            rng.randn(classes, width).astype(np.float32) * scale),
        "out_bias": mx.nd.zeros((classes,)),
    }
    return sym, params


def scenario_corrupt_reload_under_load(workdir, seconds=2.5,
                                       n_clients=4):
    """Commit a CORRUPT checkpoint step into a watched directory while
    clients hammer the server: the poller must quarantine it (alarm
    counter), keep serving the old version with zero non-shed request
    failures, and pick up the next GOOD step normally."""
    import numpy as np

    import mxnet_tpu as mx
    from .. import serving, telemetry
    from ..checkpoint import CheckpointManager
    from ..checkpoint.core import MANIFEST, step_dir
    from ..serving.batcher import ServingOverloadError
    from ..telemetry import watchdog as wd

    workdir = str(workdir)
    ckdir = os.path.join(workdir, "ckpt")
    # the watchdog runs ARMED through this scenario and must stay
    # silent: a corrupt reload degrades, it never stalls the stack
    os.environ["MXNET_WATCHDOG_S"] = "5.0"
    fires0 = wd.fires()
    sym, params = _tiny_model()
    mgr = CheckpointManager(ckdir, async_save=False, keep_last=0)
    mgr.save(1, arrays=params, symbol=sym, block=True)

    alarm = telemetry.REGISTRY.counter("mxnet_serving_corrupt_ckpt_total")
    alarm0 = alarm.value(labels={"model": "m"})

    server = serving.ModelServer(max_batch_size=8, name="chaos-reload")
    result = {"ok": False, "non_shed_failures": [], "shed": 0,
              "served": 0}
    lock = threading.Lock()
    stop = threading.Event()
    try:
        server.repository.watch("m", ckdir, interval=0.05)
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                server.repository.get("m")
                break
            except mx.base.MXNetError:
                time.sleep(0.05)
        x = np.ones((16,), np.float32)

        def client():
            while not stop.is_set():
                try:
                    server.predict("m", {"data": x}, wait_s=30.0)
                    with lock:
                        result["served"] += 1
                except ServingOverloadError:
                    with lock:
                        result["shed"] += 1
                except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                    with lock:
                        result["non_shed_failures"].append(
                            f"{type(e).__name__}: {e}")
                # graftlint: disable=naked-retry -- paced load generator; lifetime is bounded by the stop event the scenario always sets
                time.sleep(0.002)

        clients = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in clients:
            t.start()
        time.sleep(seconds / 3)

        # craft a COMMITTED-but-corrupt step 2: clone step 1, flip bytes
        # in the data file, fix the manifest step, commit atomically (the
        # watcher can never see a half-built dir)
        src = step_dir(ckdir, 1)
        build = step_dir(ckdir, 2) + ".build"
        shutil.copytree(src, build)
        with open(os.path.join(build, MANIFEST)) as f:
            manifest = json.load(f)
        manifest["step"] = 2
        data_name = next(iter(manifest["files"]))
        with open(os.path.join(build, data_name), "r+b") as f:
            f.seek(10)
            f.write(b"\xff\xff\xff\xff")  # checksum now lies
        with open(os.path.join(build, MANIFEST), "w") as f:
            json.dump(manifest, f)
        os.rename(build, step_dir(ckdir, 2))
        result["corrupt_committed_at"] = 2

        time.sleep(seconds / 3)  # several polls hit the corrupt step
        with lock:
            result["version_during_corruption"] = \
                server.repository.latest_version("m")

        # the next GOOD step must still hot-reload (fresh param values
        # so the swap is observable)
        _sym, params3 = _tiny_model(seed=7, scale=0.07)
        mgr.save(3, arrays=params3, symbol=sym, block=True)
        deadline = time.time() + 15
        while server.repository.latest_version("m") < 3 and \
                time.time() < deadline:
            time.sleep(0.05)
        time.sleep(seconds / 3)
        stop.set()
        for t in clients:
            t.join(timeout=30)
        result["final_version"] = server.repository.latest_version("m")
        result["quarantined"] = server.repository.corrupt_steps(
            "m", ckdir)
        result["alarm_count"] = alarm.value(labels={"model": "m"}) - alarm0
        result["watchdog_silent"] = wd.fires() == fires0
        result["ok"] = bool(
            not result["non_shed_failures"]
            and result["served"] > 0
            and result["version_during_corruption"] == 1
            and result["final_version"] == 3
            and result["quarantined"] == [2]
            and result["alarm_count"] >= 1
            and result["watchdog_silent"])
    finally:
        stop.set()
        server.repository.stop_watches()
        server.shutdown()
        mgr.close()
        os.environ.pop("MXNET_WATCHDOG_S", None)
    return result


# ---------------------------------------------------------------------------
# scenario 3: wedged batcher worker — watchdog + shedding + liveness
# ---------------------------------------------------------------------------
def scenario_wedged_batcher(seconds=2.0, watchdog_s=0.4, n_clients=6):
    """Wedge one of two batcher workers; assert the watchdog fires
    naming the wedged section, /healthz flips 503 -> 200 around the
    stall, the wedged batch resolves as typed timeouts (nothing lost),
    and the surviving worker + shedding keep p99 bounded."""
    import numpy as np

    from .. import telemetry
    from ..serving.batcher import (DynamicBatcher, RequestTimeoutError,
                                   ServingOverloadError)
    from ..telemetry import watchdog as wd
    from ..telemetry.exporter import start_exporter, stop_exporter

    os.environ["MXNET_WATCHDOG_S"] = str(watchdog_s)
    dump_dir = tempfile.mkdtemp(prefix="mx-chaos-wd-")
    os.environ["MXNET_WATCHDOG_DIR"] = dump_dir
    fires0 = wd.fires()
    chaos.reset()
    chaos.arm("serving/batcher/worker", "wedge", hits=1, count=1)

    def runner(feed, n_real):
        time.sleep(0.002)
        return [feed["x"] * 2.0]

    def healthz(port):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    result = {"ok": False, "non_typed_failures": [], "shed": 0,
              "timeouts": 0, "served": 0}
    lat_ms = []
    lock = threading.Lock()
    stop_t = time.perf_counter() + seconds
    port = start_exporter(0)
    b = DynamicBatcher(runner, max_batch_size=8, max_latency_ms=2.0,
                       num_workers=2, max_queue_depth=64,
                       shed_watermark=16, name="chaos-wedge")
    try:
        def client():
            x = np.ones((8,), np.float32)
            while time.perf_counter() < stop_t:
                t0 = time.perf_counter()
                try:
                    b.submit({"x": x}, timeout_ms=400.0).result(10.0)
                    with lock:
                        lat_ms.append((time.perf_counter() - t0) * 1e3)
                        result["served"] += 1
                except ServingOverloadError:
                    with lock:
                        result["shed"] += 1
                    time.sleep(0.001)
                except RequestTimeoutError:
                    with lock:
                        result["timeouts"] += 1
                except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                    with lock:
                        result["non_typed_failures"].append(
                            f"{type(e).__name__}: {e}")

        clients = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in clients:
            t.start()
        # the watchdog must fire for the wedged section mid-load
        deadline = time.time() + max(10.0, 6 * watchdog_s)
        while wd.fires() <= fires0 and time.time() < deadline:
            time.sleep(0.05)
        result["watchdog_fired"] = wd.fires() > fires0
        result["stalled_sections"] = wd.stalled_sections()
        code, body = healthz(port)
        result["healthz_during_stall"] = (code, body.strip())
        dump = wd.last_dump()
        dump_text = ""
        if dump and os.path.exists(dump):
            with open(dump) as f:
                dump_text = f.read()
        result["dump_names_wedge"] = bool(
            "serving/chaos-wedge" in dump_text
            and "failpoints" in dump_text)
        for t in clients:
            t.join(timeout=30)
        # release the wedge: the worker resumes, progress beats end the
        # stall episode, liveness returns to 200
        chaos.release("serving/batcher/worker")
        x = np.ones((8,), np.float32)
        b.submit({"x": x}).result(10.0)
        deadline = time.time() + 10
        while wd.stalled_sections() and time.time() < deadline:
            b.submit({"x": x}).result(10.0)
            time.sleep(0.05)
        code2, body2 = healthz(port)
        result["healthz_after_release"] = (code2, body2.strip())
        lat_ms.sort()
        result["p99_ms"] = _percentile(lat_ms, 99)
        result["ok"] = bool(
            result["watchdog_fired"]
            and result["dump_names_wedge"]
            and code == 503 and "serving/chaos-wedge" in body
            and code2 == 200
            and not result["non_typed_failures"]
            and result["served"] > 0
            and result["p99_ms"] is not None
            and result["p99_ms"] < 1000.0)
    finally:
        chaos.reset()
        b.close(timeout=5.0)
        stop_exporter()
        os.environ.pop("MXNET_WATCHDOG_S", None)
        os.environ.pop("MXNET_WATCHDOG_DIR", None)
        shutil.rmtree(dump_dir, ignore_errors=True)
    return result


# ---------------------------------------------------------------------------
# scenario: replica killed mid-burst — the router drains it, siblings
# absorb, zero non-shed requests dropped (ISSUE 10)
# ---------------------------------------------------------------------------
def scenario_replica_kill_mid_burst(seconds=2.5, n_replicas=3,
                                    n_clients=8):
    """Chaos over the ReplicaPool router: injected dispatch faults must
    SPILL to siblings (``serving/router/dispatch`` raises, the rescued
    requests still answer), then one replica is killed mid-burst
    (``remove_replica`` = drain + drop, the kill path an autoscaler or
    an operator takes) — its admitted requests all complete, the
    surviving replicas absorb the load, p99 stays bounded, and not one
    non-shed request is dropped or left hanging."""
    import numpy as np

    from .. import telemetry
    from ..serving.batcher import (RequestTimeoutError,
                                   ServingOverloadError)
    from ..serving.metrics import ServingMetrics
    from ..serving.router import ReplicaPool

    def factory(rid):
        def run(feed, n_real):
            time.sleep(0.002)
            return [feed["x"] * 2.0]
        return run

    chaos.reset()
    # 12 injected dispatch faults, probabilistic so siblings rescue
    # (an arm firing on EVERY attempt would fail all K hops of one
    # request — that is the all-replicas-refused path, not spill)
    chaos.arm("serving/router/dispatch", "raise", prob=0.5, count=12)
    spill_counter = telemetry.REGISTRY.counter(
        "mxnet_serving_router_spill_total")
    spills0 = spill_counter.value(labels={"model": "chaos-pool"})

    pool = ReplicaPool(factory, num_replicas=n_replicas,
                       name="chaos-pool", model="chaos-pool",
                       metrics=ServingMetrics("chaos-pool"),
                       max_batch_size=8, max_latency_ms=2.0,
                       num_workers=1, max_queue_depth=64,
                       shed_watermark=32)
    result = {"ok": False, "non_typed_failures": [], "shed": 0,
              "served": 0, "injected_refusals": 0}
    lat_ms = []
    lock = threading.Lock()
    stop_t = time.perf_counter() + seconds
    try:
        def client():
            x = np.ones((8,), np.float32)
            while time.perf_counter() < stop_t:
                t0 = time.perf_counter()
                try:
                    pool.submit({"x": x}, timeout_ms=2000.0).result(10.0)
                    with lock:
                        lat_ms.append((time.perf_counter() - t0) * 1e3)
                        result["served"] += 1
                except ServingOverloadError:
                    with lock:
                        result["shed"] += 1
                    time.sleep(0.001)
                except chaos.ChaosInjectedError:
                    # every replica's dispatch took the injected fault:
                    # typed + retryable — the client retries, nothing
                    # is silently lost
                    with lock:
                        result["injected_refusals"] += 1
                except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                    with lock:
                        result["non_typed_failures"].append(
                            f"{type(e).__name__}: {e}")

        clients = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in clients:
            t.start()
        # mid-burst: kill replica 0 (drain-on-removal — the router
        # finishes everything it admitted, then drops it from routing)
        time.sleep(seconds / 2)
        victim_rid = pool.replica_ids()[0]
        victim = pool.remove_replica(victim_rid, drain=True)
        result["victim_drained"] = victim.occupancy() == 0
        result["survivors"] = pool.replica_ids()
        for t in clients:
            t.join(timeout=30)
        # every admitted request resolved: one more round trip proves
        # the survivors still serve
        x = np.ones((8,), np.float32)
        pool.submit({"x": x}).result(10.0)
        lat_ms.sort()
        result["p99_ms"] = _percentile(lat_ms, 99)
        result["spills"] = (spill_counter.value(
            labels={"model": "chaos-pool"}) - spills0)
        result["ok"] = bool(
            result["victim_drained"]
            and len(result["survivors"]) == n_replicas - 1
            and result["served"] > 0
            and result["spills"] >= 1
            and not result["non_typed_failures"]
            and result["p99_ms"] is not None
            and result["p99_ms"] < 1000.0)
    finally:
        chaos.reset()
        pool.close(timeout=5.0)
    return result


# ---------------------------------------------------------------------------
# scenario: replica death mid-generation (ISSUE 16)
# ---------------------------------------------------------------------------
def scenario_replica_kill_mid_generation(n_sessions=6, max_new=10):
    """Chaos over the stateful serving plane: two generation engines
    (the "replicas") stream concurrent sessions; an injected
    ``serving/generation/decode`` fault kills one engine's loop past
    its restart budget mid-generation.  Contract: every session on the
    victim fails TYPED-retryable (``ServingWorkerError``) — never
    hangs — and the client resumes it on the sibling engine with
    ``prompt + tokens-so-far`` as the new prompt (the sibling's prefix
    cache makes the resume cheap); sessions on the survivor stream to
    completion untouched.  Afterwards both engines' slot pools and the
    resource ledger's ``kv_pages``/``prefix_cache`` rows are PROVABLY
    zero — a dead replica leaks nothing."""
    import numpy as np

    from ..serving import generation
    from ..serving.batcher import (RequestTimeoutError, ServingClosedError,
                                   ServingOverloadError,
                                   ServingWorkerError)
    from ..telemetry.resources import LEDGER

    chaos.reset()
    engine_kw = dict(slots=4, page_tokens=8, kv_budget_mb=8,
                     prefix_cache_entries=8, max_len=96,
                     loop_restarts=0, session_timeout_s=30.0)
    # identical seeds: the sibling holds the same weights, so a greedy
    # resume continues the victim's stream deterministically
    eng_a = generation.GenerationEngine(
        generation.tiny_lm(vocab=24, d_model=8, max_len=96, seed=11),
        name="chaos-gen-a", **engine_kw)
    eng_b = generation.GenerationEngine(
        generation.tiny_lm(vocab=24, d_model=8, max_len=96, seed=11),
        name="chaos-gen-b", **engine_kw)
    eng_a.warm()
    eng_b.warm()
    # one engine dies: the site is shared, hits-triggered, count=1 —
    # whichever loop reaches the Nth decode dispatch first is the victim
    chaos.arm("serving/generation/decode", "raise", hits=4, count=1)

    result = {"ok": False, "completed": 0, "resumed": 0, "shed": 0,
              "hung": 0, "non_typed_failures": []}
    lock = threading.Lock()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 24, size=rng.randint(4, 12)).astype(np.int32)
               for _ in range(n_sessions)]
    engines = [eng_a, eng_b]

    def client(i):
        eng = engines[i % 2]
        sibling = engines[(i + 1) % 2]
        try:
            sess = eng.start_session(prompts[i], max_new_tokens=max_new,
                                     greedy=True)
        except (ServingOverloadError, ServingClosedError):
            with lock:
                result["shed"] += 1
            return
        try:
            sess.result(30.0)
            with lock:
                result["completed"] += 1
            return
        except ServingWorkerError:
            pass  # the replica died under this session: resume below
        except (ServingOverloadError, ServingClosedError):
            with lock:
                result["shed"] += 1
            return
        except RequestTimeoutError:
            with lock:
                result["hung"] += 1
            return
        except Exception as e:  # noqa: BLE001 — gate-fatal bucket
            with lock:
                result["non_typed_failures"].append(
                    f"{type(e).__name__}: {e}")
            return
        # typed-retryable death: resume on the sibling from where the
        # stream stopped
        done = list(sess.tokens)
        resume_prompt = np.concatenate(
            [prompts[i], np.asarray(done, np.int32)])
        try:
            rest = sibling.generate(resume_prompt,
                                    max_new_tokens=max_new - len(done)
                                    or 1, greedy=True)
            with lock:
                result["resumed"] += 1
                result["completed"] += bool(done + rest)
        except (ServingOverloadError, ServingClosedError,
                ServingWorkerError):
            with lock:
                result["shed"] += 1
        except Exception as e:  # noqa: BLE001 — gate-fatal bucket
            with lock:
                result["non_typed_failures"].append(
                    f"resume: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_sessions)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        result["hung"] += sum(t.is_alive() for t in threads)
        result["victim"] = ("chaos-gen-a" if eng_a.stats()["failed"]
                            else "chaos-gen-b" if eng_b.stats()["failed"]
                            else None)
    finally:
        chaos.reset()
        eng_a.close()
        eng_b.close()
    # zero-leak assertion: slots, pages and ledger rows all returned
    owners = LEDGER.snapshot()["owners"]
    leaks = {}
    for eng in engines:
        pool_stats = eng.pool.stats()
        row = owners.get(f"generation/{eng.name}", {})
        leaks[eng.name] = {
            "slots_in_use": pool_stats["slots_in_use"],
            "kv_bytes": pool_stats["kv_bytes"],
            "ledger_kv": row.get("kv_pages", 0),
            "ledger_prefix": row.get("prefix_cache", 0)}
    result["leaks"] = leaks
    result["zero_leak"] = all(
        not any(v.values()) for v in leaks.values())
    result["ok"] = bool(
        result["victim"] is not None
        and result["completed"] + result["shed"] == n_sessions
        and result["resumed"] >= 1
        and result["hung"] == 0
        and result["zero_leak"]
        and not result["non_typed_failures"])
    return result


# ---------------------------------------------------------------------------
# scenario 4: SIGKILL mid-scan-window, bit-identical resume
# ---------------------------------------------------------------------------
_SCAN_VICTIM = """
import os, sys
import numpy as np
import mxnet_tpu as mx
import mxnet_tpu.chaos  # arms the kill at window 3 from MXNET_CHAOS
from mxnet_tpu import io as mxio
from mxnet_tpu.checkpoint import CheckpointManager

ckdir = sys.argv[1]
K = int(os.environ["MXNET_SCAN_STEPS"])
mgr = CheckpointManager(ckdir, async_save=False, keep_last=0)
saved = set()

def boundary_save(param):
    mod = param.locals["self"]
    step = mod._optimizer.num_update
    if step % K == 0 and step not in saved:
        saved.add(step)
        mgr.save_module(mod, step, block=True)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chaos_scan_common as common
common.fit(boundary_save)
print("FINISHED", flush=True)  # must never print: the kill fires first
"""

_SCAN_COMMON = """
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import io as mxio

N, FEAT, BATCH = 256, 20, 16

def mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")

def init_params(seed=5):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, FEAT) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}

def dataset():
    rng = np.random.RandomState(3)
    x = rng.randn(N, FEAT).astype(np.float32)
    y = rng.randint(0, 10, N).astype(np.float32)
    return x, y

OPT = {"learning_rate": 0.05, "momentum": 0.9}

def fit(batch_end_callback=None, start_batch=0, module=None):
    mx.random.seed(0)
    x, y = dataset()
    x, y = x[start_batch * BATCH:], y[start_batch * BATCH:]
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y),
                          batch_size=BATCH, label_name="softmax_label")
    mod = module or mx.mod.Module(mlp(), context=mx.cpu())
    kwargs = {} if module is not None else {
        "arg_params": {k: v.copy() for k, v in init_params().items()}}
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=dict(OPT), eval_metric="acc",
            batch_end_callback=batch_end_callback, **kwargs)
    params, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in params.items()}
"""


def scenario_sigkill_mid_scan(workdir, scan_k=4, timeout=180.0):
    """A K-step scanned fit SIGKILLs itself (chaos ``kill``) before its
    third window dispatches; the parent restores the last boundary
    checkpoint and continues the fit — the final weights must be
    BIT-IDENTICAL to an uninterrupted run."""
    import numpy as np

    from ..checkpoint import CheckpointManager, latest_step

    workdir = str(workdir)
    os.makedirs(workdir, exist_ok=True)
    with open(os.path.join(workdir, "chaos_scan_common.py"), "w") as f:  # graftlint: disable=torn-write -- ephemeral scenario script, single consumer
        f.write(_SCAN_COMMON)
    victim = os.path.join(workdir, "scan_victim.py")
    with open(victim, "w") as f:  # graftlint: disable=torn-write -- ephemeral scenario script, single consumer
        f.write(_SCAN_VICTIM)
    ckdir = os.path.join(workdir, "ckpt")

    result = {"ok": False}
    # windows 1 and 2 run (boundaries K and 2K committed); the kill arm
    # fires as window 3 is about to stage — "mid-window" by construction
    proc = subprocess.Popen(
        [sys.executable, victim, ckdir],
        env=_child_env(MXNET_SCAN_STEPS=scan_k, MXNET_FUSED_STEP=1,
                       MXNET_CHAOS="train/scan_window=kill:hits=3"),
        stdout=subprocess.PIPE, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
    result["victim_exit"] = proc.returncode
    result["victim_finished"] = "FINISHED" in (out or "")
    resume_step = latest_step(ckdir)
    result["resume_step"] = resume_step
    if resume_step != 2 * scan_k or result["victim_finished"]:
        return result

    # run the scenario's fit shapes in-process: the uninterrupted
    # reference, then the boundary-restore continuation
    sys.path.insert(0, workdir)
    try:
        import importlib

        import chaos_scan_common as common
        importlib.reload(common)
        os.environ["MXNET_SCAN_STEPS"] = str(scan_k)
        os.environ["MXNET_FUSED_STEP"] = "1"
        try:
            _ref_mod, ref_params = common.fit()

            mgr = CheckpointManager(ckdir, async_save=False, keep_last=0)
            mod, _ckpt = mgr.restore_module(resume_step)
            mgr.close()
            _mod, resumed = common.fit(start_batch=resume_step,
                                       module=mod)
        finally:
            os.environ.pop("MXNET_SCAN_STEPS", None)
            os.environ.pop("MXNET_FUSED_STEP", None)
    finally:
        sys.path.remove(workdir)
    diverged = [k for k in ref_params
                if not np.array_equal(ref_params[k], resumed[k])]
    result["diverged_params"] = diverged
    result["ok"] = bool(result["victim_exit"] == -9 and not diverged)
    return result


# ---------------------------------------------------------------------------
# scenario 5: mesh collective stall + kill, restore onto a RESIZED mesh

_MESH_COMMON = """
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import io as mxio
from mxnet_tpu.parallel.mesh import make_mesh

N, FEAT, BATCH = 128, 20, 16

def mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")

def init_params(seed=5):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, FEAT) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}

def dataset():
    rng = np.random.RandomState(3)
    x = rng.randn(N, FEAT).astype(np.float32)
    y = rng.randint(0, 10, N).astype(np.float32)
    return x, y

OPT = {"learning_rate": 0.05, "momentum": 0.9}

def fit(dp, batch_end_callback=None, start_batch=0, end_batch=None,
        module=None):
    mx.random.seed(0)
    x, y = dataset()
    stop = None if end_batch is None else end_batch * BATCH
    x, y = x[start_batch * BATCH:stop], y[start_batch * BATCH:stop]
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y),
                          batch_size=BATCH, label_name="softmax_label")
    mod = module or mx.mod.Module(mlp(), context=mx.cpu())
    kwargs = {} if module is not None else {
        "arg_params": {k: v.copy() for k, v in init_params().items()}}
    with make_mesh(dp=dp):
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params=dict(OPT), eval_metric="acc",
                kvstore="dist_device_sync",
                batch_end_callback=batch_end_callback, **kwargs)
    assert mod._mesh is not None, "mesh fused path did not engage"
    params, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in params.items()}
"""

_MESH_WEDGE = """
import json, os, sys
import mxnet_tpu as mx
import mxnet_tpu.chaos  # arms the wedge from MXNET_CHAOS
from mxnet_tpu.telemetry import watchdog

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chaos_mesh_common as common
common.fit(2)  # wedge releases via timeout -> scan path self-heals
dump = watchdog.last_dump()
txt = ""
if dump and os.path.exists(dump):
    with open(dump) as f:
        txt = f.read()
print("RESULT " + json.dumps({
    "fires": watchdog.fires(),
    "names_fit_section": "train/fit" in txt,
    "names_collective_frame": "parallel/collective" in txt
                              or "failpoints" in txt,
}), flush=True)
"""

_MESH_VICTIM = """
import os, sys
import mxnet_tpu as mx
import mxnet_tpu.chaos  # arms the kill at window 3 from MXNET_CHAOS
from mxnet_tpu.checkpoint import CheckpointManager

ckdir = sys.argv[1]
K = int(os.environ["MXNET_SCAN_STEPS"])
mgr = CheckpointManager(ckdir, async_save=False, keep_last=0)
saved = set()

def boundary_save(param):
    mod = param.locals["self"]
    step = mod._optimizer.num_update
    if step % K == 0 and step not in saved:
        saved.add(step)
        mgr.save_module(mod, step, block=True)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chaos_mesh_common as common
common.fit(4, boundary_save)
print("FINISHED", flush=True)  # must never print: the kill fires first
"""

_MESH_REF = """
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.checkpoint import CheckpointManager

ckdir, out = sys.argv[1], sys.argv[2]
K = int(os.environ["MXNET_SCAN_STEPS"])
S = 2 * K  # the boundary the victim dies after

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chaos_mesh_common as common
mgr = CheckpointManager(ckdir, async_save=False, keep_last=0)
saved = set()

def boundary_save(param):
    mod = param.locals["self"]
    step = mod._optimizer.num_update
    if step % K == 0 and step not in saved:
        saved.add(step)
        mgr.save_module(mod, step, block=True)

# the no-fault reference: dp=4 to the boundary, then a planned
# restore-resize onto dp=2 for the rest — the exact trajectory the
# faulted run must reproduce
common.fit(4, boundary_save, end_batch=S)
mod, _ckpt = mgr.restore_module(S)
mgr.close()
_m, params = common.fit(2, start_batch=S, module=mod)
np.savez(out, **params)
"""

_MESH_RESUME = """
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.checkpoint import CheckpointManager

ckdir, out = sys.argv[1], sys.argv[2]
K = int(os.environ["MXNET_SCAN_STEPS"])
S = 2 * K

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chaos_mesh_common as common
mgr = CheckpointManager(ckdir, async_save=False, keep_last=0)
mod, _ckpt = mgr.restore_module(S)
mgr.close()
_m, params = common.fit(2, start_batch=S, module=mod)
np.savez(out, **params)
"""


def scenario_mesh_collective_stall(workdir, scan_k=2, timeout=240.0):
    """The mesh fused step under composed faults, two phases:

    1. **stall**: the ``parallel/collective`` failpoint wedges the
       window boundary of a dp=2 mesh fit; the hang watchdog must fire
       naming the stalled mesh step (``train/fit`` section + the wedged
       failpoint frame in the dump), the wedge timeout must turn the
       stall into a typed error, and the fit must SELF-HEAL by falling
       back to per-batch steps and completing.
    2. **kill + resize**: a dp=4 mesh fit SIGKILLs itself (chaos
       ``kill``) before its third window; a fresh process restores the
       last boundary checkpoint onto a RESIZED dp=2 mesh and continues —
       bit-identical to a no-fault run that performed the same planned
       dp=4 → dp=2 restore-resize at that boundary (PR 2's elastic
       restore as the resize mechanism).
    """
    import numpy as np

    from ..checkpoint import latest_step

    workdir = str(workdir)
    os.makedirs(workdir, exist_ok=True)
    for fname, src in (("chaos_mesh_common.py", _MESH_COMMON),
                       ("mesh_wedge.py", _MESH_WEDGE),
                       ("mesh_victim.py", _MESH_VICTIM),
                       ("mesh_ref.py", _MESH_REF),
                       ("mesh_resume.py", _MESH_RESUME)):
        with open(os.path.join(workdir, fname), "w") as f:  # graftlint: disable=torn-write -- ephemeral scenario scripts, single consumer
            f.write(src)
    mesh_env = dict(
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        MXNET_SCAN_STEPS=scan_k, MXNET_MESH_FUSED_STEP=1)
    result = {"ok": False}

    # phase 1: wedge the window boundary; watchdog names it, the fit
    # self-heals through the wedge-timeout error
    proc = subprocess.Popen(
        [sys.executable, os.path.join(workdir, "mesh_wedge.py")],
        env=_child_env(MXNET_CHAOS="parallel/collective=wedge:hits=2",
                       MXNET_CHAOS_WEDGE_TIMEOUT_S=1.5,
                       MXNET_WATCHDOG_S=0.3, MXNET_WATCHDOG_DIR=workdir,
                       **mesh_env),
        stdout=subprocess.PIPE, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
    result["wedge_exit"] = proc.returncode
    payload = {}
    for line in (out or "").splitlines():
        if line.startswith("RESULT "):
            payload = json.loads(line[len("RESULT "):])
    result["wedge"] = payload
    wedge_ok = (proc.returncode == 0 and payload.get("fires", 0) >= 1
                and payload.get("names_fit_section")
                and payload.get("names_collective_frame"))
    result["wedge_ok"] = bool(wedge_ok)

    # phase 2: kill before window 3, restore onto a resized mesh
    ckdir = os.path.join(workdir, "ckpt")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(workdir, "mesh_victim.py"), ckdir],
        env=_child_env(MXNET_CHAOS="parallel/collective=kill:hits=3",
                       **mesh_env),
        stdout=subprocess.PIPE, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
    result["victim_exit"] = proc.returncode
    result["victim_finished"] = "FINISHED" in (out or "")
    resume_step = latest_step(ckdir)
    result["resume_step"] = resume_step
    if resume_step != 2 * scan_k or result["victim_finished"]:
        return result

    def run_child(script, *args):
        proc = subprocess.run(
            [sys.executable, os.path.join(workdir, script)] + list(args),
            env=_child_env(**mesh_env), capture_output=True, text=True,
            timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(f"{script} failed: "
                               f"{proc.stderr.strip()[-500:]}")

    ref_out = os.path.join(workdir, "ref.npz")
    res_out = os.path.join(workdir, "resumed.npz")
    run_child("mesh_ref.py", ckdir + "-ref", ref_out)
    run_child("mesh_resume.py", ckdir, res_out)
    ref = dict(np.load(ref_out))
    resumed = dict(np.load(res_out))
    diverged = [k for k in ref
                if not np.array_equal(ref[k], resumed[k])]
    result["diverged_params"] = diverged
    result["ok"] = bool(wedge_ok and result["victim_exit"] == -9
                        and not diverged)
    return result


# ---------------------------------------------------------------------------
# scenario 7: multi-host peer loss mid-window — survivors checkpoint,
# the elastic launcher respawns the dp/2 survivor mesh, the continued
# fit is bitwise identical to a planned resize (ISSUE 11)
# ---------------------------------------------------------------------------
def scenario_peer_loss_mid_window(workdir, scan_k=2, timeout=240.0):
    """Kill host 1 of a 2-process × 4-fake-device jax.distributed mesh
    at its window-3 boundary (chaos ``kill`` at ``multihost/peer_loss``)
    and assert the whole elastic contract:

    * the survivor takes a **typed** exit (PeerLostError → the
      ELASTIC_RESTART code) from the deadline-bounded rendezvous — no
      straggler kill, no hang, no untyped crash;
    * the boundary checkpoint commits and the launcher respawns the
      dp/2 survivor world, which finishes training;
    * the final weights are BITWISE identical to a planned resize (the
      same host *leaving* via the preemption path at the same
      boundary);
    * recovery wall time was measured (the launcher's clock ran);
    * the fault generation left ONE postmortem bundle whose merged
      event rings name the injected site (``multihost/peer_loss``) as
      the FIRST anomalous event, and whose fleet snapshot tags the
      killed rank ``lost`` (ISSUE 12).
    """
    import json as _json

    import numpy as np

    from ..parallel import elastic as E

    workdir = str(workdir)
    os.makedirs(workdir, exist_ok=True)
    K, NB, BS = scan_k, 4 * scan_k, 32
    result = {"ok": False}

    sa, pa, la = E._launch(
        os.path.join(workdir, "faulted"), 2, NB, BS, K,
        rank_env={1: {"MXNET_CHAOS": "multihost/peer_loss=kill:hits=3"}})
    result["postmortems"] = list(la.postmortems)
    result["postmortem_rings"] = 0
    result["first_anomaly_site"] = None
    result["fleet_lost_tagged"] = False
    if la.postmortems:
        with open(la.postmortems[0], encoding="utf-8") as f:
            bundle = _json.load(f)
        result["postmortem_rings"] = len(bundle.get("rings", {}))
        anomaly = bundle.get("first_anomaly") or {}
        result["first_anomaly_site"] = \
            (anomaly.get("fields") or {}).get("site")
        result["fleet_lost_tagged"] = (
            bundle.get("fleet", {}).get("ranks", {})
            .get("1", {}).get("state") == "lost")
    sb, pb, _lb = E._launch(
        os.path.join(workdir, "planned"), 2, NB, BS, K,
        leave_at=2 * K)
    result["faulted"] = {k: v for k, v in sa.items()}
    result["planned_ok"] = bool(sb.get("ok"))
    gen0 = sa["history"][0]["exits"]
    result["gen0_exits"] = gen0
    result["typed_only"] = sorted(gen0) == [-9, E.ELASTIC_RESTART]
    result["survivor_world"] = sa["history"][-1]["world"]
    result["recovery_s"] = (sa.get("recovery_s") or [None])[0]
    try:
        p_fault = E._final_params(pa)
        p_plan = E._final_params(pb)
        diverged = [k for k in p_plan
                    if not np.array_equal(p_fault[k], p_plan[k])]
    except Exception as e:  # noqa: BLE001 — gate-fatal bucket
        result["error"] = f"{type(e).__name__}: {e}"
        return result
    result["diverged_params"] = diverged
    result["ok"] = bool(
        sa.get("ok") and sb.get("ok")
        and result["typed_only"]
        and sa.get("restarts") == 1
        and result["survivor_world"] == 1
        and result["recovery_s"] is not None
        and not diverged
        and result["postmortem_rings"] >= 2
        and result["first_anomaly_site"] == "multihost/peer_loss"
        and result["fleet_lost_tagged"])
    return result


# ---------------------------------------------------------------------------
# scenario: reader death mid-epoch — the streaming data plane rebalances,
# the epoch completes exactly-once, all-dead is a typed error (ISSUE 19)
# ---------------------------------------------------------------------------
def scenario_reader_death_mid_epoch(workers=4, shards=16,
                                    batches_per_shard=4, kill_at=13):
    """Chaos over the streaming data plane (``io_pipeline``):

    1. one of ``workers`` reader workers dies at its ``kill_at``-th
       ``io/reader/read`` — the pipeline requeues the victim's shards
       onto the survivors, the epoch completes with every sample row
       delivered exactly once IN THE SAME seeded order as the serial
       baseline, the rebalance counter ticks, and no single ``next()``
       stalls;
    2. a slow reader (delay arm) is absorbed the same way — order
       unchanged, nothing dropped;
    3. every reader dying raises a typed :class:`DataReaderError` on
       the train thread — never a hang (asserted via a joined helper
       thread, not hope).
    """
    import numpy as np

    from .. import io_pipeline as pipe
    from .. import telemetry

    batch_size = 8
    n_rows = shards * batches_per_shard * batch_size
    data = np.arange(n_rows * 3, dtype=np.float32).reshape(n_rows, 3)
    label = np.arange(n_rows, dtype=np.float32)

    def make_pipe(n_workers):
        src = pipe.NDArraySource(data, label, batch_size=batch_size,
                                 batches_per_shard=batches_per_shard)
        return pipe.DataPipeline(src, workers=n_workers, seed=7)

    def drain(p, stall_box=None):
        """One full epoch; returns the concatenated row-index sequence."""
        idx = []
        while True:
            t0 = time.perf_counter()
            try:
                batch = p.next()
            except StopIteration:
                break
            if stall_box is not None:
                stall_box[0] = max(stall_box[0],
                                   time.perf_counter() - t0)
            idx.append(np.asarray(batch.index))
        return np.concatenate(idx) if idx else np.empty((0,), np.int64)

    result = {"ok": False, "non_typed_failures": [], "rebalances": 0,
              "max_next_stall_s": 0.0}
    reb0 = telemetry._DATA_REBALANCE.value()
    chaos.reset()
    p_base = p_kill = p_slow = p_dead = None
    try:
        # serial baseline: the seeded shard order, workers=0
        p_base = make_pipe(0)
        baseline = drain(p_base)
        result["batches"] = len(baseline) // batch_size
        if sorted(baseline.tolist()) != list(range(n_rows)):
            result["non_typed_failures"].append(
                "baseline is not a permutation of the dataset")

        # pass 1: kill one reader mid-epoch
        chaos.arm("io/reader/read", "raise", hits=kill_at, count=1)
        p_kill = make_pipe(workers)
        stall = [0.0]
        try:
            seq = drain(p_kill, stall)
        except pipe.DataReaderError as e:
            result["non_typed_failures"].append(
                f"one dead reader must rebalance, not raise: {e}")
            seq = np.empty((0,), np.int64)
        result["max_next_stall_s"] = round(stall[0], 3)
        result["exactly_once"] = bool(np.array_equal(seq, baseline))
        result["rebalances"] = telemetry._DATA_REBALANCE.value() - reb0
        chaos.reset()

        # pass 2: a slow reader is absorbed, order unchanged
        chaos.arm("io/reader/read", "delay", value=0.01, hits=3, count=6)
        p_slow = make_pipe(workers)
        slow_seq = drain(p_slow)
        result["slow_reader_order_ok"] = bool(
            np.array_equal(slow_seq, baseline))
        chaos.reset()

        # pass 3: ALL readers dead -> typed DataReaderError, no hang
        chaos.arm("io/reader/read", "raise", hits=1)
        p_dead = make_pipe(workers)
        box = {"raised": None}

        def all_dead():
            try:
                drain(p_dead)
                box["raised"] = "completed-without-error"
            except pipe.DataReaderError:
                box["raised"] = "typed"
            except Exception as e:  # noqa: BLE001 — gate-fatal bucket
                box["raised"] = f"{type(e).__name__}: {e}"

        t = threading.Thread(target=all_dead, name="chaos-all-dead")
        t.start()
        t.join(timeout=30)
        result["all_dead_hung"] = t.is_alive()
        result["all_dead_outcome"] = box["raised"]
        if box["raised"] not in (None, "typed"):
            result["non_typed_failures"].append(
                f"all-dead pass: {box['raised']}")

        result["ok"] = bool(
            result["exactly_once"]
            and result["rebalances"] >= 1
            and result["max_next_stall_s"] < 10.0
            and result["slow_reader_order_ok"]
            and not result["all_dead_hung"]
            and result["all_dead_outcome"] == "typed"
            and not result["non_typed_failures"])
    finally:
        chaos.reset()
        for p in (p_base, p_kill, p_slow, p_dead):
            if p is not None:
                p.close()
    return result


def scenario_rollup_under_churn(ranks=64, cycles=24):
    """[fleet/push] The fleet telemetry plane under membership churn on
    a lossy push path (ISSUE 20): ``ranks`` in-process synthetic
    reporters drive ONE real leader (KVServer + FleetStore + summary
    rollup, virtual clock) while 10% of pushes are chaos-dropped at the
    ``fleet/push`` site, 8 ranks die mid-run and 8 join late.

    Gates: the leader loop takes ZERO exceptions (a dropped delta must
    resolve via resync, never a merge error); the rollup stays bounded
    (a scrape never blocks the push path); every dead rank is tagged
    lost/stale in the summary within the peer timeout; the dropped
    pushes are actually counted (the arm fired, not a no-op run)."""
    from ..telemetry import fleet_sim

    result = {"ok": False, "ranks": ranks, "cycles": cycles}
    # dying/joining ranks live at the top of the rank space so the
    # simulator's scripted anomaly ranks (low) stay out of the churn
    churn = {"die": list(range(ranks - 16, ranks - 8)),
             "die_at": cycles // 2,
             "join": list(range(ranks - 8, ranks)),
             "join_at": cycles // 4}
    chaos.arm("fleet/push", "raise", prob=0.1, count=None)
    try:
        r = fleet_sim.run_sim(ranks=ranks, cycles=cycles,
                              interval_s=5.0, seed=7, delta=True,
                              churn=churn, alloc_window=0)
    finally:
        chaos.reset()
    peers = r["final_summary"]["peers"] or {}
    anomalous = set(r["final_summary"]["anomalous"] or ())
    dead_tagged = all(str(rank) in anomalous for rank in churn["die"])
    result.update({
        "leader_exceptions": r["leader_exceptions"],
        "dropped_pushes": r["merge"]["dropped"],
        "resyncs": r["merge"]["resync"],
        "merge_p99_ms": round(r["merge"]["p99_ms"], 3),
        "rollup_max_ms": round(r["rollup"]["max_ms"], 2),
        "peers": peers,
        "dead_ranks_tagged": dead_tagged,
        "silent_rank_state": r["alerts"]["silent_rank_state"],
    })
    result["ok"] = bool(
        not r["leader_exceptions"]
        and r["merge"]["dropped"] > 0
        and dead_tagged
        and r["alerts"]["silent_rank_state"] in ("lost", "stale")
        and peers.get("alive", 0) >= ranks - 16 - 1
        and r["rollup"]["max_ms"] < 250.0)
    return result


def run_all(workdir=None, verbose=True):
    """Run the composed scenarios sequentially; returns
    {name: result dict}.  The smoke asserts every ``ok``."""
    base = workdir or tempfile.mkdtemp(prefix="mx-chaos-")
    results = {}
    scenarios = [
        ("worker_kill_revive",
         lambda: scenario_worker_kill_revive(os.path.join(base, "s1"))),
        ("corrupt_reload_under_load",
         lambda: scenario_corrupt_reload_under_load(
             os.path.join(base, "s2"))),
        ("wedged_batcher", scenario_wedged_batcher),
        ("replica_kill_mid_burst", scenario_replica_kill_mid_burst),
        ("replica_kill_mid_generation",
         scenario_replica_kill_mid_generation),
        ("reader_death_mid_epoch", scenario_reader_death_mid_epoch),
        ("sigkill_mid_scan",
         lambda: scenario_sigkill_mid_scan(os.path.join(base, "s4"))),
        ("mesh_collective_stall",
         lambda: scenario_mesh_collective_stall(os.path.join(base, "s5"))),
        ("peer_loss_mid_window",
         lambda: scenario_peer_loss_mid_window(os.path.join(base, "s7"))),
        ("rollup_under_churn", scenario_rollup_under_churn),
    ]
    for name, fn in scenarios:
        t0 = time.perf_counter()
        chaos.reset()
        try:
            results[name] = fn()
        finally:
            chaos.reset()
        results[name]["elapsed_s"] = round(time.perf_counter() - t0, 1)
        if verbose:
            print(f"[chaos] {name}: "
                  f"{'OK' if results[name].get('ok') else 'FAIL'} "
                  f"({results[name]['elapsed_s']}s)", flush=True)
    return results
