"""Serving smoke: concurrent burst + autoscaling hot-swap under load.

CI entry point (``python -m mxnet_tpu.serving.smoke``), two phases:

1. **burst contract** — spin up a ModelServer (2-replica pools) on the
   virtual 8-device CPU mesh, fire 64 concurrent requests through a
   deliberately small queue so SOME of them shed, and assert the
   robustness contract: every request is either answered with a
   numerically correct output or fails fast with a structured
   MXNetError — nothing hangs, nothing crashes the server.
2. **autoscaling hot-swap** (ISSUE 10) — ``ModelRepository.watch`` a
   checkpoint directory while sustained client load runs against the
   replica pool; commit a new step mid-traffic and assert the swap is
   invisible: ZERO dropped non-shed requests, the new version serves,
   and ZERO executor-cache misses after the flip (the warm hooks
   compiled the new version's full bucket ladder BEFORE the pointer
   moved — composing ISSUE 7's warm-before-flip with the pool).
3. **output-health guard** (ISSUE 14) — a model producing NaN logits
   fails those requests with typed ``NonFiniteError`` (never served),
   bumps ``mxnet_numerics_serving_nonfinite_total``, and the pool's
   survivors keep answering healthy requests.
4. **generation hot reload** (ISSUE 16) — ``server.load_generator`` a
   tiny LM, AOT-warm the decode step + prefill ladder, stream N
   concurrent sessions (more than the slot pool holds, so some shed
   typed), hot-reload a new model version MID-STREAM, and assert:
   zero non-shed drops, ZERO decode-step compiles after the flip
   returns (warm-before-flip), and the KV slot pool + resource-ledger
   page accounting back at exactly zero afterwards.

Prints one JSON summary line; exit code 0 iff all contracts held.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# hermetic compile-cache namespace: the smoke's warm/flip accounting
# must not depend on what earlier local runs persisted, nor on a
# directory placed from outside
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ.setdefault("MXNET_COMPILE_CACHE_DIR",
                      tempfile.mkdtemp(prefix="mx-serve-smoke-cache-"))

N_CLIENTS = 64
IN_DIM = 16


def output_health_guard():
    """Phase 3: non-finite logits fail typed, never serve, pool
    survives.  Returns (summary dict, failure list)."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.base import NonFiniteError
    from mxnet_tpu.telemetry import numerics

    failures = []
    # log(x): positive inputs are healthy, negative inputs produce NaN
    sym = mx.sym.log(mx.sym.Variable("data"))
    server = serving.ModelServer(max_batch_size=4, max_latency_ms=2.0,
                                 num_replicas=2, name="nf-smoke")
    server.load("m", symbol=sym, params={})
    nf0 = numerics.summary()  # noqa: F841 — arm check only
    healthy = server.predict("m", {"data": np.ones(IN_DIM, np.float32)})
    if not np.allclose(np.asarray(healthy[0]), 0.0):
        failures.append("guard smoke: healthy request served wrong")
    typed = 0
    try:
        server.predict("m", {"data": -np.ones(IN_DIM, np.float32)})
        failures.append("guard smoke: NaN output was SERVED")
    except NonFiniteError:
        typed = 1
    except Exception as e:  # noqa: BLE001 — wrong error type = failure
        failures.append(f"guard smoke: wrong error type "
                        f"{type(e).__name__}: {e}")
    # survivors keep serving after the guard fired
    try:
        again = server.predict(
            "m", {"data": 2 * np.ones(IN_DIM, np.float32)})
        if not np.allclose(np.asarray(again[0]), np.log(2.0)):
            failures.append("guard smoke: post-guard answer wrong")
    except Exception as e:  # noqa: BLE001 — survivors must serve
        failures.append(f"guard smoke: pool stopped serving after the "
                        f"guard fired: {type(e).__name__}: {e}")
    counter = 0
    from mxnet_tpu.telemetry import REGISTRY
    fam = REGISTRY.get("mxnet_numerics_serving_nonfinite_total")
    if fam is not None:
        counter = sum(s[2] for s in fam._samples())
    if counter < 1:
        failures.append("guard smoke: serving_nonfinite counter did "
                        "not bump")
    server.shutdown()
    return {"typed_failures": typed,
            "serving_nonfinite_total": counter}, failures


def autoscaling_hot_swap():
    """Phase 2: ModelRepository.watch hot-swaps a committed step under
    sustained replica-pool load — zero dropped non-shed requests, zero
    post-flip cold compiles.  Returns (summary dict, failure list)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, serving
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.serving import (RequestTimeoutError, ServingClosedError,
                                   ServingOverloadError)

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(24, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(mx.nd.zeros((1, IN_DIM)))
    if not getattr(net, "_cached_graph", None):
        net._build_sym_graph()
    sym = net._cached_graph[1]
    params = {f"arg:{k}": p._reduce()
              for k, p in net.collect_params().items()}
    x = np.random.RandomState(1).randn(IN_DIM).astype(np.float32)

    ckdir = tempfile.mkdtemp(prefix="mx-serve-smoke-ck-")
    failures = []
    served = [0]
    sheds = [0]
    stop = threading.Event()
    server = serving.ModelServer(max_batch_size=8, max_latency_ms=3.0,
                                 max_queue_depth=64, num_replicas=2,
                                 name="smoke-swap")
    repo = server.repository
    with CheckpointManager(ckdir, keep_last=0) as mgr:
        mgr.save(1, arrays=params, symbol=sym, block=True)
        assert repo.poll_checkpoint("swapm", ckdir) == 1

        def client():
            while not stop.is_set():
                try:
                    server.predict("swapm", {"data": x}, wait_s=30.0)
                    served[0] += 1
                except (ServingOverloadError, RequestTimeoutError,
                        ServingClosedError):
                    sheds[0] += 1
                except Exception as e:  # noqa: BLE001 — contract violation
                    failures.append(f"{type(e).__name__}: {e}")
                    return

        clients = [threading.Thread(target=client) for _ in range(4)]
        for t in clients:
            t.start()
        try:
            time.sleep(0.5)   # v1 traffic feeds the shape census
            repo.watch("swapm", ckdir, interval=0.05)
            mgr.save(2, arrays=params, symbol=sym, block=True)
            deadline = time.time() + 30
            while repo.latest_version("swapm") != 2:
                if time.time() > deadline:
                    failures.append("watcher never flipped to step 2")
                    break
                time.sleep(0.02)
            # the flip is live: warmup compiled the v2 ladder pre-flip,
            # so continued load must be a pure executor-cache hit
            misses_at_flip = server._cache.stats()["misses"]
            served_at_flip = served[0]
            time.sleep(0.5)
        finally:
            repo.unwatch("swapm")
            stop.set()
            for t in clients:
                t.join(timeout=30)
        post_flip_misses = (server._cache.stats()["misses"]
                            - misses_at_flip)
        served_post_flip = served[0] - served_at_flip
        server.shutdown()
    if post_flip_misses:
        failures.append(
            f"{post_flip_misses} executor-cache miss(es) AFTER the "
            "version flip — a request paid a cold compile")
    if served_post_flip <= 0:
        failures.append("no traffic completed after the hot swap")
    if served[0] <= 0:
        failures.append("no traffic completed at all during the swap")
    summary = {
        "served": served[0], "shed": sheds[0],
        "served_post_flip": served_post_flip,
        "post_flip_misses": post_flip_misses,
        "final_version": repo.latest_version("swapm"),
        "pool": server.stats()["pools"].get("swapm"),
    }
    return summary, failures


def generation_hot_reload():
    """Phase 4: stateful generation sessions across a mid-stream hot
    reload — zero non-shed drops, zero post-flip decode compiles, KV
    ledger provably zero after.  Returns (summary dict, failure list)."""
    from mxnet_tpu import serving
    from mxnet_tpu.serving import (RequestTimeoutError, ServingClosedError,
                                   ServingOverloadError)
    from mxnet_tpu.serving.generation import tiny_lm
    from mxnet_tpu.telemetry.resources import LEDGER

    failures = []
    server = serving.ModelServer(num_replicas=1, name="gen-smoke")
    server.load_generator("lm", tiny_lm(vocab=32, d_model=8, max_len=128,
                                        seed=5),
                          warm=True, slots=8, page_tokens=16,
                          kv_budget_mb=8, prefix_cache_entries=8,
                          max_len=128)
    eng = server.generator("lm")
    rng = np.random.RandomState(0)
    shared = rng.randint(1, 31, size=24).astype(np.int32)  # prefix-reuse head
    completed = [0]
    shed = [0]
    stop = threading.Event()

    def client(i):
        r = np.random.RandomState(100 + i)
        sheds_in_a_row = 0
        while not stop.is_set():
            tail = r.randint(1, 31, size=r.randint(2, 8)).astype(np.int32)
            prompt = np.concatenate([shared, tail]) if i % 2 else tail
            try:
                toks = server.generate("lm", prompt, timeout=30.0,
                                       max_new_tokens=8)
                if len(toks) != 8:
                    failures.append(f"gen client {i}: {len(toks)} tokens")
                completed[0] += 1
                sheds_in_a_row = 0
            except (ServingOverloadError, RequestTimeoutError,
                    ServingClosedError):
                shed[0] += 1   # typed admission shed: the contract allows it
                sheds_in_a_row += 1
                if sheds_in_a_row > 400:   # persistently full: give up
                    return
                time.sleep(0.005 * 2 ** min(sheds_in_a_row, 4)
                           * (1.0 + 0.25 * r.rand()))
            except Exception as e:  # noqa: BLE001 — contract violation
                failures.append(f"gen client {i}: {type(e).__name__}: {e}")
                return

    clients = [threading.Thread(target=client, args=(i,)) for i in range(12)]
    for t in clients:
        t.start()
    try:
        time.sleep(0.6)   # v1 streams
        flip_version = server.load_generator(
            "lm", tiny_lm(vocab=32, d_model=8, max_len=128, seed=6))
        compiles_at_flip = eng.stats()["decode_compiles"]
        time.sleep(0.6)   # v2 streams, in-flight v1 sessions finish on it
    finally:
        stop.set()
        for t in clients:
            t.join(timeout=30)
    post_flip_compiles = eng.stats()["decode_compiles"] - compiles_at_flip
    stats = eng.stats()
    server.shutdown()
    if post_flip_compiles:
        failures.append(f"{post_flip_compiles} decode-step compile(s) "
                        "AFTER the generation hot reload — a session "
                        "paid a cold compile mid-stream")
    if completed[0] <= 0:
        failures.append("no generation session completed at all")
    if stats["version"] != flip_version:
        failures.append(f"engine never flipped to v{flip_version}")
    kv = stats["kv"]
    ledger_kv = LEDGER.snapshot()["owners"].get(
        f"generation/{eng.name}", {}).get("kv_pages", 0)
    if kv["slots_in_use"] or kv["kv_bytes"] or ledger_kv:
        failures.append(f"generation leaked KV state after shutdown: "
                        f"{kv['slots_in_use']} slots, {kv['kv_bytes']} "
                        f"bytes, ledger={ledger_kv} pages")
    return {"completed": completed[0], "shed": shed[0],
            "flipped_to": stats["version"],
            "post_flip_decode_compiles": post_flip_compiles,
            "max_active": stats["max_active"],
            "prefix_cache": stats["prefix_cache"],
            "kv": kv}, failures


def main():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu import serving
    from mxnet_tpu.serving import (RequestTimeoutError, ServingClosedError,
                                   ServingOverloadError)

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    xs = np.random.RandomState(0).randn(N_CLIENTS, IN_DIM).astype(np.float32)
    ref = net(mx.nd.array(xs)).asnumpy()

    server = serving.ModelServer(max_batch_size=8, max_latency_ms=4.0,
                                 max_queue_depth=16, num_replicas=2,
                                 name="smoke")
    server.load("mlp", block=net)
    # prime the hot bucket so concurrent clients race a warm server, not
    # one giant first-call XLA compile
    server.predict("mlp", {"data": xs[0]})

    results = [None] * N_CLIENTS  # ("ok", out) | ("shed", e) | ("bad", why)
    barrier = threading.Barrier(N_CLIENTS)

    def client(i):
        barrier.wait(timeout=60)  # a stuck sibling breaks the barrier typed
        try:
            out = server.predict("mlp", {"data": xs[i]}, wait_s=60.0)
            results[i] = ("ok", out[0])
        except (ServingOverloadError, RequestTimeoutError,
                ServingClosedError) as e:
            # the ONLY acceptable failures under the contract: a
            # structured shed/timeout/shutdown.  Any other MXNetError —
            # notably ServeFuture.result's no-response timeout, i.e. a
            # wedged server — is a contract violation, not a shed.
            results[i] = ("shed", e)
        except Exception as e:  # noqa: BLE001 — contract violation
            results[i] = ("bad", f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)

    ok = shed = 0
    failures = []
    for i, r in enumerate(results):
        if r is None:
            failures.append(f"client {i}: hung (no result)")
        elif r[0] == "ok":
            if not np.allclose(r[1], ref[i], atol=1e-5):
                failures.append(f"client {i}: wrong answer")
            else:
                ok += 1
        elif r[0] == "shed":
            shed += 1
        else:
            failures.append(f"client {i}: unstructured failure: {r[1]}")

    server.shutdown()
    snap = server.stats()
    if ok == 0:
        failures.append("no request was answered at all")

    # phase 2: autoscaling hot-swap under sustained load
    try:
        swap_summary, swap_failures = autoscaling_hot_swap()
    except Exception as e:  # noqa: BLE001 — smoke must report, not crash
        swap_summary = {"error": f"{type(e).__name__}: {e}"}
        swap_failures = [f"autoscaling phase crashed: "
                         f"{type(e).__name__}: {e}"]
    failures += swap_failures

    # phase 3: output-health guard (numerics observatory, ISSUE 14)
    try:
        guard_summary, guard_failures = output_health_guard()
    except Exception as e:  # noqa: BLE001 — smoke must report, not crash
        guard_summary = {"error": f"{type(e).__name__}: {e}"}
        guard_failures = [f"output-health phase crashed: "
                          f"{type(e).__name__}: {e}"]
    failures += guard_failures

    # phase 4: stateful generation across a mid-stream hot reload
    try:
        gen_summary, gen_failures = generation_hot_reload()
    except Exception as e:  # noqa: BLE001 — smoke must report, not crash
        gen_summary = {"error": f"{type(e).__name__}: {e}"}
        gen_failures = [f"generation phase crashed: "
                        f"{type(e).__name__}: {e}"]
    failures += gen_failures

    summary = {
        "smoke": "serving", "clients": N_CLIENTS, "answered": ok,
        "shed": shed, "failures": failures,
        "output_health": guard_summary,
        "throughput_rps": snap.get("throughput_rps"),
        "p99_ms": snap.get("latency_ms", {}).get("p99"),
        "batch_occupancy": snap.get("batch_occupancy"),
        "executor_cache": snap.get("executor_cache"),
        "pools": snap.get("pools"),
        "autoscaling": swap_summary,
        "generation": gen_summary,
    }
    print(json.dumps(summary), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
