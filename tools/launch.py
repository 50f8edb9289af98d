#!/usr/bin/env python
"""Launch a distributed parameter-server job on localhost.

Role parity with /root/reference/tools/launch.py:128 + dmlc-tracker
'local' mode: spawns 1 server (the kvstore_server process), N workers,
each with the DMLC_* rendezvous env the dist kvstore reads
(kvstore.py KVStoreDist).  Multi-host TPU jobs use the SPMD path
(mxnet_tpu.parallel over ICI/DCN), not this launcher — this covers the
reference's `launch.py -n N --launcher local python train.py` workflow.

One process per chip: a TPU chip belongs to the first process that
initialises jax on it.  The server only aggregates on the host and is
pinned to the CPU backend here.  The workers inherit the caller's
environment unchanged, so on a host with C chips N workers that each
use ``mx.tpu()`` all try to take the same chips and all but the first
fail or hang: today run N > 1 workers with ``JAX_PLATFORMS=cpu``
(``--env JAX_PLATFORMS=cpu``), or one worker that owns the chips.
Giving worker k chip k is not implemented.

Usage:
  python tools/launch.py -n 4 [-p 9091] python train_script.py args...
"""
import argparse
import os
import signal
import subprocess
import sys
import time


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed job on localhost "
                    "(parity: reference tools/launch.py local mode)")
    parser.add_argument("-n", "--num-workers", required=True, type=int)
    parser.add_argument("-s", "--num-servers", type=int, default=1,
                        help="only 1 server process is supported (it "
                        "owns the whole store)")
    parser.add_argument("-p", "--port", type=int, default=9091)
    parser.add_argument("--env", nargs="*", default=[],
                        help="extra KEY=VALUE env for all roles")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.num_servers != 1:
        parser.error("the TPU kvstore server is a single process "
                     "(aggregation is in-memory); use -s 1")

    base_env = dict(os.environ)
    for kv in args.env:
        k, _, v = kv.partition("=")
        base_env[k] = v
    base_env.update({
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(args.port),
    })

    procs = []

    def shutdown(*_):
        for p in procs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGINT, shutdown)
    signal.signal(signal.SIGTERM, shutdown)

    # server role (parity: DMLC_ROLE=server blocking in RunServer)
    senv = dict(base_env)
    senv["DMLC_ROLE"] = "server"
    senv["JAX_PLATFORMS"] = "cpu"  # aggregates on the host: takes no chip
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    senv["PYTHONPATH"] = repo + os.pathsep + senv.get("PYTHONPATH", "")
    server = subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.kvstore_server"], env=senv)
    procs.append(server)
    time.sleep(0.3)

    # worker roles
    workers = []
    for rank in range(args.num_workers):
        wenv = dict(base_env)
        wenv.update({"DMLC_ROLE": "worker", "DMLC_RANK": str(rank),
                     "DMLC_WORKER_ID": str(rank)})
        wenv["PYTHONPATH"] = repo + os.pathsep + wenv.get("PYTHONPATH", "")
        w = subprocess.Popen(args.command, env=wenv)
        workers.append(w)
        procs.append(w)

    rc = 0
    for w in workers:
        rc = w.wait() or rc
    server.terminate()
    sys.exit(rc)


if __name__ == "__main__":
    main()
