#!/usr/bin/env python
"""Allreduce bandwidth measurement (parity: reference tools/bandwidth/
measure.py, which timed kvstore push+pull of ResNet/VGG-sized gradients
across GPUs).

TPU redesign: the collective is an XLA ``psum`` over a ``jax.sharding.Mesh``
(the same collective KVStoreICI and parallel.spmd ride), timed with the
transfer-sync + differenced-reps discipline shared with bench.py (an
async-dispatch timer measures queueing, not the wire).

Reported metric matches the reference: algorithmic bandwidth
  BW_alg = 2 * (n-1)/n * bytes / time
(the ring-allreduce wire optimum), per size in a sweep.

Runs anywhere jax has >1 device:
  * real multi-chip TPU: numbers are ICI bandwidth.
  * virtual CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8):
    numbers are host memcpy — useful only to validate the tool + shardings.

Usage:
  python tools/bandwidth/measure.py [--sizes 1e6,4e6,...] [--reps 10]
                                    [--dtype float32] [--output out.json]
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1e5,1e6,1e7,2.5e7",
                    help="comma-separated element counts")
    ap.add_argument("--reps", type=int, default=10,
                    help="base rep count R; timing differences 2R vs R")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        print(json.dumps({"error": f"need >1 device, have {n} "
                          "(set XLA_FLAGS=--xla_force_host_platform_"
                          "device_count=8 for a virtual mesh)"}))
        return
    mesh = Mesh(np.array(devs), ("dp",))
    dtype = np.dtype(args.dtype)
    results = {"n_devices": n,
               "platform": devs[0].platform,
               "device_kind": getattr(devs[0], "device_kind", "?"),
               "dtype": str(dtype),
               "method": "psum over Mesh('dp'), dynamic-R fori_loop, "
                         "transfer-sync, differenced",
               "note": ("virtual CPU mesh measures host memcpy, not a "
                        "wire" if devs[0].platform == "cpu" else
                        "ICI allreduce"),
               "sweep": []}

    for size_s in args.sizes.split(","):
        size = int(float(size_s))

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(None, None, P("dp")),
                           out_specs=P("dp"), check_vma=False)
        def allreduce_chain(r, salt, x):
            # x: per-device shard; chain r psums, each data-dependent on
            # the previous (the *1e-30 fold keeps values stable but
            # unprovably so). salt: per-call-unique live input — some
            # relays cache repeated identical executions (see bench.py)
            x = x + (salt * 1e-30).astype(x.dtype)
            def body(_, acc):
                return lax.psum(acc * (1 + acc[0] * 1e-30).astype(acc.dtype),
                                "dp") / n
            return lax.fori_loop(0, r, body, x)

        def run(r, salt, x):
            return allreduce_chain(r, salt, x)[0].astype(jnp.float32)

        x = jnp.ones((size,), dtype)
        c = jax.jit(run).lower(jnp.int32(1), jnp.float32(0), x).compile()
        float(c(jnp.int32(2), jnp.float32(1), x))  # warm
        calls = [1]

        def timed(r, tries=3):
            ts = []
            for _ in range(tries):
                calls[0] += 1
                t0 = time.perf_counter()
                float(c(jnp.int32(r), jnp.float32(calls[0]), x))
                ts.append(time.perf_counter() - t0)
            return min(ts)

        t1 = timed(args.reps)
        t2 = timed(2 * args.reps)
        per = (t2 - t1) / args.reps
        nbytes = size * dtype.itemsize
        if per <= 0:
            results["sweep"].append({"elements": size, "anomaly":
                                     f"T(2R)={t2:.5f} <= T(R)={t1:.5f}"})
            continue
        bw_alg = 2 * (n - 1) / n * nbytes / per
        results["sweep"].append({
            "elements": size,
            "mbytes": round(nbytes / 1e6, 2),
            "ms_per_allreduce": round(per * 1e3, 4),
            "algbw_gbs": round(bw_alg / 1e9, 3),
        })
        print(f"{size:>12,} elems  {nbytes/1e6:8.1f} MB  "
              f"{per*1e3:8.3f} ms  {bw_alg/1e9:8.2f} GB/s", flush=True)

    if args.output:
        tmp = f"{args.output}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=1)
        os.replace(tmp, args.output)
        print(f"wrote {args.output}")
    else:
        print(json.dumps(results))


if __name__ == "__main__":
    main()
