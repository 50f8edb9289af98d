#!/usr/bin/env python3
"""How ``fixture_*.xplane.pb`` beside this file were recorded: a few
steps of a tiny program on however many chips the machine has, with the
benchmark's ``bench/sync`` annotation after every step and an annotated
sleep on the host between steps (an idle gap with a known cause).  With
more than one chip the batch is sharded and the gradient is summed
across them, so the trace holds an all-reduce.

    python3 benchmark/trace/record_fixture.py <out-dir> [chips]

Run on the chip; it writes ``<out-dir>/fixture_<n>chip.xplane.pb`` for
the first ``chips`` devices (all of them by default).  The
numbers beside the fixtures (``fixture_expected.json``) were then worked
out from the event lists by ``tests/bench_harness``'s independent
brute-force arithmetic, not by ``reduce.py``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_dir, chips=None, steps=5):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("record_fixture: needs a TPU", file=sys.stderr)
        return 1
    devs = devs[:int(chips)] if chips else devs
    n = len(devs)
    mesh = Mesh(devs, ("dp",))
    x = jax.device_put(jnp.ones((16 * n, 1024, 1024), jnp.float32),
                       NamedSharding(mesh, P("dp")))
    w = jax.device_put(jnp.eye(1024, dtype=jnp.float32) * 0.5,
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(w, x):
        def loss(w):
            return jnp.mean(jnp.tanh(x @ w))
        return w - 0.1 * jax.grad(loss)(w)

    w = step(w, x)
    w.block_until_ready()
    tmp = tempfile.mkdtemp(prefix="fixture-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/sync"):
        pass
    for _ in range(steps):
        with jax.profiler.TraceAnnotation("bench/step_call"):
            w = step(w, x)
        w.block_until_ready()
        with jax.profiler.TraceAnnotation("bench/host_sleep"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench/sync"):
            pass
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, f"fixture_{n}chip.xplane.pb")
    shutil.copy(files[0], dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]) if len(sys.argv) > 1 else main("."))
