#!/usr/bin/env python
"""Microbenchmark: Pallas flash attention vs plain-XLA attention.

Prints one JSON line per (seq_len, causal) point:
  {"metric": "flash_attention", "seq": S, "causal": bool,
   "flash_ms": ..., "xla_ms": ..., "speedup": ...}

Run on the TPU chip (default env; through the chip tool, one process),
or with JAX_PLATFORMS=cpu in interpreter mode for plumbing checks only:
an interpreter timing says nothing about the kernel.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench(fn, *args, reps=10):
    out = fn(*args)
    jax_block(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax_block(out)
    return (time.perf_counter() - t0) / reps * 1e3


def jax_block(x):
    import jax
    jax.block_until_ready(x)


def main():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_attention import (flash_attention,
                                                _reference_attention)

    b, h, d = int(os.environ.get("BENCH_B", 4)), 8, 128
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    seqs = [int(s) for s in
            os.environ.get("BENCH_SEQS", "512,1024,2048").split(",")]
    for s in seqs:
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, h, s, d), dtype)
        k = jax.random.normal(kk, (b, h, s, d), dtype)
        v = jax.random.normal(kv, (b, h, s, d), dtype)
        for causal in (False, True):
            flash = jax.jit(lambda q_, k_, v_, c=causal:
                            flash_attention(q_, k_, v_, c))
            xla = jax.jit(lambda q_, k_, v_, c=causal:
                          _reference_attention(q_, k_, v_, c, d ** -0.5))
            fm = bench(flash, q, k, v)
            xm = bench(xla, q, k, v)
            print(json.dumps({
                "metric": "flash_attention", "seq": s, "causal": causal,
                "batch": b, "heads": h, "head_dim": d,
                "dtype": str(dtype.__name__),
                "flash_ms": round(fm, 3), "xla_ms": round(xm, 3),
                "speedup": round(xm / fm, 3)}), flush=True)


if __name__ == "__main__":
    main()
