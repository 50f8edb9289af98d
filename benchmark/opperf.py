#!/usr/bin/env python
"""Per-operator benchmark harness (parity: reference benchmark/opperf/
opperf.py + nd_operations/*).

Times forward and forward+backward of each registered op at representative
shapes, through the SAME fcompute path the executors run.

Methodology (shared with bench.py — see its docstring):
  * every timed program returns one scalar and timing wraps
    ``float(...)``, a 4-byte device->host transfer.  On the v5e
    ``block_until_ready`` is a barrier too (chip_smoke.py's clock leg:
    92.3 ms against 93.0 ms for the same matmul chain, PR 21), so the
    transfer is a convention here, not a necessity; one helper for both
    is ROADMAP D4.
  * each op runs R times inside ONE jitted ``lax.fori_loop`` with a
    dynamic trip count; iterations are serialized by folding a scalar
    derived from iteration i's output into iteration i+1's input (nothing
    hoistable, nothing dead).  Op time = (T(2R) - T(R)) / R — the fixed
    per-dispatch cost cancels.
  * backward = jax.vjp with a ones cotangent, same loop discipline.

Usage:
  python benchmark/opperf.py                    # all suites, default dev
  python benchmark/opperf.py --suite gemm nn    # subset
  python benchmark/opperf.py --dtype float32 --output results.json
  JAX_PLATFORMS=cpu python benchmark/opperf.py  # CPU smoke (numbers are
                                                # about the host, not TPU)

No TPU results are committed: not measured on this machine yet.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _suites(dtype):
    """suite -> list of (label, op_name, attrs, input_shapes).

    Shapes follow the reference's opperf defaults (1024x1024-class tensors
    for elementwise/reduction, ImageNet-class for conv/pool) so numbers
    are comparable in spirit.
    """
    B = {
        "unary": [
            ("relu_1Mx", "relu", {}, [(1024, 1024)]),
            ("sigmoid_1Mx", "sigmoid", {}, [(1024, 1024)]),
            ("exp_1Mx", "exp", {}, [(1024, 1024)]),
            ("log_1Mx", "log", {}, [(1024, 1024)]),
            ("sqrt_1Mx", "sqrt", {}, [(1024, 1024)]),
            ("negative_1Mx", "negative", {}, [(1024, 1024)]),
        ],
        "binary": [
            ("add_1Mx", "elemwise_add", {}, [(1024, 1024), (1024, 1024)]),
            ("mul_1Mx", "elemwise_mul", {}, [(1024, 1024), (1024, 1024)]),
            ("bcast_add_row", "broadcast_add", {}, [(1024, 1024), (1, 1024)]),
            ("bcast_mul_col", "broadcast_mul", {}, [(1024, 1024), (1024, 1)]),
        ],
        "reduction": [
            ("sum_1Mx", "sum", {}, [(1024, 1024)]),
            ("mean_axis0", "mean", {"axis": 0}, [(1024, 1024)]),
            ("max_axis1", "max", {"axis": 1}, [(1024, 1024)]),
            ("argmax_axis1", "argmax", {"axis": 1}, [(1024, 1024)]),
        ],
        "gemm": [
            ("dot_1k", "dot", {}, [(1024, 1024), (1024, 1024)]),
            ("dot_4k", "dot", {}, [(4096, 4096), (4096, 4096)]),
            ("batch_dot_32x512", "batch_dot", {},
             [(32, 512, 512), (32, 512, 512)]),
            ("fc_bs128", "FullyConnected", {"num_hidden": 1024},
             [(128, 1024), (1024, 1024), (1024,)]),
        ],
        "nn": [
            ("conv3x3_64c_56sq", "Convolution",
             {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1),
              "no_bias": True},
             [(32, 64, 56, 56), (64, 64, 3, 3)]),
            ("conv1x1_256c_56sq", "Convolution",
             {"kernel": (1, 1), "num_filter": 256, "no_bias": True},
             [(32, 64, 56, 56), (256, 64, 1, 1)]),
            ("maxpool2x2", "Pooling",
             {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
             [(32, 64, 112, 112)]),
            ("batchnorm_train", "BatchNorm", {"_training": True},
             [(32, 64, 56, 56), (64,), (64,), (64,), (64,)]),
            ("layernorm_seq", "LayerNorm", {},
             [(32, 512, 1024), (1024,), (1024,)]),
            ("softmax_vocab32k", "softmax", {}, [(128, 32768)]),
            ("activation_relu", "Activation", {"act_type": "relu"},
             [(32, 64, 112, 112)]),
        ],
        "index": [
            ("take_emb", "take", {}, [(50000, 512)], [(8192,)]),
            ("one_hot_1k", "one_hot", {"depth": 1000}, [], [(8192,)]),
            ("topk_k10", "topk", {"k": 10, "ret_typ": "value"},
             [(128, 32768)]),
            ("sort_32k", "sort", {}, [(128, 32768)]),
            ("transpose_2d", "transpose", {}, [(4096, 4096)]),
            ("concat_axis1", "Concat", {"dim": 1},
             [(1024, 512), (1024, 512)]),
        ],
        "optimizer": [
            ("sgd_mom_25M", "sgd_mom_update",
             {"lr": 0.01, "momentum": 0.9, "rescale_grad": 1.0},
             [(25_000_000,), (25_000_000,), (25_000_000,)]),
            ("adam_25M", "adam_update",
             {"lr": 1e-3, "rescale_grad": 1.0},
             [(25_000_000,), (25_000_000,), (25_000_000,), (25_000_000,)]),
        ],
    }
    return B


# ops whose inputs must be integral (indices): input index -> (low, high)
_INT_INPUTS = {
    "take_emb": {1: (0, 50000)},
    "one_hot_1k": {0: (0, 1000)},
}
# ops with no meaningful backward (integer outputs / updates)
_FWD_ONLY = {"argmax_axis1", "one_hot_1k", "topk_k10", "sort_32k",
             "sgd_mom_25M", "adam_25M"}


def time_op(label, op_name, attrs, shapes, int_shapes, dev, dtype,
            base_reps, do_backward):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu.ops import registry

    op = registry.get(op_name)
    fcompute = op.raw(dict(attrs))

    rng = np.random.RandomState(0)
    args = []
    for i, s in enumerate(shapes):
        a = rng.uniform(0.5, 1.5, size=s).astype(dtype)
        args.append(jax.device_put(a, dev))
    ranges = _INT_INPUTS.get(label, {})
    for i, s in enumerate(int_shapes):
        lo, hi = ranges.get(i + len(shapes), ranges.get(i, (0, 2)))
        a = rng.randint(lo, hi, size=s).astype(np.int32)
        args.append(jax.device_put(a, dev))

    def first_scalar(out):
        o = out[0] if isinstance(out, (tuple, list)) else out
        return o.ravel()[0].astype(jnp.float32)

    def perturb(a, s):
        """Make iteration i+1's input data-depend on iteration i's output
        so XLA can neither hoist the body (loop-invariant code motion) nor
        fold the dependence away.  s*1e-30 rounds to zero at runtime, so
        values stay stable; the compiler cannot prove that.

        Floats: one-element scatter into the loop-CARRIED buffer — O(1),
        and XLA updates the dead carry in place (no copy pass).
        Ints: add (s > 1e30), runtime-false but not statically foldable —
        int inputs here are small index vectors, the pass is negligible.
        """
        if jnp.issubdtype(a.dtype, jnp.floating):
            idx = (0,) * a.ndim
            return a.at[idx].add((s * 1e-30).astype(a.dtype))
        return a + (s > 1e30).astype(a.dtype)

    def fwd_once(a0, rest):
        return first_scalar(fcompute(*([a0] + list(rest))))

    def bwd_once(a0, rest):
        rest = list(rest)

        def f(z):
            out = fcompute(*([z] + rest))
            return out[0] if isinstance(out, (tuple, list)) else out

        out, vjp = jax.vjp(f, a0)
        # cotangent seeded from the input: for LINEAR ops the gradient does
        # not depend on a0, and a constant cotangent would let XLA fold the
        # whole vjp to a constant and hoist it out of the timing loop
        seed = (a0.ravel()[0].astype(jnp.float32) * 1e-30)
        cot = jnp.ones_like(out) * (1 + seed).astype(out.dtype)
        (gx,) = vjp(cot)
        return gx.ravel()[0].astype(jnp.float32)

    def make_loop(once):
        # `salt` is a fresh scalar per CALL: a unique live input, so no
        # layer between the caller and the chip can answer a repeated
        # (executable, args) call from a cache. It seeds the carry, so it
        # is not dead code.
        def loop(r, salt, a0, *rest):
            def body(_, carry):
                a, s = carry
                a = perturb(a, s)
                return (a, once(a, rest))
            return lax.fori_loop(0, r, body,
                                 (a0, salt * jnp.float32(1e-30)))[1]
        return jax.jit(loop)

    res = {"op": op_name, "attrs": {k: (list(v) if isinstance(v, tuple)
                                        else v) for k, v in attrs.items()},
           "shapes": [list(s) for s in shapes] + [list(s) for s in int_shapes],
           "dtype": str(np.dtype(dtype))}

    for phase, once in (("fwd", fwd_once),
                        *((("fwd_bwd", bwd_once),) if do_backward else ())):
        try:
            loop = make_loop(once)
            c = loop.lower(jnp.int32(1), jnp.float32(0), *args).compile()
            float(c(jnp.int32(2), jnp.float32(1), *args))  # warm
            call_no = [1]

            def timed(r, tries=3):
                ts = []
                for _ in range(tries):
                    call_no[0] += 1
                    t0 = time.perf_counter()
                    float(c(jnp.int32(r), jnp.float32(call_no[0]), *args))
                    ts.append(time.perf_counter() - t0)
                return min(ts)

            # adaptive rep count: the differenced signal (R * op_time)
            # must be >> the jitter of the fixed per-call cost.  The trip
            # count is DYNAMIC, so scaling R needs no recompile.
            r = base_reps
            t1 = timed(r)
            t2 = timed(2 * r)
            per = (t2 - t1) / r
            target_s = 0.08
            if per * r < target_s:
                est = max(per, 1e-7)
                r = int(min(5000, max(r, target_s / est)))
                t1 = timed(r)
                t2 = timed(2 * r)
                per = (t2 - t1) / r
            if per <= 0:
                res[phase] = {"anomaly": f"T(2R)={t2:.5f} <= T(R)={t1:.5f} "
                              f"at R={r}"}
            else:
                res[phase + "_ms"] = round(per * 1e3, 5)
                res[phase + "_reps"] = r
        except Exception as e:
            res[phase] = {"error": f"{type(e).__name__}: {e}"}
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--suite", nargs="*", default=None,
                    help="subset of suites (default: all)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--reps", type=int, default=20,
                    help="base rep count R; timing differences 2R vs R")
    ap.add_argument("--no-backward", action="store_true")
    ap.add_argument("--output", default=None, help="write results JSON here")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    dtype = np.dtype(args.dtype)
    suites = _suites(dtype)
    chosen = args.suite or sorted(suites)

    meta = {"device": str(dev), "device_kind": getattr(dev, "device_kind", "?"),
            "platform": dev.platform, "dtype": str(dtype),
            "method": "jitted dynamic-R fori_loop, transfer-sync, "
                      "differenced (T(2R)-T(R))/R",
            "base_reps": args.reps}
    results = {"meta": meta, "results": {}}
    t_all = time.perf_counter()
    for suite in chosen:
        if suite not in suites:
            print(f"unknown suite {suite!r}; have {sorted(suites)}",
                  file=sys.stderr)
            continue
        for entry in suites[suite]:
            label, op_name, attrs, shapes = entry[0], entry[1], entry[2], entry[3]
            int_shapes = entry[4] if len(entry) > 4 else []
            do_bwd = not args.no_backward and label not in _FWD_ONLY
            t0 = time.perf_counter()
            try:
                r = time_op(label, op_name, attrs, shapes, int_shapes, dev,
                            dtype, args.reps, do_bwd)
            except Exception as e:
                # one op that does not lower must not lose every point
                # measured so far; the error is recorded under its label
                r = {"error": f"{type(e).__name__}: {e}"}
            r["suite"] = suite
            results["results"][label] = r
            msg = " ".join(f"{k}={v}" for k, v in r.items()
                           if k.endswith("_ms")) or r.get("error", "")[:60]
            print(f"[{time.perf_counter() - t_all:6.1f}s] {label:22s} {msg}"
                  f"  ({time.perf_counter() - t0:.1f}s incl. compile)",
                  flush=True)
            if args.output:  # incremental: survive a kill mid-run
                tmp = args.output + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(results, f, indent=1)
                os.replace(tmp, args.output)  # atomic: never truncate

    out = args.output
    if out:
        tmp = out + ".tmp"  # atomic like the incremental writes
        with open(tmp, "w") as f:
            json.dump(results, f, indent=1)
        os.replace(tmp, out)
        print(f"wrote {out}")
    else:
        print(json.dumps(results))


if __name__ == "__main__":
    main()
