#!/usr/bin/env python
"""On-device op numerics sweep.

Runs the declarative CASES table (tests/test_op_coverage.py — the same
table the CPU suite sweeps) on BOTH the host CPU backend and the real
TPU, and records the per-op max abs/rel error of the TPU leg against the
CPU leg — the reference's backend-equivalence strategy
(tests/python/gpu/test_operator_gpu.py:1 re-imports the whole CPU suite;
python/mxnet/test_utils.py:1283 check_consistency).

Results stream to the JSON report after EVERY op, --resume skips ops
already recorded, and a time budget bounds the run.  Random/sampling ops
compare moments rather than values (their counter-key streams are
device-independent by construction, but the sweep stays conservative).
One process: it holds the chip for the whole sweep.

Usage:
  python tools/tpu_op_sweep.py [--budget 1200] [--resume]
  JAX_PLATFORMS=cpu python tools/tpu_op_sweep.py --self-test  # harness
"""
import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))

OUT = os.path.join(_REPO, "docs", "tpu_op_sweep.json")
T0 = time.perf_counter()


def log(m):
    print(f"[sweep +{time.perf_counter() - T0:6.1f}s] {m}",
          file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=1200)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--self-test", action="store_true",
                    help="cpu-vs-cpu harness check (no TPU needed)")
    args = ap.parse_args()

    import jax
    import numpy as np
    cpu = jax.devices("cpu")[0]
    if args.self_test:
        target = cpu
    else:
        target = jax.devices()[0]
        if target.platform != "tpu":
            sys.exit(f"tpu_op_sweep needs a TPU: jax found platform "
                     f"{target.platform!r} (--self-test checks the harness "
                     "cpu against cpu)")
    log(f"target device: {target}")

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import nd
    from mxnet_tpu.ndarray import invoke
    from mxnet_tpu.ndarray.ndarray import NDArray
    import test_op_coverage as cov

    report = {"device": str(getattr(target, "device_kind", target)),
              "ops": {}}
    if args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            report["ops"] = json.load(f).get("ops", {})
        log(f"resuming: {len(report['ops'])} ops already recorded")

    names = sorted(cov.CASES)
    n_ok = n_fail = 0
    for i, name in enumerate(names):
        if name in report["ops"] and "error" not in report["ops"][name]:
            continue
        if time.perf_counter() - T0 > args.budget:
            log(f"budget exhausted at {i}/{len(names)}")
            break
        case = cov.CASES[name]
        op = cov._resolve(name)
        rec = {"status": "ok"}
        try:
            legs = {}
            for tag, dev in (("cpu", cpu), ("tpu", target)):
                arrs = [NDArray(jax.device_put(np.asarray(x), dev))
                        for x in case.inputs]
                # zero-input ops (creation family) have no operand to
                # carry the device — pin the default device explicitly
                # or both legs silently run on the same backend
                with jax.default_device(dev):
                    out = invoke(op, arrs, dict(case.attrs))
                outs = out if isinstance(out, list) else [out]
                legs[tag] = [o.asnumpy().astype(np.float64) for o in outs]
            is_random = (name.startswith("_random")
                         or name.startswith("_sample")
                         or name in ("multinomial", "_shuffle"))
            if is_random:
                # moments, not values: samplers draw per-device streams
                m_cpu = [float(np.mean(o)) for o in legs["cpu"]]
                m_tpu = [float(np.mean(o)) for o in legs["tpu"]]
                rec["mean_cpu"], rec["mean_tpu"] = m_cpu, m_tpu
                rec["kind"] = "random-moments"
            else:
                max_abs = max_rel = 0.0
                for a, b in zip(legs["cpu"], legs["tpu"]):
                    diff = np.abs(a - b)
                    max_abs = max(max_abs, float(diff.max(initial=0.0)))
                    denom = np.maximum(np.abs(a), 1e-6)
                    max_rel = max(max_rel,
                                  float((diff / denom).max(initial=0.0)))
                rec["max_abs_err"] = max_abs
                rec["max_rel_err"] = max_rel
                # TPU f32 matmul internals run ~bf16ish; elementwise ops
                # should be (nearly) exact
                if max_rel > 5e-2 and max_abs > 1e-3:
                    rec["status"] = "MISMATCH"
            if rec["status"] == "ok":
                n_ok += 1
            else:
                n_fail += 1
        except Exception as e:
            rec = {"status": "error", "error": f"{type(e).__name__}: {e}"}
            n_fail += 1
        report["ops"][name] = rec
        # rewritten after every op: replace atomically so a killed sweep
        # still leaves a loadable report
        tmp = f"{args.out}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=1)
        os.replace(tmp, args.out)
        if i % 25 == 0:
            log(f"{i}/{len(names)} swept ({n_ok} ok, {n_fail} errors)")

    bad = {k: v for k, v in report["ops"].items()
           if v.get("status") not in ("ok",)}
    summary = {"metric": "tpu_op_sweep", "swept": len(report["ops"]),
               "total": len(names), "mismatch_or_error": len(bad)}
    report["summary"] = summary
    tmp = f"{args.out}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1)
    os.replace(tmp, args.out)
    for k, v in sorted(bad.items()):
        log(f"BAD {k}: {v}")
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
