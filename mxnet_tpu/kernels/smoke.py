"""Kernels smoke — the CI phase for the kernel layer.

CPU-only (Pallas interpreter) proof obligations:

1. every registered kernel passes its interpreter-mode fwd+bwd
   correctness gate vs its jax reference, on every config of a tiny
   grid;
2. a tiny measured tune commits winners and persists them into the
   versioned namespace next to the PR 7 compile-cache ladders;
3. a SECOND process reloads those winners with ZERO re-tunes (asserted
   from the child's own counters);
4. a salt flip (fresh namespace) invalidates cleanly: the child falls
   back to heuristic defaults, still zero re-tunes, no crash;
5. trace budgets hold through the PR 7 ledger: one recorded tune trace
   per search, and re-resolving every kernel after tuning records
   nothing new.

Run: ``python -m mxnet_tpu.kernels.smoke`` (ci/run.sh kernels phase).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

# small shapes: the smoke proves mechanics, not device speed
SMOKE_SHAPES = {
    "layernorm": (64, 32),
    "softmax_ce": (64, 16),
    "attention": (2, 2, 32, 8),
}
SMOKE_GRIDS = {
    "layernorm": [{"block_rows": 64}, {"block_rows": 16}],
    "softmax_ce": [{"block_rows": 32}, {"block_rows": 8}],
    "attention": [{"block_q": 128, "block_k": 128},
                  {"block_q": 64, "block_k": 64}],
}


def _child():
    """Re-resolve every smoke shape and report sources + tune count."""
    import numpy as np

    from mxnet_tpu import kernels
    sources = {}
    for name, shape in SMOKE_SHAPES.items():
        kb = kernels.get(name, shape, np.float32)
        sources[name] = None if kb is None else kb.source
    print(json.dumps({"tunes": kernels.autotune.tunes_performed(),
                      "sources": sources}))
    return 0


def _spawn(env):
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.kernels.smoke", "--child"],
        env=env, capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise SystemExit(f"kernels smoke child failed:\n{out.stdout}\n"
                         f"{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--child" in argv:
        return _child()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # a CPU smoke of the persistence MECHANISM: fresh directory on purpose
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    cache_dir = os.environ.get("MXNET_COMPILE_CACHE_DIR")
    if not cache_dir:
        cache_dir = tempfile.mkdtemp(prefix="mxnet-kernels-smoke-")
        os.environ["MXNET_COMPILE_CACHE_DIR"] = cache_dir
    os.environ["MXNET_KERNELS"] = "tuned"

    import numpy as np

    from mxnet_tpu import kernels
    from mxnet_tpu.compile.ledger import LEDGER
    from mxnet_tpu.kernels.registry import gate_report

    # 1. gates: the full tiny grid must be classifiable and pass
    print("== kernels smoke: interpreter-mode correctness gates ==")
    for name, shape in SMOKE_SHAPES.items():
        report = gate_report(name, shape, np.float32)
        bad = [key for key, ok in report.items() if not ok]
        assert not bad, f"kernel {name!r}: gate failed for {bad}"
        print(f"   {name}: {len(report)} configs gated, all pass")

    # 2. tune the tiny grid; winners must persist
    print("== kernels smoke: tiny-grid measured tune ==")
    before = LEDGER.trace_count("kernels/tune")
    for name, shape in SMOKE_SHAPES.items():
        cfg, source = kernels.tune(name, shape, np.float32,
                                   configs=SMOKE_GRIDS[name], repeats=1)
        assert source == "tuned", (name, source)
        print(f"   {name}: winner {cfg}")
    assert kernels.autotune.tunes_performed() == len(SMOKE_SHAPES)
    path = kernels.autotune.winners_path()
    assert os.path.exists(path), path

    # 5a. ledger budget: exactly one tune trace per search
    tuned_traces = LEDGER.trace_count("kernels/tune") - before
    assert tuned_traces == len(SMOKE_SHAPES), tuned_traces

    # 5b. re-resolving every kernel is ladder-cache work: zero new traces
    for name, shape in SMOKE_SHAPES.items():
        kb = kernels.get(name, shape, np.float32)
        assert kb is not None and kb.source == "tuned", (name, kb)
    assert LEDGER.trace_count("kernels/tune") - before == tuned_traces, \
        "re-resolution re-tuned"
    print("== kernels smoke: trace budget holds "
          f"({tuned_traces} tune traces, 0 on re-resolution) ==")

    # 3. second process: persisted winners reload, zero re-tunes
    env = dict(os.environ)
    child = _spawn(env)
    assert child["tunes"] == 0, child
    assert all(src == "persisted" for src in child["sources"].values()), \
        child
    print("== kernels smoke: second process reloaded persisted winners, "
          "0 re-tunes ==")

    # 4. salt flip: fresh namespace, clean fallback to defaults
    env_salt = dict(env, MXNET_COMPILE_CACHE_SALT="kernels-smoke-stale")
    child = _spawn(env_salt)
    assert child["tunes"] == 0, child
    assert all(src == "default" for src in child["sources"].values()), \
        child
    # the original namespace must survive the salted run untouched
    assert os.path.exists(path), "salt flip clobbered the live namespace"
    print("== kernels smoke: salt flip fell back to heuristic defaults, "
          "live namespace untouched ==")

    print("kernels smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
