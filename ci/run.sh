#!/bin/bash
# CI pipeline (parity: reference ci/build.py stages, single-host form):
# build native libs, generated-code sync checks, full test suite on the
# virtual 8-device CPU mesh, entry-point dry runs.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== native build =="
make -C src
make -C src capi
make -C amalgamation

echo "== generated code in sync =="
python cpp-package/OpWrapperGenerator.py
git diff --exit-code cpp-package/include/mxnet_tpu/op.hpp

echo "== graftlint (whole-program static analysis, baseline-gated) =="
# phase 1 (lexical): lock-discipline / torn-write / host-sync /
# tracer-leak / swallowed-error / env-knob-drift / raw-phase-timing /
# naked-retry / unbounded-wait / per-param-collective /
# metric-cardinality / leaked-thread; phase 1.5 lowers per-function
# CFGs (exception edges, finally duplication) for the lifecycle
# dataflow; phase 2 (call-graph flow rules): collective-divergence /
# lock-order-cycle / trace-host-escape / resource-leak-on-raise /
# double-release / release-under-wrong-lock.
# Fails only on NEW violations (ci/graftlint_baseline.json holds
# triaged pre-existing debt); --timings prints where lint time goes
# and the whole run must fit the 15 s wall budget (the engine is a
# pre-test phase — it must stay cheaper than one test file).
# docs/lint.md has the rule catalog and suppression syntax.
lint_t0=$SECONDS
python tools/graftlint.py --fail-on-new --timings
lint_wall=$(( SECONDS - lint_t0 ))
echo "graftlint wall: ${lint_wall}s (budget 15s)"
if [ "${lint_wall}" -ge 15 ]; then
  echo "graftlint exceeded its CI wall budget (${lint_wall}s >= 15s)" >&2
  exit 1
fi

echo "== unit suite (virtual 8-device CPU mesh via tests/conftest.py) =="
MXNET_TEST_EXAMPLES=1 python -m pytest tests/ -q

echo "== fused + scanned train step smoke (dispatch budget, parity) =="
# the fused path must issue at most 3 XLA dispatches per train step and
# stay bit-identical to the per-param update loop; the K=8 scanned
# window must issue <= (1+eps)/K dispatches per step and stay
# bit-identical to the sequential fused loop (mxnet_tpu/fused_step.py)
JAX_PLATFORMS=cpu python -m mxnet_tpu.fused_step

echo "== streaming data plane smoke (shard-order determinism, dead-reader exactly-once, backpressure) =="
# the multi-worker prefetch pipeline must deliver the seeded per-epoch
# shard order bitwise-identically for 0/1/2/4 workers, survive a reader
# death mid-epoch with every batch delivered exactly once, and hold the
# buffered-batch bound under a stalled consumer (docs/data.md)
JAX_PLATFORMS=cpu python -m mxnet_tpu.io_pipeline

echo "== mesh fused step smoke (dp x tp fit: dispatch budget, kvstore-loop parity) =="
# a dist_device_sync Module.fit on a dp=2,tp=2 fake-device mesh must run
# each K=8 window as ONE donated shard_map dispatch (<= (1+eps)/K per
# step) and stay bitwise identical — weights AND optimizer state — to
# the sequential per-param kvstore push/pull loop (docs/parallel.md)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m mxnet_tpu.parallel.fused

echo "== elastic multi-host smoke (2 processes x 4 fake devices: kill-and-recover) =="
# a 2-subprocess jax.distributed mesh (gloo CPU collectives) drives the
# fused window across hosts; rank 1 is SIGKILLed at window 3 -> the
# survivor takes a typed PeerLostError at the deadline-bounded
# rendezvous, commits the boundary checkpoint, and the launcher
# respawns the dp/2 survivor world — the continued fit must be BITWISE
# identical to a planned resize, within the per-process dispatch
# budget (docs/parallel.md preemption runbook).  The smoke also scrapes
# the leader's /fleet.json (the killed rank must be tagged lost with
# its last registry snapshot, per-rank families present for EVERY
# generation) and validates the fault generation's postmortem bundle:
# all ranks' flight rings + the final fleet snapshot, with the injected
# site as the first anomalous event (docs/observability.md runbook)
JAX_PLATFORMS=cpu python -m mxnet_tpu.parallel.elastic

echo "== serving smoke (replica pools: burst + hot-swap + generation sessions) =="
# phase 1: 64 concurrent clients against a 2-replica pool with a small
# queue — every request answered correctly or shed with a structured
# error; phase 2: ModelRepository.watch hot-swaps a newly committed
# checkpoint step under sustained load — ZERO dropped non-shed requests
# and ZERO executor-cache misses after the flip (warm-before-flip x
# replica pools); phase 3: NaN logits fail typed, survivors serve;
# phase 4: stateful generation — warm decode + prefill ladders, N
# concurrent sessions over an 8-slot paged KV pool, hot-reload the LM
# MID-STREAM: zero non-shed drops, ZERO post-flip decode compiles, and
# KV slot/ledger page accounting exactly zero after (docs/serving.md)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m mxnet_tpu.serving.smoke

echo "== checkpoint smoke (save -> kill writer mid-save -> restore) =="
JAX_PLATFORMS=cpu python -m mxnet_tpu.checkpoint.smoke

echo "== telemetry smoke (fit + serving burst, exporter scraped, watchdog silent) =="
# 5-step fit + serving burst with the Prometheus endpoint on: required
# metric families must scrape, step lanes must cover >=90% of step wall,
# and the hang watchdog must not fire (docs/observability.md)
JAX_PLATFORMS=cpu python -m mxnet_tpu.telemetry.smoke

echo "== fleet smoke (256-rank simulator, delta plane gates, backcompat pin) =="
# the in-process fleet simulator at a CI-bounded scale: 256 synthetic
# delta-push reporters against one real leader on a virtual clock —
# merge p99 < 1ms, summary rollup < 50ms, summary scrape < 256KiB,
# breach->leader alert lag < 2 push intervals, zero leader exceptions,
# and the rank<=8 detail view byte-identical to the pre-delta merge
# path (docs/observability.md "fleet at scale"); must finish well
# inside 20s on plain host CPU
JAX_PLATFORMS=cpu timeout -k 5 120 \
  python -m mxnet_tpu.telemetry.fleet_sim --ranks 256 --cycles 25 \
    --reference-ranks 0 --json > /tmp/fleet_smoke.json
python - <<'PYEOF'
import json
rep = json.load(open("/tmp/fleet_smoke.json"))
assert rep["ok"], {k: v for k, v in rep["gates"].items() if not v["ok"]}
assert rep["wall_s"] < 20.0, f"fleet smoke too slow: {rep['wall_s']:.1f}s"
print(f"fleet smoke: 256 ranks in {rep['wall_s']:.1f}s, "
      f"merge p99 {rep['result']['merge']['p99_ms']:.3f}ms, "
      f"rollup max {rep['result']['rollup']['max_ms']:.1f}ms, "
      f"scrape {rep['result']['scrape']['summary_kib']:.1f}KiB")
PYEOF

echo "== compile smoke (persistent cache, ladder warmup, retrace ratchet) =="
# publish -> AOT-warm the bucket ladder -> mixed-size burst: the workload
# must trace exactly ladder-size times and compile NOTHING post-warmup;
# the BucketPlanner must beat pow2 on a skewed histogram (docs/compile.md)
JAX_PLATFORMS=cpu python -m mxnet_tpu.compile.smoke

echo "== kernels smoke (gates, measured tune, persisted winners, salt flip) =="
# every registered Pallas kernel must pass its interpreter-mode fwd+bwd
# correctness gate vs its pure-XLA reference on a tiny grid; a measured
# tune commits winners into the versioned namespace next to the compile
# cache ladders; a SECOND process reloads them with zero re-tunes; a
# salt flip falls back to heuristic defaults without touching the live
# namespace; tune trace budgets hold on the ledger (docs/kernels.md)
JAX_PLATFORMS=cpu python -m mxnet_tpu.kernels.smoke

echo "== chaos smoke (failpoints, composed fault scenarios, self-healing) =="
# the composed scenarios: kvstore worker kill/revive commits past
# the kill, corrupt-checkpoint-under-reload serves the old version with
# zero non-shed failures, a wedged batcher stays p99-bounded under a
# named watchdog stall, a serving replica killed mid-burst drains with
# zero non-shed drops while siblings absorb the load, a generation
# engine killed mid-stream fails its sessions typed-retryable so they
# resume on the sibling with ZERO leaked KV slots/pages, a
# mid-scan-window SIGKILL resumes bit-identically, and the
# stalled/killed mesh fused step self-heals + resumes bit-identically
# onto a resized mesh; disabled-failpoint overhead must stay < 1us
# (docs/chaos.md)
JAX_PLATFORMS=cpu python -m mxnet_tpu.chaos.smoke

echo "== soak smoke (90s train+ckpt+reload+traffic under chaos, alert-engine gated) =="
# the ROADMAP 5b harness: a bounded-minutes loop of train windows,
# checkpoint commits, serving hot-reload and Poisson traffic while a
# seeded benign chaos mix fires, with the resource sampler + in-process
# alert engine + exporter armed.  Passes only if the judgment layer
# stayed quiet: zero firing alerts at exit, zero page-severity fires,
# RSS leak slope below MXNET_SOAK_RSS_SLOPE_MAX, watchdog silent, and a
# final /alerts.json + /fleet.json scrape that parses
# (docs/observability.md alerts section, docs/chaos.md soak runbook)
JAX_PLATFORMS=cpu python -m mxnet_tpu.chaos.soak --seconds 90

echo "== entry points =="
JAX_PLATFORMS=cpu python -c \
  "import __graft_entry__ as g; fn, a = g.entry(); fn(*a)"
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "CI OK"
