"""mxnet_tpu.compile — compilation as a managed artifact (ISSUE 7).

Single owner of the compilation lifecycle, four pieces:

* **persistent artifacts** (:mod:`cache`) — jax's persistent compilation
  cache wired under the serving executor cache and the fused/scanned
  train step — at ``JAX_COMPILATION_CACHE_DIR`` untouched when that is
  set, else ``MXNET_COMPILE_CACHE_DIR`` with versioned invalidation,
  else ``<checkout>/.jax_cache``: a restarted process deserializes
  executables instead of recompiling them.
* **AOT warmup** (:mod:`warmup`) — a model version's full bucket ladder
  is ``lower().compile()``d at publish time (and BEFORE the served-
  version pointer flips on checkpoint hot-reload), so first-request
  latency is an executor-cache hit, not a compile.
* **measured ladders** (:mod:`planner` + :mod:`stats`) — the power-of-
  two bucket guess is replaced by a DP over the telemetry request-size
  histogram minimizing expected padding waste under a ladder-size
  budget, persisted per model-version.
* **retrace ratchet** (:mod:`ledger`) — every trace/compile event is
  counted with (callsite, reason) and surfaced as
  ``mxnet_compile_*`` telemetry lanes; CI pins smoke workloads to their
  trace budget (``python -m mxnet_tpu.compile.smoke``).

See docs/compile.md for the lifecycle, planning policy, and the
"why did this retrace?" runbook.
"""
from __future__ import annotations

from .. import telemetry as _telemetry
from .cache import (active_dir, cache_dir, cache_root,
                    ensure_persistent_cache, guarded_compile, prune_stale,
                    quarantine_active, stale_namespaces, version_key)
from .ledger import LEDGER, TraceLedger, record_trace
from .planner import (clear_ladders, ladder_for, ladders, load_ladder,
                      padding_waste, plan_for, plan_ladder, pow2_ladder,
                      save_ladder, set_ladder)
from .stats import STATS, ShapeStats, bucket_feed_signature, sample_signature
from .warmup import (aot_compile, clear_warmed, mark_warmed, note_retrace,
                     warm_version, warmed_signatures)

__all__ = [
    "LEDGER", "STATS", "ShapeStats", "TraceLedger", "active_dir",
    "aot_compile", "bucket_feed_signature", "cache_dir", "cache_root",
    "clear_ladders", "clear_warmed", "ensure_persistent_cache",
    "guarded_compile", "ladder_for", "ladders", "load_ladder",
    "mark_warmed", "note_retrace",
    "padding_waste", "plan_for", "plan_ladder", "pow2_ladder",
    "prune_stale", "quarantine_active", "record_trace",
    "sample_signature", "save_ladder",
    "set_ladder", "snapshot", "stale_namespaces", "stats",
    "version_key", "warm_version", "warmed_signatures",
]


def snapshot():
    """One dict: ledger counts, shape stats, active ladders, cache dir."""
    return {
        "cache_dir": active_dir(),
        "ledger": LEDGER.snapshot(),
        "shape_stats": STATS.snapshot(),
        "ladders": {m: list(l) for m, l in ladders().items()},
    }


stats = snapshot  # subsystem-idiomatic alias (serving.stats() etc.)

_telemetry.register_collector("compile", snapshot)
