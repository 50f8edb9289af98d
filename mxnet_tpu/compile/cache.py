"""Persistent XLA compilation artifacts (mxnet_tpu.compile, part 1).

Every process used to pay the full cold-trace + backend-compile cost for
each (model, version, bucket) serving executor and each fused/scanned
train step.  jax ships a content-addressed persistent compilation cache
(keyed by the serialized MLIR module + compile options + backend); this
module owns its lifecycle for the whole framework:

* **location** — in order: ``JAX_COMPILATION_CACHE_DIR`` (jax's own
  variable: the directory is used exactly as given — no sub-directory,
  no marker file, never renamed — and this module never sets another);
  else ``MXNET_COMPILE_CACHE_DIR`` (the hermetic CPU tests and smokes),
  versioned as below; else ``<checkout>/.jax_cache``, a fixed path beside
  the package, because the path is part of jax's cache key and a
  directory that moves never hits;
* **versioned invalidation** — under ``MXNET_COMPILE_CACHE_DIR``
  artifacts live in a subdirectory named by a digest of (jax, jaxlib,
  mxnet_tpu, ``MXNET_COMPILE_CACHE_SALT``), so upgrading any layer of
  the stack switches to a fresh namespace (jax's own content key covers
  the other two locations); ``prune_stale()`` garbage-collects the
  namespaces no live version can use;
* **activation** — :func:`ensure_persistent_cache` is called lazily from
  the compile-heavy paths (serving executor-cache misses, ladder warmup,
  ``FusedTrainStep``/``ScanTrainStep`` trace builds), is idempotent, and
  is a no-op when ``MXNET_COMPILE_CACHE=0``.

Entries below ``MXNET_COMPILE_CACHE_MIN_COMPILE_S`` of backend compile
time are not persisted (jax's own default policy): tiny programs are
cheaper to recompile than to hash + stat.  Tests and the CI compile
smoke set it to 0 so toy models persist too.
"""
from __future__ import annotations

import hashlib
import logging
import os
import shutil
import threading

log = logging.getLogger("mxnet_tpu.compile")

_MARKER = "MXNET_CACHE_KEY"

_lock = threading.Lock()
_resolved = False      # ensure_persistent_cache ran (even if disabled)
_active = None         # the versioned dir jax writes to, when enabled


def version_key():
    """Digest naming the artifact namespace: any jax / jaxlib /
    mxnet_tpu upgrade (or an explicit ``MXNET_COMPILE_CACHE_SALT``)
    changes it, which IS the invalidation policy — executables compiled
    by a different stack are never looked up, only orphaned."""
    import jax
    import jaxlib

    from .. import config as _config
    from ..base import __version__ as mx_version
    raw = "|".join((f"jax={jax.__version__}",
                    f"jaxlib={jaxlib.__version__}",
                    f"mxnet_tpu={mx_version}",
                    f"salt={_config.get('MXNET_COMPILE_CACHE_SALT')}"))
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def outside_dir():
    """``JAX_COMPILATION_CACHE_DIR`` when set: a directory placed from
    outside the program, which jax reads itself."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def cache_root():
    """Home of the framework's own persisted files (ladder plans, kernel
    winners, versioned artifact namespaces): ``MXNET_COMPILE_CACHE_DIR``,
    else ``<checkout>/.jax_cache``.  Never the outside directory."""
    from .. import config as _config
    return _config.get("MXNET_COMPILE_CACHE_DIR") or _CHECKOUT_CACHE


def cache_dir():
    """The directory compiled executables persist to (see the module
    docstring for the order)."""
    from .. import config as _config
    outside = outside_dir()
    if outside:
        return outside
    if _config.get("MXNET_COMPILE_CACHE_DIR"):
        return os.path.join(cache_root(), version_key())
    return _CHECKOUT_CACHE


def active_dir():
    """The directory jax is currently persisting to (None when the cache
    is disabled or :func:`ensure_persistent_cache` has not run yet)."""
    with _lock:
        return _active


def ensure_persistent_cache():
    """Point jax's persistent compilation cache at :func:`cache_dir`.

    Idempotent and thread-safe; called from every compile-heavy path so
    a process that serves or trains always resolves the cache before its
    first expensive compile.  Returns the active directory, or None when
    ``MXNET_COMPILE_CACHE=0``.  With ``JAX_COMPILATION_CACHE_DIR`` set
    jax has already read the directory and its own thresholds: nothing
    is configured here.
    """
    global _resolved, _active
    with _lock:
        if _resolved:
            return _active
        from .. import config as _config
        if not _config.get("MXNET_COMPILE_CACHE"):
            _resolved = True
            return None
        import jax
        if outside_dir():
            _resolved = True
            _active = jax.config.jax_compilation_cache_dir
            return _active
        target = cache_dir()
        try:
            os.makedirs(target, exist_ok=True)
            if target != _CHECKOUT_CACHE:
                marker = os.path.join(target, _MARKER)
                if not os.path.exists(marker):
                    tmp = marker + f".tmp.{os.getpid()}"
                    with open(tmp, "w") as f:
                        f.write(version_key() + "\n")
                    os.replace(tmp, marker)
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", target)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(_config.get("MXNET_COMPILE_CACHE_MIN_COMPILE_S")))
            # no size floor: the compile-time floor above is the policy
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
            # jax memoizes is_cache_used() at the FIRST compile of the
            # process — which already happened (framework import jits a
            # few helpers) with no directory configured.  Reset so the
            # next compile re-initializes against our directory.
            from jax._src import compilation_cache as _cc
            _cc.reset_cache()
        except Exception:
            # an unusable cache dir degrades to cold compiles, never to a
            # broken process
            log.exception("persistent compilation cache disabled: could "
                          "not activate %r", target)
            _resolved = True
            _active = None
            return None
        _resolved = True
        _active = target
        log.info("persistent compilation cache at %s", target)
        return target


def stale_namespaces():
    """Version-key subdirectories under :func:`cache_root` that no
    longer match the running stack (candidates for :func:`prune_stale`)."""
    root, current = cache_root(), version_key()
    if not os.path.isdir(root):
        return []
    return sorted(d for d in os.listdir(root)
                  if d != current and os.path.isdir(os.path.join(root, d))
                  and os.path.exists(os.path.join(root, d, _MARKER)))


def prune_stale():
    """Delete stale artifact namespaces; returns the names removed.
    Never runs implicitly — an operator (or the runbook) calls it."""
    removed = []
    root = cache_root()
    for name in stale_namespaces():
        shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        removed.append(name)
    return removed


def _owned(active):
    """True for the versioned namespace under ``MXNET_COMPILE_CACHE_DIR``
    — the only directory this module creates, and so the only one it
    may move."""
    from .. import config as _config
    return (active is not None and not outside_dir()
            and bool(_config.get("MXNET_COMPILE_CACHE_DIR"))
            and active == cache_dir())


def quarantine_active(reason=""):
    """Move the ACTIVE artifact namespace into ``<root>/quarantine/`` and
    detach jax from it (fresh compiles from here on; a process restart
    re-activates against a clean directory).

    This is the self-healing response to a corrupt/truncated persisted
    executable (ISSUE 8): jax's cache granularity hides WHICH entry
    failed to deserialize, so the whole namespace is quarantined — the
    artifacts survive for offline diagnosis, and nothing in the bad
    namespace is ever looked up again.  Returns the quarantine path, or
    None when no cache was active — or when the directory was placed
    from outside (``JAX_COMPILATION_CACHE_DIR``, or the fixed
    ``<checkout>/.jax_cache`` that other processes share): those are
    never moved or detached, and the caller's error propagates.
    """
    global _active, _resolved
    with _lock:
        active = _active
        if not _owned(active):
            return None
        _active = None
        _resolved = True  # stay detached for the rest of the process
    dest_root = os.path.join(cache_root(), "quarantine")
    os.makedirs(dest_root, exist_ok=True)
    dest = os.path.join(
        dest_root, f"{os.path.basename(active)}.{os.getpid()}")
    try:
        os.rename(active, dest)
    except OSError as e:
        log.warning("compile cache: could not quarantine %r (%s); "
                    "detaching anyway", active, e)
        dest = None
    try:
        import jax
        jax.config.update("jax_compilation_cache_dir", None)
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
    except Exception as e:  # noqa: BLE001 — detach is best-effort; fresh compiles still work
        log.warning("compile cache: detach from jax failed: %s", e)
    try:
        from .. import telemetry as _telemetry
        _telemetry.REGISTRY.counter(
            "mxnet_compile_cache_quarantined_total",
            "persistent compile-cache namespaces quarantined after an "
            "artifact failed to load").inc()
    except Exception:  # graftlint: disable=swallowed-error -- accounting must not mask the quarantine
        pass
    log.error("compile cache: quarantined artifact namespace %r -> %r%s; "
              "falling back to fresh compiles", active, dest,
              f" ({reason})" if reason else "")
    return dest


def guarded_compile(fn, what="compile"):
    """Run ``fn()`` (a trace/compile/first-forward); if it raises while
    the persistent compilation cache is active, quarantine the namespace
    (corrupt/truncated artifacts are the prime suspect) and retry ONCE
    against fresh compiles.  With no cache active, or one this module
    may not move (see :func:`quarantine_active`), the error propagates
    unchanged — there is nothing to heal.
    """
    from ..chaos.failpoints import failpoint
    try:
        failpoint("compile/cache/artifact")
        return fn()
    except Exception as e:
        if not _owned(active_dir()):
            raise
        log.warning("compile cache: %s failed with the persistent cache "
                    "active (%s: %s) — quarantining and recompiling "
                    "fresh", what, type(e).__name__, e)
        quarantine_active(f"{what}: {type(e).__name__}: {e}")
        return fn()


def _reset_for_tests():
    """Forget the resolved state so a test can re-activate against a
    fresh directory; restores jax's cache defaults."""
    global _resolved, _active
    with _lock:
        was = _active
        _resolved = False
        _active = None
    if was is not None:
        import jax
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        try:
            from jax._src import compilation_cache as _cc
            _cc.reset_cache()
        except Exception as e:  # noqa: BLE001 — test-only helper
            log.debug("reset_cache unavailable: %s", e)
    return was
