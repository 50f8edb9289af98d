"""Kernel registry + correctness gates — the kernels-layer contract.

Every hand-fused kernel in the tree is declared here as a
:class:`KernelSpec`: its pure-XLA reference implementation, its tunable
config space (tile/block choices), a heuristic default config, and the
tolerance its outputs must meet.  The registry enforces ONE invariant
before any tuned config becomes eligible: the interpreter-mode
correctness gate — forward AND backward (through the kernel's
custom_vjp) must match the reference within the spec's stated tolerance
on this exact (config, shape, dtype).  A config that has not passed its
gate is never dispatched; a config that fails falls back to the
reference implementation and increments the fallback counter the
``kernel_fallback`` alert watches.

The gate runs the kernel wherever its example inputs live: under the
Pallas interpreter in a CPU process, under Mosaic where the default
backend is a tpu (ops/_pallas_rows.per_platform).  The same kernel
bodies compile under Mosaic at the shapes chip_smoke.py checks; the
tuner's persisted winners are keyed by kernel, shape and dtype only, so
timings taken under the interpreter would be reloaded on the chip — a
debt left to ROADMAP S3/D3 with the MXNET_KERNELS switch.
"""
from __future__ import annotations

import json
import logging
import threading

import numpy as np

from ..base import MXNetError

log = logging.getLogger("mxnet_tpu.kernels")

_lock = threading.Lock()
_SPECS = {}
_GATE_CACHE = {}   # (name, cfg_key, shape, dtype) -> bool
_GATE_WARNED = set()


class KernelSpec:
    """Declaration of one fused kernel.

    * ``reference(*args, **kwargs)`` — pure jax/XLA implementation; the
      numerics oracle AND the fallback executable.
    * ``make(config)`` — build the Pallas implementation for one config
      dict; same call signature as ``reference``.
    * ``config_space(shape, dtype)`` — candidate config dicts for a
      concrete shape/dtype (the autotuner's search grid).
    * ``default_config(shape, dtype)`` — the heuristic config used when
      nothing tuned/persisted exists (last rung of the lookup ladder).
    * ``example_inputs(shape, dtype, rng)`` — ``(args, kwargs)`` used by
      the gate and the tuner's measurements.
    * ``grad_argnums`` — which positional args the gate differentiates.
    * ``tolerance(dtype)`` — ``(rtol, atol)`` for fwd and bwd compares.
    """

    __slots__ = ("name", "doc", "reference", "make", "config_space",
                 "default_config", "example_inputs", "grad_argnums",
                 "tolerance")

    def __init__(self, name, doc, reference, make, config_space,
                 default_config, example_inputs, grad_argnums,
                 tolerance):
        self.name = str(name)
        self.doc = doc
        self.reference = reference
        self.make = make
        self.config_space = config_space
        self.default_config = default_config
        self.example_inputs = example_inputs
        self.grad_argnums = tuple(grad_argnums)
        self.tolerance = tolerance


def register_kernel(spec):
    if not isinstance(spec, KernelSpec):
        raise MXNetError("register_kernel expects a KernelSpec")
    with _lock:
        _SPECS[spec.name] = spec
    return spec


def get_spec(name):
    spec = _SPECS.get(name)
    if spec is None:
        raise MXNetError(
            f"unknown kernel {name!r}; registered: {sorted(_SPECS)}")
    return spec


def list_kernels():
    with _lock:
        return sorted(_SPECS)


def config_key(config):
    """Canonical string for a config dict (persistence + cache keys)."""
    return json.dumps(config or {}, sort_keys=True, separators=(",", ":"))


def _gate_counter():
    from ..telemetry import REGISTRY
    return REGISTRY.counter(
        "mxnet_kernel_gate_total",
        "kernel correctness-gate outcomes by {kernel, result}")


def _run(fn, args, kwargs, grad_argnums):
    """(forward output, grads at grad_argnums) — through whatever vjp
    the implementation defines (custom_vjp for the Pallas kernels,
    plain autodiff for references)."""
    import jax
    import jax.numpy as jnp

    out = fn(*args, **kwargs)

    def loss(*diff):
        full = list(args)
        for i, v in zip(grad_argnums, diff):
            full[i] = v
        o = fn(*full, **kwargs)
        return jnp.sum(jnp.square(o.astype(jnp.float32)))

    grads = jax.grad(loss, argnums=tuple(range(len(grad_argnums))))(
        *[args[i] for i in grad_argnums])
    return out, grads


def _close(a, b, rtol, atol):
    return np.allclose(np.asarray(a, dtype=np.float32),
                       np.asarray(b, dtype=np.float32),
                       rtol=rtol, atol=atol)


def gate(name, config, shape, dtype):
    """Interpreter-mode fwd+bwd correctness gate vs the reference.

    True iff the kernel built from ``config`` matches the spec's
    reference within tolerance on ``(shape, dtype)`` — cached per exact
    key, so the real cost is paid once per process.  A False here means
    the caller MUST NOT dispatch this config (kernels.get serves the
    reference instead and counts the fallback).
    """
    import jax.numpy as jnp

    spec = get_spec(name)
    key = (name, config_key(config), tuple(int(s) for s in shape),
           jnp.dtype(dtype).name)
    with _lock:
        hit = _GATE_CACHE.get(key)
    if hit is not None:
        return hit
    ok, detail = _gate_once(spec, config, shape, dtype)
    with _lock:
        _GATE_CACHE[key] = ok
    try:
        _gate_counter().inc(labels={"kernel": name,
                                    "result": "pass" if ok else "fail"})
    except Exception:  # graftlint: disable=swallowed-error -- gate accounting must never change the gate's answer
        pass
    if not ok:
        with _lock:
            warned = key in _GATE_WARNED
            _GATE_WARNED.add(key)
        if not warned:
            log.warning(
                "kernel %r config %s FAILED its correctness gate on "
                "shape=%s dtype=%s (%s); this config is ineligible — "
                "callers fall back to the reference implementation",
                name, config_key(config), tuple(shape),
                jnp.dtype(dtype).name, detail)
    return ok


def run_host_isolated(fn):
    """Run ``fn()`` on a fresh thread and return its result.

    JAX trace state is thread-local: the gate (and the tuner's
    measurements) may be reached from inside someone else's trace — an
    op resolving its kernel while a scan/jit body traces.  A worker
    thread gives these concrete example runs a clean eager context that
    no ambient trace can capture into its jaxpr.
    """
    box = {}

    def _work():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller's thread below
            box["error"] = e

    t = threading.Thread(target=_work, name="mxnet-kernels-eval")
    t.start()
    t.join()
    if "error" in box:
        raise box["error"]
    return box["value"]


def _gate_eval(spec, config, shape, dtype):
    rng = np.random.RandomState(0)
    args, kwargs = spec.example_inputs(shape, dtype, rng)
    rtol, atol = spec.tolerance(dtype)
    impl = spec.make(dict(config or {}))
    out_k, grads_k = _run(impl, args, kwargs, spec.grad_argnums)
    out_r, grads_r = _run(spec.reference, args, kwargs,
                          spec.grad_argnums)
    if not _close(out_k, out_r, rtol, atol):
        return False, "forward mismatch"
    for i, (gk, gr) in enumerate(zip(grads_k, grads_r)):
        if not _close(gk, gr, rtol, atol):
            return False, f"backward mismatch (arg {spec.grad_argnums[i]})"
    return True, ""


def _gate_once(spec, config, shape, dtype):
    try:
        return run_host_isolated(
            lambda: _gate_eval(spec, config, shape, dtype))
    except Exception as e:  # noqa: BLE001 — a crashing config is an ineligible config, not a crashed caller
        return False, f"{type(e).__name__}: {e}"


def gate_report(name, shape, dtype):
    """Gate every config in the spec's space; {config_key: bool}.  The
    smoke phase uses this to prove the whole grid is classifiable."""
    spec = get_spec(name)
    return {config_key(c): gate(name, c, shape, dtype)
            for c in spec.config_space(shape, dtype)}


def reset_gate_cache():
    with _lock:
        _GATE_CACHE.clear()
        _GATE_WARNED.clear()
