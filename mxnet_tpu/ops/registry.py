"""Operator registry.

TPU-native re-design of the NNVM op registry (reference:
include/mxnet/op_attr_types.h:124-294, src/operator/* NNVM_REGISTER_OP). In the
reference each op carries FInferShape/FInferType/FCompute<cpu|gpu>/FGradient
attributes; kernels are hand-written CUDA/mshadow. Here an op's ``fcompute`` is
a JAX emission (jax.numpy / lax / pallas):

- shape+dtype inference = ``jax.eval_shape`` over fcompute (always consistent
  with the kernel, unlike hand-written FInferShape);
- gradient = ``jax.vjp`` over fcompute (an op can override with a custom
  fgradient for numerically-better or cheaper rules);
- CPU/GPU/TPU dispatch = XLA backends — one registration covers all devices
  (the reference needs .cc + .cu per op);
- per-op kernel fusion/scheduling = XLA; the imperative path jit-caches each
  (op, attrs) pair so steady-state dispatch is a cache hit.

Op attrs are plain keyword arguments, normalised to a hashable canonical tuple
(the role dmlc::Parameter plays in the reference).
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from ..base import MXNetError

_OPS = {}


def _amp_cast(arrays, mode):
    """Input casting for mixed precision, applied INSIDE the op's traced
    function so jax.vjp transposes the casts (low-precision compute, full-
    precision gradient accumulation).  The '_amp' attr rides the jit-cache
    key, so amp-on and amp-off programs never collide.

    Role parity: src/nnvm/low_precision_pass.cc inserts amp_cast/
    amp_multicast nodes by allow/deny list; here the cast is attached at
    dispatch by mxnet_tpu.amp.
    """
    import jax.numpy as jnp
    low = jnp.bfloat16 if mode.endswith("bfloat16") else jnp.float16
    out = []
    for a in arrays:
        dt = getattr(a, "dtype", None)
        if dt is None or not jnp.issubdtype(a.dtype, jnp.floating):
            out.append(a)
        elif mode.startswith("low"):
            out.append(a.astype(low) if a.dtype == jnp.float32 else a)
        elif mode.startswith("f32"):
            out.append(a.astype(jnp.float32)
                       if a.dtype in (jnp.bfloat16, jnp.float16) else a)
        else:  # widest
            out.append(a)
    if mode.startswith("widest"):
        f = [a for a in out if getattr(a, "dtype", None) is not None and
             jnp.issubdtype(a.dtype, jnp.floating)]
        if f:
            widest = jnp.result_type(*[a.dtype for a in f])
            out = [a.astype(widest)
                   if getattr(a, "dtype", None) is not None and
                   jnp.issubdtype(a.dtype, jnp.floating) else a
                   for a in out]
    return tuple(out)


def _canon_attr(v):
    """Make an attr value hashable + jit-stable."""
    if isinstance(v, (list, tuple)):
        return tuple(_canon_attr(x) for x in v)
    if isinstance(v, np.ndarray):
        return tuple(v.ravel().tolist()) + ("__shape__",) + v.shape
    if isinstance(v, np.dtype):
        return v.name
    if isinstance(v, type) and issubclass(v, np.generic):
        return np.dtype(v).name
    return v


class Operator:
    """A registered operator.

    fcompute(attrs: dict, *inputs: jax.Array) -> jax.Array | tuple[jax.Array]
    """

    def __init__(self, name, fcompute, num_outputs=1, is_random=False,
                 mutate_aux=(), fgradient=None, alias=(), scalar_args=("scalar",),
                 num_visible=None, input_names=None, eager_only=False):
        self.name = name
        # the jax.named_scope its device ops run under (docs/observability.md)
        self.scope = "op/" + name
        self.fcompute = fcompute
        self.num_outputs = num_outputs
        # eager_only: op produces data-dependent (dynamic) shapes — legal in
        # eager jax, illegal under jit/trace. The imperative path runs it
        # unjitted; traced paths (CachedOp/executor) reject it with a clear
        # error. Parity: the reference's dynamic-shape FComputeEx ops
        # (contrib.boolean_mask, np_nonzero-class).
        self.eager_only = eager_only
        # outputs beyond num_visible are internal (parity: the reference's
        # FNumVisibleOutputs, e.g. box_nms hides its index record)
        self.num_visible = num_visible
        self.is_random = is_random
        self.mutate_aux = mutate_aux  # indices of inputs that receive updated state
        self.fgradient = fgradient
        self.alias = alias
        # names assigned, in order, to positional non-array args in the
        # generated imperative wrapper (e.g. nd.clip(x, 0, 1))
        self.scalar_args = scalar_args
        # declared input roles (FListInputNames parity). The symbol layer
        # auto-creates `{instance}_{suffix}` variables for trailing inputs
        # the user did not supply — reference behavior, e.g.
        # sym.FullyConnected(data, num_hidden=k) synthesizes fc_weight/
        # fc_bias. Tuple, or callable(attrs) -> tuple (no_bias handling).
        self.input_names = input_names
        self._jit_cache = {}

    def resolve_input_names(self, attrs):
        n = self.input_names
        if n is None:
            return None
        return tuple(n(attrs)) if callable(n) else tuple(n)

    # -- dynamic arity (multi-tensor ops: num_weights-driven) --------------
    def resolve_num_outputs(self, attrs):
        """Output count for given attrs. num_outputs may be an int, the
        name of an attr holding the count (e.g. split's "num_outputs"), or
        a callable(attrs) -> int (multi_sgd_*: 2*num_weights)."""
        n = self.num_outputs
        if isinstance(n, str):
            return int(attrs.get(n, 1))
        if callable(n):
            return int(n(attrs))
        return int(n)

    def resolve_mutate_aux(self, attrs):
        """Mutated-state input indices for given attrs; tuple or
        callable(attrs) -> tuple (multi_sgd_mom: one momentum per weight)."""
        ma = self.mutate_aux
        return tuple(ma(attrs)) if callable(ma) else tuple(ma)

    # -- compiled execution ------------------------------------------------
    def _call(self, attrs):
        """The one closure every path runs: ``fcompute`` under the scope
        ``op/<name>``, so the device ops it becomes carry the operator's
        name in their HLO metadata (``profiler.device_ops`` reads it back
        from a trace; docs/observability.md has the vocabulary)."""
        fcompute = self.fcompute
        amp_mode = attrs.get("_amp")
        clean = {k: v for k, v in attrs.items() if k != "_amp"}
        scope = self.scope

        def call(*arrays):
            if amp_mode:
                arrays = _amp_cast(arrays, amp_mode)
            with jax.named_scope(scope):
                return fcompute(clean, *arrays)

        return call

    def jitted(self, attrs_key, attrs):
        fn = self._jit_cache.get(attrs_key)
        if fn is None:
            fn = self._jit_cache[attrs_key] = jax.jit(self._call(attrs))
        return fn

    def bind(self, **attrs):
        """Return (jitted_fn, attrs_key) for the given attrs."""
        key = tuple(sorted((k, _canon_attr(v)) for k, v in attrs.items()))
        return self.jitted(key, attrs), key

    def raw(self, attrs):
        """Unjitted closure — used under jax.vjp (jax 0.9 cannot linearize
        some primitives, e.g. reduce_window, through an inner jit)."""
        return self._call(attrs)

    def grad_aware(self, attrs):
        """Compute closure that honors a registered custom ``fgradient``
        under jax transforms (jax.custom_vjp wrapper).

        The imperative tape applies fgradient itself (ndarray.py); every
        TRACED path — symbol executor, group2ctx runner, fused subgraph
        bodies — must use this so whole-graph jax.vjp picks up the custom
        rule instead of differentiating fcompute literally (e.g.
        SoftmaxOutput's forward is plain softmax; its training gradient
        is softmax - one_hot(label), reference softmax_output-inl.h).
        Wrappers are cached per canonical attrs key (this sits on the
        per-node hot loop of every executor forward)."""
        if self.fgradient is None:
            return self.raw(attrs)
        cache = getattr(self, "_grad_aware_cache", None)
        if cache is None:
            cache = self._grad_aware_cache = {}
        key = tuple(sorted((k, _canon_attr(v)) for k, v in attrs.items()))
        f = cache.get(key)
        if f is not None:
            return f
        base = self.raw(attrs)
        fg = self.fgradient
        clean = {k: v for k, v in attrs.items() if k != "_amp"}

        @jax.custom_vjp
        def f(*arrays):
            return base(*arrays)

        def fwd(*arrays):
            return f(*arrays), arrays

        def bwd(primals, cts):
            cts_t = tuple(cts) if isinstance(cts, (tuple, list)) else (cts,)
            # the forward's scope ends with ``base``: the custom rule's
            # device ops are the operator's too
            with jax.named_scope(self.scope):
                gs = fg(clean, primals, cts_t)
            import jax.numpy as jnp
            out = []
            for g, p in zip(gs, primals):
                if g is None:
                    g = jnp.zeros_like(p)
                elif hasattr(g, "dense"):
                    # SparseCot (row-sparse tape gradient, e.g. Embedding
                    # sparse_grad): custom_vjp needs dense jax cotangents;
                    # the traced-graph path has no sparse gradient storage
                    g = g.dense()
                out.append(g)
            return tuple(out)

        f.defvjp(fwd, bwd)
        cache[key] = f
        return f

    def infer(self, attrs, *avals):
        """Shape/dtype inference via abstract evaluation."""
        fn, _ = self.bind(**attrs)
        return jax.eval_shape(fn, *avals)

    def __repr__(self):
        return f"Operator({self.name})"


def register(name, num_outputs=1, is_random=False, mutate_aux=(),
             fgradient=None, alias=(), scalar_args=("scalar",),
             num_visible=None, input_names=None, eager_only=False):
    """Decorator: register fcompute under ``name`` (+ aliases)."""

    def deco(fcompute):
        op = Operator(name, fcompute, num_outputs=num_outputs,
                      is_random=is_random, mutate_aux=mutate_aux,
                      fgradient=fgradient, alias=alias, scalar_args=scalar_args,
                      num_visible=num_visible, input_names=input_names,
                      eager_only=eager_only)
        if name in _OPS:
            raise MXNetError(f"op {name} already registered")
        _OPS[name] = op
        for a in alias:
            _OPS[a] = op
        return fcompute

    return deco


def register_simple(name, fn, **kw):
    """Register an op whose fcompute ignores attrs: fn(*inputs)."""
    register(name, **kw)(lambda attrs, *ins: fn(*ins))


def get(name):
    op = _OPS.get(name)
    if op is None:
        raise MXNetError(f"operator {name} is not registered")
    return op


def exists(name):
    return name in _OPS


def list_ops():
    return sorted(_OPS)
