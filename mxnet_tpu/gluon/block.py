"""gluon.Block / HybridBlock.

Re-design of reference python/mxnet/gluon/block.py (Block:128,
HybridBlock:679) + src/imperative/cached_op.{h,cc}. The reference's
hybridize() traces the net into an nnvm graph and replays it through CachedOp
(static_alloc pre-plans memory and bulks engine pushes). TPU-native
equivalent: trace the *entire* forward — children included — into one jitted
XLA computation (parameters become traced inputs, BatchNorm moving stats and
other mutated state become extra outputs written back after each call). XLA
then owns memory planning, fusion and async dispatch, which is exactly the
role CachedOp::StaticForward plays in the reference (cached_op.cc:742).
"""
from __future__ import annotations

import contextlib
import copy
import math
import re
import threading
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from .. import autograd, ndarray as nd
from .. import random as _random
from ..base import MXNetError, np_dtype
from ..context import Context, cpu, current_context
from ..ndarray import NDArray
from ..ops import pallas_attention, pallas_kda
from .parameter import Parameter, ParameterDict, DeferredInitializationError
from .utils import _indent


# thread-local flag: set while tracing a CachedOp so nested HybridBlocks
# run their imperative path inside the parent's trace
_TRACING = threading.local()

# shared executor for cached-op pullbacks: the pullback is a pytree whose
# leaves are the residual arrays, so one jit covers every (block, signature)
# with the same residual structure. ``cts`` holds one cotangent per
# user-visible output of the block and nothing else: the parameters the
# forward mutated (BatchNorm's running statistics) left it as auxiliary
# outputs, which have no cotangent
_BWD_EXEC = jax.jit(lambda vjp_fn, cts: vjp_fn(cts))

# a pullback residual under this many bytes crosses the host packed with the
# others of its dtype: a program output costs the host per buffer, not per
# byte (0.06 ms each on a v5e's host, PERF.md §6, PR 33), and two thirds of
# a vision net's residuals are per-channel vectors and scalars
_RESIDUAL_PACK_BYTES = 64 * 1024


class _PackedPullback:
    """The pullback ``jax.vjp`` returned inside the recorded forward
    program, in the form it leaves that program: the residuals under
    ``_RESIDUAL_PACK_BYTES`` ravelled into one flat buffer per dtype
    (``packed``), every other one as it is (``large``).  A pytree with
    those buffers as leaves, so the forward program returns it and
    ``_BWD_EXEC`` takes it; its static data are the pullback's ``treedef``
    and, leaf by leaf, where the leaf travels (``slots``): ``(None, i)`` is
    ``large[i]``, ``(k, offset, shape)`` is ``shape`` elements of
    ``packed[k]`` from ``offset``.  Calling it (inside ``_BWD_EXEC``'s
    trace) slices the small leaves back out and calls the pullback."""

    __slots__ = ("large", "packed", "treedef", "slots")

    def __init__(self, large, packed, treedef, slots):
        self.large = large
        self.packed = packed
        self.treedef = treedef
        self.slots = slots

    @classmethod
    def pack(cls, vjp_fn):
        leaves, treedef = jax.tree_util.tree_flatten(vjp_fn)
        avals = [jax.typeof(leaf) for leaf in leaves]
        by_dtype = {}
        for i, aval in enumerate(avals):
            dtype = aval.dtype
            # a weakly typed leaf or an extended dtype (a random key)
            # would not come back from a concatenate as the type it was
            if (aval.size * dtype.itemsize < _RESIDUAL_PACK_BYTES
                    and not aval.weak_type
                    and (jnp.issubdtype(dtype, jnp.number)
                         or dtype == jnp.bool_)):
                by_dtype.setdefault(dtype, []).append(i)
        slots = [None] * len(leaves)
        packed = []
        for members in by_dtype.values():
            offset = 0
            for i in members:
                slots[i] = (len(packed), offset, avals[i].shape)
                offset += avals[i].size
            packed.append(jnp.concatenate(
                [jnp.ravel(leaves[i]) for i in members]))
        large = []
        for i, leaf in enumerate(leaves):
            if slots[i] is None:
                slots[i] = (None, len(large))
                large.append(leaf)
        return cls(tuple(large), tuple(packed), treedef, tuple(slots))

    def __call__(self, cts):
        leaves = []
        for slot in self.slots:
            if slot[0] is None:
                leaves.append(self.large[slot[1]])
            else:
                k, offset, shape = slot
                leaves.append(self.packed[k][
                    offset:offset + math.prod(shape)].reshape(shape))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)(cts)


jax.tree_util.register_pytree_node(
    _PackedPullback,
    lambda p: ((p.large, p.packed), (p.treedef, p.slots)),
    lambda static, buffers: _PackedPullback(*buffers, *static))

# thread-local: the layers a trace is to checkpoint one by one (remat_scope)
_REMAT = threading.local()
# what a boundary keeps of its layer besides the inputs, by name: the flash
# kernel's out and lse and the KDA kernel's o and entering states (a layer
# with neither kernel keeps nothing more).  ``parallel.spmd.remat_wrap``'s
# policy keeps the same names
KERNEL_RESIDUALS = (pallas_attention.FLASH_RESIDUALS
                    + pallas_kda.KDA_RESIDUALS)
_KEEP_KERNEL_OUTPUTS = jax.checkpoint_policies.save_only_these_names(
    *KERNEL_RESIDUALS)


def kernel_residuals_traced():
    """The named ``KERNEL_RESIDUALS`` the kernel calls this thread has
    traced so far emit: two a flash call, two a KDA call.  The difference
    across a boundary is what its policy keeps."""
    return (len(pallas_attention.FLASH_RESIDUALS)
            * pallas_attention.traced_calls()
            + len(pallas_kda.KDA_RESIDUALS) * pallas_kda.traced_calls())


class _RematScope:
    """The layers to checkpoint while a forward is traced, how many of
    them were (``boundaries``) and how many named residuals those keep
    (``saved_residuals``: ``KERNEL_RESIDUALS`` of each kernel call
    inside)."""

    def __init__(self, layers):
        self._layers = {id(b) for b in layers}
        self.boundaries = 0
        self.saved_residuals = 0

    def __contains__(self, block):
        return id(block) in self._layers

    def call(self, layer, args):
        """``layer(*args)`` under ``jax.checkpoint``: the layer's inputs
        and parameters, and the named outputs of each flash or KDA kernel
        call, are all that the backward keeps of it; everything else
        inside is computed again there.  The parameters go in as
        arguments, swapped into the layer for the call as ``_build_jit``
        does for the whole block."""
        flat, fmt = _flatten(args, "input")
        ctx = flat[0].ctx
        holders = [p.data(ctx) for p in layer.collect_params().values()
                   if p._data is not None]
        out_fmt = []

        def pure(param_arrays, input_arrays):
            saved = [d._data for d in holders]
            try:
                for d, a in zip(holders, param_arrays):
                    d._data = a
                args_re, _ = _regroup(
                    [NDArray(a, ctx) for a in input_arrays], fmt)
                flat_out, ofmt = _flatten(
                    layer._forward_unhybridized(*args_re), "output")
                if any(d._data is not a
                       for d, a in zip(holders, param_arrays)):
                    raise MXNetError(
                        f"remat: layer {layer.name} updates a parameter in "
                        "place (BatchNorm statistics); such a layer cannot "
                        "be a rematerialisation boundary")
                out_fmt[:] = [ofmt]
                return tuple(o._data for o in flat_out)
            finally:
                for d, a in zip(holders, saved):
                    d._data = a

        named = kernel_residuals_traced()
        outs = jax.checkpoint(pure, policy=_KEEP_KERNEL_OUTPUTS)(
            tuple(d._data for d in holders), tuple(a._data for a in flat))
        self.boundaries += 1
        self.saved_residuals += kernel_residuals_traced() - named
        return _regroup([NDArray(o, ctx) for o in outs], out_fmt[0])[0]


@contextlib.contextmanager
def remat_scope(layers):
    """While a forward is traced inside this scope, each call of one of
    ``layers`` is a rematerialisation boundary (``jax.checkpoint`` that
    saves the flash and KDA kernels' outputs by name, ``KERNEL_RESIDUALS``,
    and nothing else inside, so the backward recomputes the layer but not
    those kernels).
    Yields the scope; its ``boundaries`` counts the calls that were
    wrapped and ``saved_residuals`` the named residuals they keep.
    ``parallel.spmd.TrainStep(remat=True)`` opens it over the
    ``remat_layers`` a block declares."""
    prev = getattr(_REMAT, "scope", None)
    _REMAT.scope = scope = _RematScope(layers)
    try:
        yield scope
    finally:
        _REMAT.scope = prev


_CachedEntry = __import__("collections").namedtuple(
    "_CachedEntry",
    "jitted fwd_vjp_jit raw out_fmt_box mutated_idx_box param_list ctx "
    "arg_is_nd n_params")


class _BlockScope:
    """Name manager for Blocks (parity: block.py _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..name import NameManager
                prefix = NameManager._current_value().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        from ..name import NameManager
        self._name_scope = NameManager(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


def _flatten(args, inout_str):
    if isinstance(args, NDArray):
        return [args], int(0)
    if args is None:
        return [None], int(-1)
    assert isinstance(args, (list, tuple)), \
        f"{inout_str} must be (nested) NDArrays, got {type(args)}"
    flat = []
    fmts = []
    for i in args:
        arg, fmt = _flatten(i, inout_str)
        flat.extend(arg)
        fmts.append(fmt)
    return flat, fmts


def _regroup(args, fmt):
    if isinstance(fmt, int):
        if fmt == -1:
            return None, args[1:]
        if fmt == 0:
            return args[0], args[1:]
        return args[:fmt], args[fmt:]
    assert isinstance(args, (list, tuple)), "invalid regroup input"
    ret = []
    for i in fmt:
        res, args = _regroup(args, i)
        ret.append(res)
    return ret, args


class Block:
    """Base class for all neural network layers and models
    (parity: gluon/block.py:128)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = {}
        self._forward_pre_hooks = {}
        self._hook_counter = 0

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self.__dict__.items()
            if isinstance(block, Block))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)):
                raise TypeError(
                    f"Changing attribute type for {self.name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params, \
                "Overriding Parameter attribute %s is not allowed. " \
                "If you want to share parameters between blocks, please set " \
                "'params' at Block construction instead."
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _check_container_with_block(self):
        children = set(self._children.values())

        def _find_unregistered_block_in_container(data):
            if isinstance(data, (list, tuple)):
                return any(_find_unregistered_block_in_container(ele)
                           for ele in data)
            if isinstance(data, dict):
                return any(_find_unregistered_block_in_container(v)
                           for v in data.values())
            if isinstance(data, Block):
                return data not in children
            return False

        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and not k.startswith("__"):
                if _find_unregistered_block_in_container(v):
                    warnings.warn(
                        f'"{name_of(self)}" is an unregistered container with '
                        "Blocks. Note that Blocks inside the list, tuple or "
                        "dict will not be registered automatically. Make sure "
                        "to register them using register_child() or switching "
                        "to nn.Sequential/nn.HybridSequential instead.",
                        stacklevel=3)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Name scope managing child naming (parity: block.py name_scope)."""
        return self._scope

    @property
    def params(self):
        """This Block's parameter dictionary (no children)."""
        return self._params

    def collect_params(self, select=None):
        """All Parameters of this Block and its children
        (parity: block.py collect_params)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """Save parameters to file (parity: block.py:316)."""
        params = self._collect_params_with_prefix()
        if deduplicate:
            reverse_params = {v: k for k, v in params.items()}
            params = {v: k for k, v in reverse_params.items()}
        arg_dict = {key: val._reduce() for key, val in params.items()}
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        """Load parameters from file (parity: block.py:357)."""
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in i for i in loaded.keys()):
            # legacy loading: mx.nd.save(net.collect_params()) format
            del loaded
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix,
                cast_dtype=cast_dtype, dtype_source=dtype_source)
            return
        if not allow_missing:
            params_inv = {}
            for k, v in params.items():
                params_inv.setdefault(v, []).append(k)
            for name, param in params.items():
                assert any(p in loaded for p in params_inv[param]), \
                    (f"Parameter '{name}' is missing in file '{filename}', "
                     "which contains parameters: %s. Set allow_missing=True "
                     "to ignore missing parameters." % _brief_print_list(loaded.keys()))
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    f"Parameter '{name}' loaded from file '{filename}' is "
                    "not present in ParameterDict, which contains parameters "
                    "%s. Set ignore_extra=True to ignore."
                    % _brief_print_list(params.keys()))
            if name in params:
                params[name]._load_init(loaded[name], ctx,
                                        cast_dtype=cast_dtype,
                                        dtype_source=dtype_source)

    save_params = save_parameters
    load_params = load_parameters

    def register_child(self, block, name=None):
        """Register block as a child (parity: block.py register_child)."""
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = self._hook_counter
        self._hook_counter += 1
        self._forward_pre_hooks[handle] = hook
        return _HookHandle(self._forward_pre_hooks, handle)

    def register_forward_hook(self, hook):
        handle = self._hook_counter
        self._hook_counter += 1
        self._forward_hooks[handle] = hook
        return _HookHandle(self._forward_hooks, handle)

    def apply(self, fn):
        """Apply fn recursively to every child then self."""
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        """Initialize parameters of self and children
        (parity: block.py initialize)."""
        from .. import initializer as init_mod
        if init is None:
            init = init_mod.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        """Activate HybridBlocks recursively (no-op on plain Blocks)."""
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        """Cast parameters and children to dtype (parity: block.py cast)."""
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        """Call forward with pre/post hooks."""
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        """Override to implement computation."""
        raise NotImplementedError()

    def summary(self, *inputs):
        """Print summary of the network (parity: block.py summary)."""
        summary = {}
        seen = set()
        hooks = []

        def _get_shape_str(args):
            flat_args, _ = _flatten(args, "input")
            shapes = [x.shape if isinstance(x, NDArray) else None
                      for x in flat_args]
            return str(shapes[0] if len(shapes) == 1 else shapes)

        def _register_summary_hook(block):
            def _summary_hook(block, _, outputs):
                class_name = block.__class__.__name__
                block_idx = len(summary) - 1
                m_key = f"{class_name}-{block_idx + 1}"
                summary[m_key] = {"output_shape": _get_shape_str(outputs),
                                  "n_params": 0, "trainable": 0, "shared": 0}
                params = 0
                for p in block.params.values():
                    params += int(np.prod(p.shape))
                    summary[m_key]["trainable"] += \
                        0 if p.grad_req == "null" else int(np.prod(p.shape))
                    if p in seen:
                        summary[m_key]["shared"] += int(np.prod(p.shape))
                    else:
                        seen.add(p)
                summary[m_key]["n_params"] = params
            hooks.append(block.register_forward_hook(_summary_hook))

        summary["Input"] = {"output_shape": _get_shape_str(inputs),
                            "n_params": 0, "trainable": 0, "shared": 0}
        try:
            self.apply(_register_summary_hook)
            self(*inputs)
            line_format = "{:>20}  {:>42} {:>15}"
            print("-" * 80)
            print(line_format.format("Layer (type)", "Output Shape", "Param #"))
            print("=" * 80)
            total_params = 0
            trainable_params = 0
            shared_params = 0
            for layer, info in summary.items():
                print(line_format.format(layer, str(info["output_shape"]),
                                         info["n_params"]))
                total_params += info["n_params"]
                trainable_params += info["trainable"]
                shared_params += info["shared"]
            print("=" * 80)
            print(f"Parameters in forward computation graph, duplicate included")
            print(f"   Total params: {total_params}")
            print(f"   Trainable params: {trainable_params}")
            print(f"   Non-trainable params: {total_params - trainable_params}")
            print(f"Shared params in forward computation graph: {shared_params}")
            print(f"Unique parameters in model: {total_params - shared_params}")
            print("-" * 80)
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    def __init__(self, hooks, handle):
        self._hooks = hooks
        self._handle = handle

    def detach(self):
        self._hooks.pop(self._handle, None)


def name_of(b):
    return b.name


def _brief_print_list(lst, limit=7):
    lst = list(lst)
    if len(lst) > limit:
        return _brief_print_list(lst[:limit // 2], limit) + ", ..., " + \
            _brief_print_list(lst[-limit // 2:], limit)
    return ", ".join(f"'{s}'" for s in lst)


class HybridBlock(Block):
    """A Block that can be traced and compiled (parity: block.py:679).

    Non-hybridized: hybrid_forward runs imperatively, op by op (each op is an
    async XLA dispatch). Hybridized: the first call per (train-mode, input
    signature) traces the whole forward into one jitted XLA computation —
    the reference's CachedOp static path (cached_op.cc:742) re-imagined as
    trace-once/compile-once.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = ()
        self._flags = {}
        self._jit_cache = {}

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = dict(kwargs)
        self._clear_cached_op()
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def _clear_cached_op(self):
        self._jit_cache = {}
        self._cached_graph = ()

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                f"Children of HybridBlock must also be HybridBlock, but {block} "
                f"has type {type(block)}. If you are using Sequential, please "
                "try HybridSequential instead.")
        super().register_child(block, name)
        self._clear_cached_op()

    def infer_shape(self, *args):
        """Infer parameter shapes from inputs by abstract evaluation."""
        self._deferred_infer(args)

    def infer_type(self, *args):
        self._deferred_infer(args)

    def _deferred_infer(self, args):
        """Run forward abstractly so deferred-shape params get concrete shapes.

        Reference infers shapes through the traced symbol graph
        (block.py _infer_attrs); here a plain imperative dry-run under
        jax.eval_shape semantics would require concrete params, so each layer
        is responsible for calling param.shape = ... in its forward pre-step
        (see nn.basic_layers Dense etc.). This helper just triggers a forward
        on zero inputs with deferred init allowed.
        """
        raise NotImplementedError(
            "Shape inference on deferred parameters happens automatically at "
            "first forward; call the block on a real batch instead.")

    # -- the TPU CachedOp --------------------------------------------------
    def _trace_signature(self, args):
        flat, fmt = _flatten(args, "input")
        sig = tuple((a.shape, str(a.dtype)) if isinstance(a, NDArray) else None
                    for a in flat)
        return flat, fmt, (sig, autograd.is_training(), autograd.is_recording())

    def _build_jit(self, flat_args, fmt, params):
        """Build the jitted whole-forward function for one input signature."""
        param_list = list(params)
        n_params = len(param_list)
        ctx = None
        for a in flat_args:
            if isinstance(a, NDArray):
                ctx = a.ctx
                break
        ctx = ctx or current_context()
        arg_is_nd = [isinstance(a, NDArray) for a in flat_args]
        static_args = [None if is_nd else a
                       for a, is_nd in zip(flat_args, arg_is_nd)]
        self_block = self
        out_fmt_box = []
        mutated_idx_box = []

        def raw(key, param_arrays, input_arrays):
            # swap tracers into every param, run the imperative forward,
            # then restore; mutated params (BatchNorm stats) are detected by
            # buffer identity and returned as extra outputs.
            saved = []
            for p, arr in zip(param_list, param_arrays):
                d = p.data(ctx)
                saved.append((d, d._data))
                d._data = arr
            tracing_prev = getattr(_TRACING, "value", False)
            _TRACING.value = True
            try:
                it = iter(input_arrays)
                call_args = []
                for is_nd, st in zip(arg_is_nd, static_args):
                    if is_nd:
                        call_args.append(NDArray(next(it), ctx))
                    else:
                        call_args.append(st)
                args_re, rest = _regroup(call_args, fmt)
                assert not rest
                if not isinstance(args_re, (list, tuple)):
                    args_re = [args_re]
                with _random.trace_key_scope(key), autograd.pause(
                        train_mode=autograd.is_training()):
                    out = self_block._forward_unhybridized(*args_re)
                flat_out, ofmt = _flatten(out, "output")
                if not out_fmt_box:
                    out_fmt_box.append(ofmt)
                mutated = []
                for i, (d, _orig) in enumerate(saved):
                    if d._data is not param_arrays[i]:
                        mutated.append((i, d._data))
                if not mutated_idx_box:
                    mutated_idx_box.append([i for i, _ in mutated])
                return (tuple(o._data for o in flat_out),
                        tuple(v for _, v in mutated))
            finally:
                _TRACING.value = tracing_prev
                for (d, orig) in saved:
                    d._data = orig

        jitted = jax.jit(raw)
        # training path: one jitted computation returning (outputs, pullback,
        # mutated); the pullback (a pytree holding the residuals, the small
        # ones packed: _PackedPullback) is executed by the shared _BWD_EXEC
        # jit — fwd and bwd each compile exactly once per signature (parity:
        # CachedOp caches fwd and bwd graphs, cached_op.cc:904/1128). The
        # mutated parameters are the vjp's auxiliary outputs (parity:
        # CachedOp never differentiates auxiliary states), so the pullback
        # takes cotangents for the block's outputs only and keeps no
        # residual for the statistics
        def fwd_vjp(key, *arrays):
            outs, vjp_fn, mutated = jax.vjp(
                lambda *a: raw(key, a[:n_params], a[n_params:]), *arrays,
                has_aux=True)
            return outs, _PackedPullback.pack(vjp_fn), mutated

        fwd_vjp_jit = jax.jit(fwd_vjp)
        return _CachedEntry(jitted, fwd_vjp_jit, raw, out_fmt_box,
                            mutated_idx_box, param_list, ctx, arg_is_nd,
                            n_params)

    def _forward_unhybridized(self, *args):
        """The plain-Block forward path (imperative, op-by-op)."""
        ctx = None
        for a in _flatten(args, "input")[0]:
            if isinstance(a, NDArray):
                ctx = a.ctx
                break
        ctx = ctx or current_context()
        try:
            params = {i: j.data(ctx) for i, j in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer_and_init(args, ctx)
            params = {i: j.data(ctx) for i, j in self._reg_params.items()}
        return self.hybrid_forward(nd, *args, **params)

    def _deferred_infer_and_init(self, args, ctx):
        """Infer deferred param shapes, then finish init.

        The reference does this with symbolic shape inference
        (block.py:_deferred_infer_shape). Here each layer implements
        ``_shape_hint(inputs)`` when it supports deferred shapes.
        """
        hint = getattr(self, "_shape_hint", None)
        if hint is not None:
            hint(*args)
        for p in self._reg_params.values():
            p._finish_deferred_init()

    def _forward_symbolic(self, x, *args):
        """Symbolic tracing path: inputs are Symbols, params become sym vars
        (parity: the reference's deferred-symbol trace in _build_cache,
        block.py:756)."""
        from .. import symbol as sym_mod
        params = {i: j.var() for i, j in self._reg_params.items()}
        return self.hybrid_forward(sym_mod, x, *args, **params)

    def forward(self, x, *args):
        """Forward: dispatch symbolic trace, hybridized (jit), or imperative."""
        from ..symbol.symbol import Symbol as _Sym
        if isinstance(x, _Sym):
            return self._forward_symbolic(x, *args)
        if not self._active or getattr(_TRACING, "value", False):
            scope = getattr(_REMAT, "scope", None)
            if scope is not None and self in scope:
                return scope.call(self, (x,) + args)
            return self._forward_unhybridized(x, *args)

        from .. import telemetry as _telemetry
        recording = autograd.is_recording()
        with _telemetry.span("gluon/cached_op/prepare"):
            all_args = (x,) + args
            flat, fmt, key = self._trace_signature(all_args)
            entry = self._jit_cache.get(key)
            if entry is None:
                # one imperative dry-run finishes any deferred param init
                needs_dry_run = any(
                    p._data is None
                    for p in self.collect_params().values())
                if needs_dry_run:
                    with autograd.pause(train_mode=autograd.is_training()):
                        self._forward_unhybridized(x, *args)
                params = [p for p in self.collect_params().values()
                          if p._data is not None]
                entry = self._build_jit(flat, fmt, params)
                self._jit_cache[key] = entry
            (jitted, fwd_vjp_jit, _raw, out_fmt_box, mutated_idx_box,
             param_list, ctx, arg_is_nd, n_params) = entry

            key_arr = _random.next_key()
            if recording:
                nd_inputs = [p.data(ctx) for p in param_list] + \
                    [a for a, is_nd in zip(flat, arg_is_nd) if is_nd]
                arrays = [i._data for i in nd_inputs]
            else:
                param_arrays = tuple(p.data(ctx)._data for p in param_list)
                input_arrays = tuple(
                    a._data for a, is_nd in zip(flat, arg_is_nd) if is_nd)

        if recording:
            # one tape node for the whole block: compiled forward returns the
            # pullback (parity: CachedOp::Backward replays one cached graph)
            with _telemetry.span("gluon/cached_op/dispatch"):
                outs, vjp_fn, mutated = fwd_vjp_jit(key_arr, *arrays)
                if _telemetry.enabled():
                    _telemetry.record_cached_op_aux_outputs(len(mutated))
                    _telemetry.record_cached_op_residuals(
                        len(vjp_fn.slots),
                        len(vjp_fn.large) + len(vjp_fn.packed))
            results = [NDArray(o, ctx) for o in outs]
            self._apply_mutation(mutated_idx_box, param_list, mutated, ctx)

            import weakref

            def vjp_user(cts, _vjp=vjp_fn):
                return _BWD_EXEC(_vjp, cts if isinstance(cts, tuple)
                                 else (cts,))

            node = autograd.TapeNode(
                f"CachedOp_{self.name}", nd_inputs,
                [weakref.ref(r) for r in results],
                vjp_user, len(results), None,
                out_avals=[(r.shape, r.dtype) for r in results])
            for r in results:
                r._autograd_node = node
            tape = autograd.get_tape()
            if tape is not None:
                tape.append(node)
        else:
            from ..ndarray.ndarray import _profiler_running
            _prof_t0 = None
            if _profiler_running():
                import time as _time
                _prof_t0 = _time.perf_counter()
            with _telemetry.span("gluon/cached_op/dispatch"):
                outs, mutated = jitted(key_arr, param_arrays, input_arrays)
            if _prof_t0 is not None:
                # profile the jit path too (the round-2 profiler missed
                # it): one record per compiled-forward invocation,
                # blocking so the duration is device time
                from .. import profiler as _prof
                _prof.record_synced(f"CachedOp_{self.name}", _prof_t0,
                                    outs)
            results = [NDArray(o, ctx) for o in outs]
            self._apply_mutation(mutated_idx_box, param_list, mutated, ctx)

        out, _ = _regroup(results, out_fmt_box[0])
        return out

    def _apply_mutation(self, mutated_idx_box, param_list, mutated, ctx):
        if mutated_idx_box and mutated_idx_box[0]:
            for idx, new_val in zip(mutated_idx_box[0], mutated):
                param_list[idx].data(ctx)._set_data(new_val)

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Override to implement computation; F is the op namespace."""
        raise NotImplementedError()

    def _build_sym_graph(self, num_inputs=1):
        """Trace this block into a Symbol graph (inputs named data/data0…)."""
        from .. import symbol as sym_mod
        if num_inputs == 1:
            inputs = [sym_mod.var("data")]
        else:
            inputs = [sym_mod.var(f"data{i}") for i in range(num_inputs)]
        out = self(*inputs)
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        self._cached_graph = (inputs, out)
        return self._cached_graph

    def export(self, path, epoch=0):
        """Export model as symbol json + params (parity: block.py:877)."""
        if not self._cached_graph:
            self._build_sym_graph()
        _, sym = self._cached_graph
        sym.save(f"{path}-symbol.json")
        arg_names = set(sym.list_arguments())
        aux_names = set(sym.list_auxiliary_states())
        arg_dict = {}
        for name, param in self.collect_params().items():
            if name in arg_names:
                arg_dict[f"arg:{name}"] = param._reduce()
            elif name in aux_names:
                arg_dict[f"aux:{name}"] = param._reduce()
        nd.save(f"{path}-{epoch:04d}.params", arg_dict)


class SymbolBlock(HybridBlock):
    """Construct a block from a symbol (parity: block.py:961).

    Implemented in the symbol milestone; imports kept here so
    ``gluon.SymbolBlock`` resolves.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=None)
        # parameters keep their symbol names verbatim (parity: block.py:1050
        # sets prefix='' so loaded checkpoints bind by original name)
        self._prefix = ""
        self._params = ParameterDict("", params)
        from ..symbol import Symbol
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        self._sym_outputs = outputs
        self._sym_inputs = inputs
        input_names = {i.name for i in inputs}
        # bind free variables of the symbol as this block's parameters
        for name in outputs.list_arguments():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            if name not in input_names:
                self.params.get(name, grad_req="null",
                                allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(i) for i in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.collect_params().load(param_file, ctx=ctx, cast_dtype=True,
                                      dtype_source="saved")
        return ret

    def forward(self, x, *args):
        from ..symbol.executor import Executor
        from ..symbol.symbol import Symbol as _Sym
        if isinstance(x, _Sym):
            # symbolic composition: splice inputs into the stored graph
            raise NotImplementedError(
                "symbolic re-composition of SymbolBlock is not yet supported")
        ctx = x.ctx if isinstance(x, NDArray) else current_context()
        arg_names = set(self._sym_outputs.list_arguments())
        aux_names = set(self._sym_outputs.list_auxiliary_states())
        arg_dict, aux_dict = {}, {}
        for inp, val in zip(self._sym_inputs, (x,) + args):
            arg_dict[inp.name] = val
        for name, p in self.collect_params().items():
            if name in aux_names:
                aux_dict[name] = p.data(ctx)
            elif name in arg_names:
                arg_dict[name] = p.data(ctx)
        ex = self._sb_executor = getattr(self, "_sb_executor", None) or \
            Executor(self._sym_outputs, ctx, arg_dict, None, "null", aux_dict)
        # refresh input bindings (cheap: rebind dict entries)
        for k, v in arg_dict.items():
            ex.arg_dict[k] = v
        ex.arg_arrays = [ex.arg_dict.get(n)
                         for n in self._sym_outputs.list_arguments()]
        outs = ex.forward(is_train=autograd.is_training())
        return outs[0] if len(outs) == 1 else outs

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError()
