"""NDArray: the imperative tensor.

Re-design of reference include/mxnet/ndarray.h + src/ndarray/ndarray.cc.
There, an NDArray is a Chunk (engine var + Storage handle) and every op is an
async engine push; here it wraps an immutable ``jax.Array`` whose dispatch is
already async under PJRT. Mutation (``a[:]=``, in-place optimizer updates,
``kWriteTo``) is modelled as swap-the-buffer + bump the engine var version —
XLA's buffer donation reuses the memory when profitable, which is the TPU
equivalent of the reference's in-place/kAddTo planning (SURVEY.md §7 hard
part 1). Views (basic slices) remember their base and write back through it.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .. import autograd, engine
from .. import random as _random
from ..base import MXNetError, check_int32_range, check_shape_int32, np_dtype
from ..context import Context, cpu, current_context
from ..ops import registry as _registry

# ops whose compute depends on autograd train/predict mode
_TRAINING_ATTR_OPS = {"Dropout", "BatchNorm", "_contrib_SyncBatchNorm"}


class _TraceHooks(__import__("threading").local):
    """Closure-capture hooks for control-flow tracing (ndarray/contrib.py).

    capture: dict filled with grad-requiring NDArrays whose concrete
             buffers an op touches during a discovery trace — these are the
             loop body's free variables that must be lifted to explicit
             differentiation inputs (the reference lifts subgraph free vars
             as extra op inputs, control_flow.cc).
    subst:   id(NDArray) -> tracer, consulted at op dispatch so a retrace
             sees those free variables as function inputs.
    """

    def __init__(self):
        self.capture = None
        self.subst = None


_trace_hooks = _TraceHooks()

_amp_mod = None


def _amp_mode_for(op_name):
    """Dispatch-time AMP routing (lazy import; no-op until amp.init())."""
    global _amp_mod
    if _amp_mod is None:
        from .. import amp as _a
        _amp_mod = _a
    return _amp_mod.amp_mode_for(op_name)


class NDArray:
    __array_priority__ = 1000.0

    __slots__ = ("_data", "_ctx", "_var", "_grad", "_grad_req",
                 "_autograd_node", "_base", "_view_index", "__weakref__")

    def __init__(self, data, ctx=None, _base=None, _view_index=None):
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self._var = engine.Var()
        self._grad = None
        self._grad_req = "null"
        self._autograd_node = None
        self._base = _base
        self._view_index = _view_index

    # -- core properties ---------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def context(self):
        return self._ctx

    @property
    def ctx(self):
        return self._ctx

    @property
    def stype(self):
        return "default"

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return transpose(self)

    @property
    def version(self):
        return self._var.version

    # -- mutation ----------------------------------------------------------
    def _set_data(self, new_data):
        if self._base is not None:
            base = self._base
            base._set_data(base._data.at[self._view_index].set(new_data))
            self._data = base._data[self._view_index]
        else:
            self._data = new_data
        self._var.bump()
        return self

    def _mark_variable(self, grad, req):
        self._grad = grad
        self._grad_req = req

    def attach_grad(self, grad_req="write", stype=None):
        """Parity: ndarray.py attach_grad — allocate grad buffer + mark.

        stype='row_sparse' keeps the gradient row-sparse end-to-end
        (Embedding sparse_grad / sparse linear models): backward writes a
        RowSparseNDArray holding only the touched rows."""
        if stype == "row_sparse":
            from . import sparse as _sp
            g = _sp.zeros("row_sparse", self.shape, ctx=self._ctx,
                          dtype=self.dtype)
        else:
            g = zeros(self.shape, ctx=self._ctx, dtype=self.dtype)
        autograd.mark_variables([self], [g], grad_req)

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- sync points (parity: WaitToRead / asnumpy).  Async device
    # failures surface HERE as MXNetError — the reference's contract
    # (threaded_engine.cc:422-451 rethrows captured opr exceptions at
    # WaitToRead/WaitForAll), not a raw XLA error at a random later op.
    def wait_to_read(self):
        try:
            self._data.block_until_ready()
        except MXNetError:
            raise
        except Exception as e:
            raise MXNetError(
                f"async operator execution failed (surfaced at "
                f"wait_to_read): {e}") from e

    def asnumpy(self):
        t0 = None
        from .. import profiler as _prof
        if _prof.is_running() and (_prof.KWARGS["profile_api"]
                                   or _prof.KWARGS["profile_all"]):
            import time as _time
            t0 = _time.perf_counter()
        try:
            out = np.asarray(self._data)
        except MXNetError:
            raise
        except Exception as e:
            raise MXNetError(
                f"async operator execution failed (surfaced at "
                f"asnumpy): {e}") from e
        if t0 is not None:
            import time as _time
            _prof.record_api("MXNDArraySyncCopyToCPU",
                             (_time.perf_counter() - t0) * 1e6)
        return out

    def __array__(self, dtype=None, copy=None):
        # without this, np.asarray(ndarray) walks __getitem__ element by
        # element — one jax dispatch per scalar
        if copy is False:
            # numpy-2 contract: a zero-copy view of device memory is
            # impossible; raising lets np.asarray(..., copy=False) fail
            # loudly instead of handing back a throwaway buffer
            raise ValueError(
                "NDArray device data cannot be aliased as a numpy array "
                "without a copy")
        arr = self.asnumpy()
        return arr.astype(dtype) if dtype is not None else arr

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    # -- conversion / placement -------------------------------------------
    def astype(self, dtype, copy=True):
        return invoke("cast", [self], {"dtype": np_dtype(dtype).name})

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device), ctx)

    def as_in_ctx(self, ctx):
        return self.as_in_context(ctx)

    def copyto(self, other):
        if isinstance(other, Context):
            return self.as_in_context(other)
        other._set_data(jax.device_put(self._data, other._ctx.jax_device))
        return other

    def copy(self):
        return NDArray(jnp.array(self._data), self._ctx)

    def tolist(self):
        return self.asnumpy().tolist()

    # -- DLPack protocol (parity: ndarray.py:2236 to_dlpack_for_read;
    # the protocol form lets torch.from_dlpack(nd_array) work directly) --
    def __dlpack__(self, **kwargs):
        # pass the full DLPack-2023 surface (max_version/dl_device/copy/
        # stream) through to the backing jax array
        self.wait_to_read()  # sync-point contract: MXNetError on failure
        return self._data.__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    def to_dlpack_for_read(self):
        from .utils import to_dlpack_for_read
        return to_dlpack_for_read(self)

    def to_dlpack_for_write(self):
        from .utils import to_dlpack_for_write
        return to_dlpack_for_write(self)

    # -- shape ops ---------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        check_shape_int32(shape, allow_wildcards=True, what="reshaped")
        return invoke("reshape", [self], {"shape": tuple(shape)})

    def reshape_like(self, other):
        return invoke("reshape_like", [self, other], {})

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def flatten(self):
        return invoke("flatten", [self], {})

    def transpose(self, axes=None):
        return invoke("transpose", [self], {"axes": axes})

    def swapaxes(self, dim1, dim2):
        return invoke("swapaxes", [self], {"dim1": dim1, "dim2": dim2})

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return invoke("broadcast_like", [self, other], {})

    def tile(self, reps):
        return invoke("tile", [self], {"reps": tuple(reps)})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("split", [self], {"num_outputs": num_outputs, "axis": axis,
                                        "squeeze_axis": squeeze_axis})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, _as_nd(indices, self._ctx)],
                      {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kw):
        return invoke("one_hot", [self], {"depth": depth, **kw})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", [self, _as_nd(index, self._ctx)],
                      {"axis": axis, "keepdims": keepdims})

    # -- reductions --------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None):
        return invoke("argmax", [self], {"axis": axis})

    def argmin(self, axis=None):
        return invoke("argmin", [self], {"axis": axis})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke("topk", [self], {"axis": axis, "k": k, "ret_typ": ret_typ,
                                       "is_ascend": is_ascend})

    def clip(self, a_min, a_max):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke("abs", [self], {})

    def sign(self):
        return invoke("sign", [self], {})

    def sqrt(self):
        return invoke("sqrt", [self], {})

    def square(self):
        return invoke("square", [self], {})

    def exp(self):
        return invoke("exp", [self], {})

    def log(self):
        return invoke("log", [self], {})

    def sigmoid(self):
        return invoke("sigmoid", [self], {})

    def tanh(self):
        return invoke("tanh", [self], {})

    def relu(self):
        return invoke("relu", [self], {})

    def softmax(self, axis=-1):
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", [self], {"axis": axis})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return invoke("dot", [self, other],
                      {"transpose_a": transpose_a, "transpose_b": transpose_b})

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sp
        return _sp.cast_storage(self, stype)

    # -- arithmetic dunders ------------------------------------------------
    def _binary(self, other, op, scalar_op):
        if isinstance(other, NDArray):
            return invoke(op, [self, other], {})
        return invoke(scalar_op, [self], {"scalar": float(other)})

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        if isinstance(o, NDArray):
            return invoke("broadcast_sub", [o, self], {})
        return invoke("_rminus_scalar", [self], {"scalar": float(o)})

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        if isinstance(o, NDArray):
            return invoke("broadcast_div", [o, self], {})
        return invoke("_rdiv_scalar", [self], {"scalar": float(o)})

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        if isinstance(o, NDArray):
            return invoke("broadcast_mod", [o, self], {})
        return invoke("_rmod_scalar", [self], {"scalar": float(o)})

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return invoke("_rpower_scalar", [self], {"scalar": float(o)})

    def __neg__(self):
        return invoke("negative", [self], {})

    def __abs__(self):
        return invoke("abs", [self], {})

    def __eq__(self, o):
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # in-place: swap buffer (engine var bumped; XLA donates when possible)
    def __iadd__(self, o):
        res = self + o
        return self._set_data(res._data)

    def __isub__(self, o):
        res = self - o
        return self._set_data(res._data)

    def __imul__(self, o):
        res = self * o
        return self._set_data(res._data)

    def __itruediv__(self, o):
        res = self / o
        return self._set_data(res._data)

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            return invoke("take", [self, key], {"axis": 0, "mode": "clip"})
        if isinstance(key, (int, np.integer)):
            n = self._data.shape[0] if self._data.ndim else 0
            if not -n <= key < n:
                # jax clamps out-of-range indices; without this check,
                # iterating an NDArray never terminates (the iteration
                # protocol probes __getitem__ until IndexError)
                raise IndexError(
                    f"index {key} is out of bounds for axis 0 with "
                    f"size {n}")
            return NDArray(self._data[key], self._ctx, _base=self, _view_index=key)
        if key == slice(None):
            return self
        if isinstance(key, (slice, tuple)):
            return NDArray(self._data[key], self._ctx, _base=self, _view_index=key)
        raise MXNetError(f"unsupported index {key!r}")

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            v = value._data
            if value._ctx != self._ctx and \
                    not isinstance(v, jax.core.Tracer):
                # x[:] = y copies INTO x's storage (parity: CopyFromTo
                # across contexts).  Adopting y's buffer where it lives
                # left a cpu-context array holding a tpu buffer, and the
                # next jit over it refused the mixed devices
                v = jax.device_put(v, self._ctx.jax_device)
        else:
            v = jnp.asarray(value, dtype=self.dtype)
        if key == slice(None):
            if getattr(v, "shape", None) != self._data.shape:
                v = jnp.broadcast_to(v, self._data.shape).astype(self.dtype)
            self._set_data(v.astype(self.dtype))
        else:
            if not isinstance(self._data, jax.core.Tracer):
                from .. import profiler as _prof
                _prof.record_dispatch("op")
            self._set_data(self._data.at[key].set(v))

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>"


# --------------------------------------------------------------------------
def _profiler_running():
    import sys
    prof = sys.modules.get("mxnet_tpu.profiler")
    return prof is not None and prof.is_running()


def _as_nd(x, ctx=None):
    if isinstance(x, NDArray):
        return x
    return array(x, ctx=ctx)


def invoke(op, inputs, attrs, out=None):
    """The imperative op entry point.

    Parity: MXImperativeInvokeEx → Imperative::Invoke → PushFCompute
    (SURVEY.md §3.1). Here: jit-cache lookup → async XLA dispatch → optional
    tape record (jax.vjp pullback stored on the tape node).
    """
    if isinstance(op, str):
        op = _registry.get(op)
    attrs = {k: v for k, v in attrs.items() if v is not None}
    if op.name in _TRAINING_ATTR_OPS:
        attrs["_training"] = autograd.is_training()
    amp_mode = _amp_mode_for(op.name)
    if amp_mode is not None:
        attrs["_amp"] = amp_mode

    _prof_t0 = None
    if _profiler_running():
        import time as _time
        _prof_t0 = _time.perf_counter()

    nd_inputs = [i for i in inputs if isinstance(i, NDArray)]
    hooks = _trace_hooks
    if hooks.subst is None and hooks.capture is None:
        arrays = [i._data for i in inputs]
    else:
        arrays = []
        for i in inputs:
            a = i._data if isinstance(i, NDArray) else i
            if isinstance(i, NDArray):
                if hooks.subst is not None:
                    a = hooks.subst.get(id(i), a)
                if hooks.capture is not None and \
                        not isinstance(a, jax.core.Tracer) and \
                        (i._grad is not None or
                         i._autograd_node is not None):
                    hooks.capture[id(i)] = i
            arrays.append(a)
    if op.is_random:
        arrays = [_random.next_key()] + arrays

    # inside an outer trace (CachedOp jit / vjp / shard_map): emit raw ops so
    # the outer transform sees the primitives directly (jax 0.9 cannot
    # linearize e.g. reduce_window through an inner jit) and trace time stays
    # flat
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        if op.eager_only:
            raise MXNetError(
                f"operator {op.name} has data-dependent output shapes and "
                "cannot be traced/hybridized (reference analog: dynamic-"
                "shape FComputeEx ops); call it imperatively")
        fn = op.raw(attrs)
    elif op.eager_only:
        fn = op.raw(attrs)  # dynamic output shapes: run un-jitted
    else:
        fn, _ = op.bind(**attrs)
    recording = autograd.is_recording()
    try:
        if recording and op.fgradient is not None:
            # op declares a custom gradient rule (parity: FGradient attr)
            outs = fn(*arrays)
            prims = tuple(arrays[1:] if op.is_random else arrays)

            def vjp_fn(cts, _op=op, _attrs=dict(attrs), _prims=prims):
                cts_t = cts if isinstance(cts, tuple) else (cts,)
                return _op.fgradient(_attrs, _prims, cts_t)
        elif recording and op.eager_only:
            # jax.vjp would abstractly trace the dynamic-shape body;
            # eager_only ops must declare an explicit fgradient to train
            raise MXNetError(
                f"operator {op.name} has data-dependent output shapes and "
                "no gradient rule; it cannot be recorded for autograd")
        elif recording:
            outs, vjp_fn = jax.vjp(op.raw(attrs), *arrays)
        else:
            outs = fn(*arrays)
            vjp_fn = None
    except MXNetError:
        raise
    except Exception as e:  # surface XLA/tracing errors as framework errors
        raise MXNetError(f"error in operator {op.name}: {e}") from e

    single = not isinstance(outs, (tuple, list))
    outs = (outs,) if single else tuple(outs)

    if not isinstance(outs[0], jax.core.Tracer):
        # dispatches-per-step lane (profiler.record_dispatch): one eager op =
        # one XLA computation launch; traced calls are someone else's
        from .. import profiler as _prof
        _prof.record_dispatch("op")

    if _prof_t0 is not None:
        import time as _time
        from .. import profiler as _prof
        # block so the recorded duration covers DEVICE execution, not
        # just async dispatch (the round-2 profiler only saw dispatch);
        # serialisation under profiling matches the reference's
        # per-opr ProfileOperator wrapping (threaded_engine.cc:288)
        if _prof.device_sync_enabled():
            try:
                jax.block_until_ready(
                    [o for o in outs if not isinstance(o, jax.core.Tracer)])
            except Exception:
                pass  # the error re-surfaces at the user's sync point
        _prof.record_op(op.name, (_time.perf_counter() - _prof_t0) * 1e6)

    ctx = nd_inputs[0]._ctx if nd_inputs else current_context()
    mutate_aux = op.resolve_mutate_aux(attrs)
    n_aux = len(mutate_aux)
    n_user = len(outs) - n_aux

    # write mutated aux state back into the input NDArrays (e.g. BatchNorm
    # moving stats, optimizer momenta) — reference does this in-place
    for j, in_idx in enumerate(mutate_aux):
        tgt = inputs[in_idx]
        if isinstance(tgt, NDArray):
            tgt._set_data(outs[n_user + j])

    user_outs = outs[:n_user]
    results = []
    if out is not None:
        out_list = out if isinstance(out, (list, tuple)) else [out]
        for o, val in zip(out_list, user_outs):
            o._set_data(val)
            results.append(o)
    else:
        results = [NDArray(o, ctx) for o in user_outs]
    engine.get().on_compute(results)

    if recording and vjp_fn is not None:
        import weakref
        if op.is_random and op.fgradient is None:
            inner = vjp_fn

            def vjp_no_key(cts, _inner=inner):
                return _inner(cts)[1:]
            vjp_use = vjp_no_key
        else:
            vjp_use = vjp_fn
        if n_aux or out is not None:
            # tape sees only user outputs; aux outputs get zero cotangents
            full_vjp = vjp_use

            def vjp_user(cts, _f=full_vjp, _outs=outs, _n=n_user):
                cts_t = cts if isinstance(cts, tuple) else (cts,)
                padded = tuple(cts_t) + tuple(
                    jnp.zeros_like(o) for o in _outs[_n:])
                return _f(padded if len(padded) > 1 else padded[0])
            vjp_use = vjp_user
        node = autograd.TapeNode(
            op.name, nd_inputs,
            [weakref.ref(r) for r in results],
            vjp_use, n_user, attrs,
            out_avals=[(r.shape, r.dtype) for r in results])
        for r in results:
            r._autograd_node = node
        tape = autograd.get_tape()
        if tape is not None:
            tape.append(node)

    visible = results if op.num_visible is None else results[:op.num_visible]
    if len(visible) == 1:
        return visible[0]
    return visible


# -- creation --------------------------------------------------------------
def array(source, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(source, NDArray):
        src = source._data
        if dtype is not None:
            src = src.astype(np_dtype(dtype))
        return NDArray(jax.device_put(src, ctx.jax_device), ctx)
    is_np = isinstance(source, np.ndarray)
    a = np.asarray(source)
    check_int32_range(a.size, "array size")
    if dtype is None:
        # parity: lists default to float32; numpy arrays keep their dtype
        # (float64 narrowed — TPUs have no f64 by default)
        dtype = a.dtype if (is_np and a.dtype != np.float64) else np.float32
    a = a.astype(np_dtype(dtype), copy=False)
    return NDArray(jax.device_put(a, ctx.jax_device), ctx)


def _creation(opname, shape, ctx, dtype, **extra):
    ctx = ctx or current_context()
    if isinstance(shape, (int, np.integer)):
        shape = (shape,)
    check_shape_int32(shape)
    attrs = {"shape": tuple(shape), "dtype": np_dtype(dtype).name, **extra}
    op = _registry.get(opname)
    fn, _ = op.bind(**attrs)
    with jax.default_device(ctx.jax_device):
        data = fn()
    return NDArray(jax.device_put(data, ctx.jax_device), ctx)


def zeros(shape, ctx=None, dtype=None, stype=None, **kwargs):
    if stype not in (None, "default"):
        from . import sparse as _sp
        return _sp.zeros(stype, shape, ctx=ctx, dtype=dtype)
    return _creation("_zeros", shape, ctx, dtype)


def ones(shape, ctx=None, dtype=None, **kwargs):
    return _creation("_ones", shape, ctx, dtype)


def full(shape, val, ctx=None, dtype=None):
    return _creation("_full", shape, ctx, dtype, value=val)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def zeros_like(a):
    return invoke("zeros_like", [a], {})


def ones_like(a):
    return invoke("ones_like", [a], {})


def eye(N, M=0, k=0, ctx=None, dtype=None):
    ctx = ctx or current_context()
    op = _registry.get("_eye")
    fn, _ = op.bind(N=N, M=M, k=k, dtype=np_dtype(dtype).name)
    return NDArray(jax.device_put(fn(), ctx.jax_device), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    ctx = ctx or current_context()
    op = _registry.get("_arange")
    fn, _ = op.bind(start=start, stop=stop, step=step, repeat=repeat,
                    dtype=np_dtype(dtype or "float32").name)
    return NDArray(jax.device_put(fn(), ctx.jax_device), ctx)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype=None):
    ctx = ctx or current_context()
    op = _registry.get("_linspace")
    fn, _ = op.bind(start=start, stop=stop, num=num, endpoint=endpoint,
                    dtype=np_dtype(dtype or "float32").name)
    return NDArray(jax.device_put(fn(), ctx.jax_device), ctx)


# -- free functions over ops ------------------------------------------------
def concat(*arrays, dim=1):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return invoke("concat", list(arrays), {"dim": dim})


def stack(*arrays, axis=0):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return invoke("stack", list(arrays), {"axis": axis})


def dot(a, b, transpose_a=False, transpose_b=False):
    from .sparse import CSRNDArray, RowSparseNDArray, _sparse_dot
    if isinstance(a, (CSRNDArray, RowSparseNDArray)) or \
            isinstance(b, (CSRNDArray, RowSparseNDArray)):
        return _sparse_dot(a, b, transpose_a, transpose_b)
    return invoke("dot", [a, b], {"transpose_a": transpose_a,
                                  "transpose_b": transpose_b})


def transpose(a, axes=None):
    return invoke("transpose", [a], {"axes": axes})


def waitall():
    from .. import profiler as _prof
    if _prof.is_running():
        import time as _time
        t0 = _time.perf_counter()
        engine.wait_for_all()
        _prof.record_api("MXNDArrayWaitAll",
                         (_time.perf_counter() - t0) * 1e6)
    else:
        engine.wait_for_all()


def moveaxis(a, source, destination):
    axes = list(range(a.ndim))
    axes.insert(destination % a.ndim, axes.pop(source % a.ndim))
    return invoke("transpose", [a], {"axes": tuple(axes)})


def maximum(lhs, rhs):
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return invoke("broadcast_maximum", [lhs, rhs], {})
    if isinstance(lhs, NDArray):
        return invoke("_maximum_scalar", [lhs], {"scalar": float(rhs)})
    if isinstance(rhs, NDArray):
        return invoke("_maximum_scalar", [rhs], {"scalar": float(lhs)})
    return max(lhs, rhs)  # both python scalars (parity: _ufunc_helper)


def minimum(lhs, rhs):
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return invoke("broadcast_minimum", [lhs, rhs], {})
    if isinstance(lhs, NDArray):
        return invoke("_minimum_scalar", [lhs], {"scalar": float(rhs)})
    if isinstance(rhs, NDArray):
        return invoke("_minimum_scalar", [rhs], {"scalar": float(lhs)})
    return min(lhs, rhs)


def add_n(*args):
    """Sum of N arrays (reference: elemwise_sum.cc ElementWiseSum)."""
    if len(args) == 1 and isinstance(args[0], (list, tuple)):
        args = args[0]
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out
