"""Imperative autograd.

Re-design of reference src/imperative/imperative.cc + python/mxnet/autograd.py.
The reference records a tape of nnvm nodes (AGInfo, imperative.h:42-66) and
replays each op's FGradient on Backward. Here the tape records, per op
invocation, the ``jax.vjp`` pullback of the op's jitted fcompute — residuals
live as device arrays, the backward pass is a reverse walk accumulating
cotangents, and every cotangent computation is itself an async XLA dispatch
(so backward overlaps exactly like the reference's engine-pushed backward).
"""
from __future__ import annotations

import threading

from .base import MXNetError

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.tape = None
    return _state


class SparseCot:
    """A row-sparse cotangent flowing through the tape: ``values[k]`` is the
    gradient contribution to row ``indices[k]`` of a (rows, ...) array.
    Indices may repeat; they are combined at accumulation/write-out time.

    TPU redesign of the reference's row_sparse gradients (FInferStorageType
    dispatching to sparse FComputeEx backward kernels, e.g. Embedding's
    take-grad, src/operator/tensor/indexing_op.h): gradient memory and
    optimizer work stay proportional to touched rows.
    """

    __slots__ = ("indices", "values", "full_shape")

    def __init__(self, indices, values, full_shape):
        self.indices = indices      # (nnz,) int array
        self.values = values        # (nnz, *row_shape)
        self.full_shape = tuple(full_shape)

    def concat(self, other):
        import jax.numpy as jnp
        assert self.full_shape == other.full_shape
        return SparseCot(jnp.concatenate([self.indices, other.indices]),
                         jnp.concatenate([self.values, other.values]),
                         self.full_shape)

    def dense(self):
        import jax.numpy as jnp
        out = jnp.zeros(self.full_shape, dtype=self.values.dtype)
        return out.at[self.indices.astype(jnp.int32)].add(self.values)

    def compact(self):
        """(unique_sorted_indices, combined_values) — host-syncs for nnz."""
        import numpy as np
        import jax
        import jax.numpy as jnp
        idx = np.asarray(self.indices)
        uniq, inv = np.unique(idx, return_inverse=True)
        vals = jax.ops.segment_sum(self.values, jnp.asarray(inv),
                                   num_segments=len(uniq))
        return jnp.asarray(uniq), vals


class TapeNode:
    __slots__ = ("op_name", "inputs", "out_refs", "vjp_fn", "n_outputs",
                 "attrs", "out_avals", "replay_fn")

    def __init__(self, op_name, inputs, out_refs, vjp_fn, n_outputs,
                 attrs=None, out_avals=None, replay_fn=None):
        self.op_name = op_name
        self.inputs = inputs          # list of input NDArrays
        self.out_refs = out_refs      # weakrefs to output NDArrays
        self.vjp_fn = vjp_fn
        self.n_outputs = n_outputs
        self.attrs = attrs
        # (shape, dtype) per output — lets backward build zero cotangents
        # for outputs the user dropped (their weakrefs are dead by then)
        self.out_avals = out_avals
        # pure jax fn(*input_arrays) -> tuple(output_arrays): lets a
        # create_graph walk differentiate THROUGH this node even when
        # op_name isn't in the registry (the _grad_* nodes a previous
        # create_graph pass recorded) — this is what makes third- and
        # higher-order gradients possible
        self.replay_fn = replay_fn


class Tape:
    def __init__(self):
        self.nodes = []

    def append(self, node):
        self.nodes.append(node)


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    s = _st()
    prev = s.recording
    s.recording = bool(is_record)
    if s.recording and s.tape is None:
        s.tape = Tape()
    return prev


def set_training(train_mode):
    s = _st()
    prev = s.training
    s.training = bool(train_mode)
    return prev


def get_tape():
    return _st().tape


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._enter_is_record = is_record
        self._enter_train_mode = train_mode
        self._prev_is_record = None
        self._prev_train_mode = None

    def __enter__(self):
        if self._enter_is_record is not None:
            self._prev_is_record = set_recording(self._enter_is_record)
        if self._enter_train_mode is not None:
            self._prev_train_mode = set_training(self._enter_train_mode)

    def __exit__(self, *args):
        if self._enter_is_record is not None:
            set_recording(self._prev_is_record)
        if self._enter_train_mode is not None:
            set_training(self._prev_train_mode)


def record(train_mode=True):
    """``with autograd.record():`` — parity python/mxnet/autograd.py:122."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


def mark_variables(variables, gradients, grad_reqs="write"):
    """Attach grad buffers (parity: autograd.py:197 / MXAutogradMarkVariables)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._mark_variable(g, req)


class Function:
    """Customize differentiation (parity: python/mxnet/autograd.py:365).

    Subclass and implement ``forward(*inputs)`` / ``backward(*ograds)``;
    backward receives one cotangent per forward output and must return one
    gradient per forward input.  ``save_for_backward(*arrays)`` stashes
    tensors on ``self.saved_tensors``.
    """

    def __init__(self):
        self._used = False
        self.saved_tensors = ()

    def save_for_backward(self, *args):
        self.saved_tensors = args

    def forward(self, *inputs):
        raise NotImplementedError()

    def backward(self, *output_grads):
        raise NotImplementedError()

    def __call__(self, *inputs):
        from .ndarray import NDArray
        if self._used:
            raise MXNetError(
                "Each Function instance can only be called once; "
                "create a new instance per forward call.")
        self._used = True
        with pause(train_mode=is_training()):
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs_l = [outputs] if single else list(outputs)
        if is_recording():
            ctx = outs_l[0]._ctx
            # backward returns one grad per FORWARD input; the tape only
            # tracks the NDArray inputs — select those positions
            nd_pos = [k for k, i in enumerate(inputs)
                      if isinstance(i, NDArray)]

            def vjp(cts, _self=self, _ctx=ctx, _pos=tuple(nd_pos)):
                cts_t = cts if isinstance(cts, tuple) else (cts,)
                ct_nds = [NDArray(c, _ctx) for c in cts_t]
                with pause():
                    igrads = _self.backward(*ct_nds)
                ig_l = igrads if isinstance(igrads, (list, tuple)) \
                    else (igrads,)
                picked = [ig_l[k] if k < len(ig_l) else None for k in _pos]
                return tuple(g._data if isinstance(g, NDArray) else g
                             for g in picked)

            record_custom(type(self).__name__,
                          [inputs[k] for k in nd_pos], outs_l, vjp)
        return outputs


def record_custom(op_name, inputs, outputs, vjp_fn, attrs=None,
                  replay_fn=None):
    """Push a hand-built node onto the tape.

    For ops that bypass the dense registry (sparse kernels, custom python
    ops): ``vjp_fn(cotangents_tuple) -> input cotangents`` where a cotangent
    may be a jax array or a SparseCot.  No-op outside a record scope.
    ``replay_fn`` (pure jax, tuple-returning) makes the node
    higher-order-differentiable under create_graph.
    """
    if not is_recording():
        return
    import weakref
    node = TapeNode(op_name, list(inputs),
                    [weakref.ref(o) for o in outputs],
                    vjp_fn, len(outputs), attrs,
                    out_avals=[(o.shape, o.dtype) for o in outputs],
                    replay_fn=replay_fn)
    for o in outputs:
        o._autograd_node = node
    tape = get_tape()
    if tape is not None:
        tape.append(node)


def _as_list(x):
    return x if isinstance(x, (list, tuple)) else [x]


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             create_graph=False, _return_for=None):
    """Run backward from ``heads`` through the tape.

    Parity: Imperative::Backward (src/imperative/imperative.cc:280) — build
    graph from output entries, ograds default to ones, execute backward nodes.

    With ``create_graph=True`` the gradient computation itself is RECORDED
    on the tape (cotangents are NDArrays, each node's pullback is replayed
    as a differentiable program), so a second backward yields higher-order
    gradients (parity: test_higher_order_grad.py).
    """
    from . import telemetry as _telemetry

    heads = _as_list(heads)
    if head_grads is None:
        head_grads = [None] * len(heads)
    else:
        head_grads = _as_list(head_grads)

    if create_graph:
        return _backward_create_graph(heads, head_grads, _return_for)

    tape = get_tape()
    if tape is None or not tape.nodes:
        raise MXNetError("backward called outside of autograd.record scope "
                         "or nothing was recorded")

    # everything on the host before, between and after the nodes' vjp
    # programs is this span's self time
    with _telemetry.span("autograd/backward/walk"):
        _walk_tape(tape, heads, head_grads, retain_graph)


def _walk_tape(tape, heads, head_grads, retain_graph):
    import jax.numpy as jnp
    import numpy as np

    from . import telemetry as _telemetry

    # cotangent accumulator keyed by id of the produced jax array's NDArray
    grads = {}

    def add_grad(nd, g):
        if nd is None or g is None:
            return
        k = id(nd)
        if k in grads:
            prev = grads[k][0]
            if isinstance(prev, SparseCot) and isinstance(g, SparseCot):
                g = prev.concat(g)
            elif isinstance(prev, SparseCot):
                g = prev.dense() + g
            elif isinstance(g, SparseCot):
                g = prev + g.dense()
            else:
                g = prev + g
            grads[k] = (g, nd)
        else:
            grads[k] = (g, nd)

    for h, hg in zip(heads, head_grads):
        if h._autograd_node is None and h._grad_req == "null":
            raise MXNetError("one of the heads is not part of the recorded graph")
        g = hg._data if hg is not None else jnp.ones_like(h._data)
        add_grad(h, g)

    # reverse execution order walk
    for node in reversed(tape.nodes):
        outs = [r() for r in node.out_refs]
        cots = []
        touched = False
        for o in outs:
            if o is not None and id(o) in grads:
                cots.append(grads[id(o)][0])
                touched = True
            else:
                # zero cotangent of right shape/dtype
                cots.append(None)
        if not touched:
            continue
        avals = node.out_avals or [None] * len(outs)
        cots = [c if c is not None else
                (jnp.zeros_like(o._data) if o is not None else
                 jnp.zeros(av[0], av[1]))
                for c, o, av in zip(cots, outs, avals)]
        # a SparseCot reaching an interior node's generic vjp must densify
        # (only the leaf write-out / sparse-aware accumulators understand it)
        cots = [c.dense() if isinstance(c, SparseCot) else c for c in cots]
        with _telemetry.span("autograd/backward/dispatch"):
            in_cots = node.vjp_fn(cots[0] if node.n_outputs == 1
                                  else tuple(cots))
        for inp, ic in zip(node.inputs, in_cots):
            if isinstance(ic, SparseCot):
                add_grad(inp, ic)
            elif ic is not None and not isinstance(ic, (int, float)) and \
                    getattr(ic, "dtype", None) is not None and ic.dtype != np.dtype([('float0', 'V')]):
                add_grad(inp, ic)

    # write accumulated grads into marked variables per grad_req
    from .ndarray.sparse import RowSparseNDArray
    for _, (g, nd) in grads.items():
        if nd._grad is None or nd._grad_req == "null":
            continue
        if isinstance(nd._grad, RowSparseNDArray):
            # sparse grad buffer (attach_grad(stype='row_sparse') /
            # Parameter grad_stype): keep gradients row-sparse end-to-end
            if not isinstance(g, SparseCot):
                nz = np.nonzero(np.any(np.asarray(g).reshape(
                    g.shape[0], -1) != 0, axis=1))[0]
                g = SparseCot(jnp.asarray(nz), g[jnp.asarray(nz)], g.shape)
            if nd._grad_req == "add" and nd._grad._indices.shape[0]:
                g = SparseCot(nd._grad._indices, nd._grad._data,
                              g.full_shape).concat(g)
            idx, vals = g.compact()
            nd._grad._indices = idx
            nd._grad._set_data(vals.astype(nd._grad._data.dtype))
        else:
            if isinstance(g, SparseCot):
                g = g.dense()
            if nd._grad_req == "add":
                nd._grad._set_data(nd._grad._data + g)
            else:
                nd._grad._set_data(g.astype(nd._grad._data.dtype))

    if not retain_graph:
        _st().tape = Tape()


def _backward_create_graph(heads, head_grads, return_for):
    """Recorded backward: every cotangent is an NDArray, every node pullback
    replays as a jax.vjp program recorded via record_custom — gradients of
    gradients fall out of walking the (grown) tape again."""
    import jax
    import numpy as np
    from .ndarray import NDArray
    from . import ndarray as _ndmod
    from .ops import registry as _registry

    tape = get_tape()
    if tape is None or not tape.nodes:
        raise MXNetError("backward called outside of autograd.record scope "
                         "or nothing was recorded")

    grads = {}

    def add_grad(nd_, g_nd):
        if nd_ is None or g_nd is None:
            return
        k = id(nd_)
        if k in grads:
            grads[k] = (grads[k][0] + g_nd, nd_)  # recorded elemwise add
        else:
            grads[k] = (g_nd, nd_)

    for h, hg in zip(heads, head_grads):
        if h._autograd_node is None and h._grad_req == "null":
            raise MXNetError("one of the heads is not part of the recorded "
                             "graph")
        add_grad(h, hg if hg is not None else _ndmod.ones_like(h))

    nodes = list(tape.nodes)  # snapshot: the walk appends grad nodes
    for node in reversed(nodes):
        outs = [r() for r in node.out_refs]
        if not any(o is not None and id(o) in grads for o in outs):
            continue
        avals = node.out_avals or [(o.shape, o.dtype) for o in outs]
        ct_nds = []
        for o, av in zip(outs, avals):
            if o is not None and id(o) in grads:
                ct_nds.append(grads[id(o)][0])
            else:
                ct_nds.append(_ndmod.zeros(av[0], dtype=av[1]))

        op = _registry.get(node.op_name) if _registry.exists(node.op_name) \
            else None
        # a differentiable forward to replay: either the registry op's
        # raw compute, or the replay_fn a previous create_graph pass
        # attached to its _grad_* node (that recursion is what makes
        # third- and higher-order derivatives work)
        if node.replay_fn is not None:
            fwd, tuple_out = node.replay_fn, True
        elif op is not None and not op.is_random and op.fgradient is None:
            fwd = op.raw(dict(node.attrs or {}))
            tuple_out = node.n_outputs > 1
        else:
            fwd = None
        if fwd is not None:
            # differentiable replay: gfun(primals, cts) -> input cotangents
            n_in = len(node.inputs)

            def gfun(*arrays, _f=fwd, _n=n_in, _m=tuple_out):
                prims, cts = arrays[:_n], arrays[_n:]
                _, vf = jax.vjp(_f, *prims)
                return vf(tuple(cts) if _m else cts[0])

            in_nds = list(node.inputs) + ct_nds
            arrays = [i._data for i in in_nds]
            # drop non-differentiable (float0: integer-input) cotangent
            # slots BEFORE the vjp so higher-order cotangents line up 1:1
            f0 = np.dtype([("float0", "V")])
            out_sds = jax.eval_shape(gfun, *arrays)
            live_idx = [i for i, o in enumerate(out_sds) if o.dtype != f0]

            def gfun_live(*arrs, _g=gfun, _li=tuple(live_idx)):
                outs_ = _g(*arrs)
                return tuple(outs_[i] for i in _li)

            outs_arr, vjp_fn = jax.vjp(gfun_live, *arrays)
            ctx = node.inputs[0]._ctx
            live = [NDArray(o, ctx) for o in outs_arr]

            def grad_vjp(cts, _v=vjp_fn):
                return _v(cts if isinstance(cts, tuple) else (cts,))

            record_custom(f"_grad_{node.op_name}", in_nds, live, grad_vjp,
                          replay_fn=gfun_live)
            in_cots = [None] * n_in
            for slot, o_nd in zip(live_idx, live):
                in_cots[slot] = o_nd
        else:
            # non-replayable node (random / custom FGradient): first-order
            # only through here
            cts_raw = [c._data for c in ct_nds]
            raw = node.vjp_fn(tuple(cts_raw) if node.n_outputs > 1
                              else cts_raw[0])
            f0 = np.dtype([("float0", "V")])
            in_cots = []
            for c in raw:
                if isinstance(c, SparseCot):
                    in_cots.append(NDArray(c.dense(), node.inputs[0]._ctx))
                elif c is None or isinstance(c, (int, float)) or \
                        getattr(c, "dtype", None) is None or c.dtype == f0:
                    in_cots.append(None)
                else:
                    in_cots.append(NDArray(c, node.inputs[0]._ctx))
        for inp, ic in zip(node.inputs, in_cots):
            if ic is not None:
                add_grad(inp, ic)

    if return_for is not None:
        out = []
        for v in return_for:
            if id(v) in grads:
                out.append(grads[id(v)][0])
            else:
                out.append(_ndmod.zeros(v.shape, dtype=v.dtype, ctx=v.ctx))
        return out
    # plain backward(create_graph=True): also fill the grad buffers
    for _, (g, nd_) in grads.items():
        if nd_._grad is not None and nd_._grad_req != "null":
            if nd_._grad_req == "add":
                nd_._grad._set_data(nd_._grad._data + g._data)
            else:
                nd_._grad._set_data(g._data.astype(nd_._grad._data.dtype))
    return None


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Differentiate heads w.r.t. variables and *return* the grads
    (parity: autograd.py:270). With create_graph=True the returned grads
    are themselves on the tape — call backward()/grad() on expressions of
    them for higher-order derivatives."""
    from .ndarray import NDArray
    heads_l = _as_list(heads)
    variables_l = _as_list(variables)
    if create_graph:
        out = backward(heads_l, _as_list(head_grads) if head_grads is not None
                       else None, retain_graph=True, train_mode=train_mode,
                       create_graph=True, _return_for=variables_l)
        return out if isinstance(variables, (list, tuple)) else out[0]
    saved = [(v._grad, v._grad_req) for v in variables_l]
    for v in variables_l:
        from . import ndarray as _nd
        v._grad = _nd.zeros(v.shape, dtype=v.dtype, ctx=v.ctx)
        v._grad_req = "add"
    backward(heads_l, head_grads, retain_graph=bool(retain_graph),
             train_mode=train_mode)
    out = [v._grad for v in variables_l]
    for v, (g, req) in zip(variables_l, saved):
        v._grad, v._grad_req = g, req
    return out if isinstance(variables, (list, tuple)) else out[0]
