"""Gluon Block/Parameter/Trainer/layers tests.

Parity with reference tests/python/unittest/test_gluon.py (2805 LoC): layer
forward shapes vs expectation, parameter management, save/load round-trips,
hybridize consistency, trainer updates.
"""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn


def test_parameter():
    p = gluon.Parameter("weight", shape=(10, 10))
    p.initialize(init="xavier", ctx=[mx.cpu(0)])
    assert len(p.list_data()) == 1
    assert len(p.list_grad()) == 1
    assert p.data(mx.cpu(0)).ctx == mx.cpu(0)
    assert p.data().shape == (10, 10)
    p.reset_ctx(ctx=[mx.cpu(0)])
    assert p.list_ctx() == [mx.cpu(0)]


def test_paramdict():
    params = gluon.ParameterDict("net_")
    params.get("weight", shape=(10, 10))
    assert list(params.keys()) == ["net_weight"]
    params.initialize(ctx=mx.cpu())
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "test.params")
        params.save(fname)
        params.load(fname, mx.cpu())


def test_constant():
    class Test(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            self.value = np.asarray([[1, 2], [3, 4]], dtype="float32")
            self.const = self.params.get_constant("const", self.value)

        def hybrid_forward(self, F, x, const):
            return x + const

    test = Test()
    test.initialize()
    trainer = gluon.Trainer(test.collect_params(), "sgd",
                            {"learning_rate": 1.0, "momentum": 0.5})
    with autograd.record():
        x = mx.nd.ones((2, 2))
        x.attach_grad()
        y = test(x)
        y.backward()
    trainer.step(1)
    assert (test.const.data().asnumpy() == test.value).all()
    assert (x.grad.asnumpy() == 1).all()


def test_basic():
    model = nn.Sequential()
    model.add(nn.Dense(128, activation="tanh", in_units=10, flatten=False))
    model.add(nn.Dropout(0.5))
    model.add(nn.Dense(64, activation="tanh", in_units=256))
    model.add(nn.Dense(32, in_units=64))
    model.add(nn.Activation("relu"))

    # ndarray
    model.initialize(mx.initializer.Xavier(magnitude=2.24))
    x = mx.nd.zeros((32, 2, 10))
    out = model(x)
    assert out.shape == (32, 32)

    model.collect_params().setattr("grad_req", "null")
    assert list(model.collect_params().values())[0]._grad is None
    model.collect_params().setattr("grad_req", "write")
    assert list(model.collect_params().values())[0]._grad is not None


def test_dense():
    model = nn.Dense(128, activation="tanh", in_units=10, flatten=False,
                     prefix="test_")
    inputs = mx.nd.zeros((2, 3, 10))
    model.initialize()
    outputs = model(inputs)
    assert {p.name for p in model.collect_params().values()} == \
        {"test_weight", "test_bias"}
    assert outputs.shape == (2, 3, 128)

    model = nn.Dense(128, activation="relu", in_units=30, flatten=True,
                     prefix="test2_")
    inputs = mx.nd.zeros((17, 2, 5, 3))
    model.initialize()
    outputs = model(inputs)
    assert outputs.shape == (17, 128)


def test_dense_deferred_shape():
    model = nn.Dense(16)
    model.initialize()
    x = mx.nd.ones((4, 7))
    out = model(x)
    assert out.shape == (4, 16)
    assert model.weight.shape == (16, 7)


@pytest.mark.parametrize("layer,shape,expected", [
    (lambda: nn.Conv2D(16, (3, 3), in_channels=4), (2, 4, 10, 10), (2, 16, 8, 8)),
    (lambda: nn.Conv2D(16, (3, 3), padding=(1, 1), in_channels=4),
     (2, 4, 10, 10), (2, 16, 10, 10)),
    (lambda: nn.Conv2D(16, (3, 3), strides=2, in_channels=4),
     (2, 4, 10, 10), (2, 16, 4, 4)),
    (lambda: nn.Conv2D(16, (3, 3), groups=2, in_channels=4),
     (2, 4, 10, 10), (2, 16, 8, 8)),
    (lambda: nn.Conv1D(16, 3, in_channels=4), (2, 4, 10), (2, 16, 8)),
    (lambda: nn.Conv3D(16, (3, 3, 3), in_channels=4), (2, 4, 8, 8, 8),
     (2, 16, 6, 6, 6)),
    (lambda: nn.MaxPool2D(2), (2, 4, 10, 10), (2, 4, 5, 5)),
    (lambda: nn.AvgPool2D(2), (2, 4, 10, 10), (2, 4, 5, 5)),
    (lambda: nn.GlobalAvgPool2D(), (2, 4, 10, 10), (2, 4, 1, 1)),
    (lambda: nn.GlobalMaxPool2D(), (2, 4, 10, 10), (2, 4, 1, 1)),
    (lambda: nn.Conv2DTranspose(16, (3, 3), in_channels=4), (2, 4, 10, 10),
     (2, 16, 12, 12)),
    (lambda: nn.Conv2DTranspose(16, (3, 3), strides=2, output_padding=1,
                                in_channels=4), (2, 4, 10, 10),
     (2, 16, 22, 22)),
])
def test_layer_shapes(layer, shape, expected):
    l = layer()
    l.initialize()
    x = mx.nd.random.uniform(shape=shape)
    out = l(x)
    assert out.shape == expected, (out.shape, expected)


def test_conv_vs_numpy():
    """Conv2D forward against explicit numpy convolution."""
    l = nn.Conv2D(2, (3, 3), in_channels=3, use_bias=False)
    l.initialize(mx.initializer.Xavier())
    x = mx.nd.random.uniform(shape=(1, 3, 5, 5))
    out = l(x).asnumpy()
    w = l.weight.data().asnumpy()
    xn = x.asnumpy()
    ref = np.zeros((1, 2, 3, 3), dtype=np.float32)
    for o in range(2):
        for i in range(3):
            for hh in range(3):
                for ww in range(3):
                    ref[0, o, hh, ww] += np.sum(
                        xn[0, i, hh:hh + 3, ww:ww + 3] * w[o, i])
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_batchnorm_running_stats():
    bn = nn.BatchNorm(in_channels=4)
    bn.initialize()
    x = mx.nd.random.normal(1.5, 2.0, shape=(8, 4, 3, 3))
    with autograd.record():
        bn(x)
    rm = bn.running_mean.data().asnumpy()
    assert not np.allclose(rm, np.zeros(4)), "running mean should update"
    # inference mode: uses running stats, output not normalized to 0 mean
    out = bn(x)
    assert out.shape == x.shape


def test_layernorm_values():
    ln = nn.LayerNorm(in_channels=5)
    ln.initialize()
    x = mx.nd.random.uniform(shape=(3, 5))
    out = ln(x).asnumpy()
    xn = x.asnumpy()
    ref = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_embedding():
    layer = nn.Embedding(10, 100)
    layer.initialize()
    x = mx.nd.array([3, 4, 2, 0])
    y = layer(x)
    assert y.shape == (4, 100)
    with autograd.record():
        y = layer(x)
        loss = y.sum()
    loss.backward()
    grad = layer.weight.grad().asnumpy()
    assert np.allclose(grad[[3, 4, 2, 0]], np.ones((4, 100)))
    assert np.allclose(grad[[1, 5, 6, 7, 8, 9]], 0)


def test_hybrid_consistency():
    """Hybridized and imperative outputs must match (inference mode)."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
                nn.Activation("relu"), nn.MaxPool2D(2), nn.Flatten(),
                nn.Dense(10))
    net.initialize()
    x = mx.nd.random.uniform(shape=(2, 3, 8, 8))
    out_imp = net(x).asnumpy()
    net.hybridize()
    out_hyb = net(x).asnumpy()
    np.testing.assert_allclose(out_imp, out_hyb, rtol=1e-5, atol=1e-5)


def test_hybrid_grad_consistency():
    """Gradients through the CachedOp (hybridized) match imperative ones."""
    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        return net

    x = mx.nd.random.uniform(shape=(3, 8))
    net1 = build()
    net1.initialize(mx.initializer.Constant(0.05))
    with autograd.record():
        l1 = (net1(x) ** 2).sum()
    l1.backward()
    g1 = {k: v.grad().asnumpy() for k, v in net1.collect_params().items()}

    net2 = build()
    net2.initialize(mx.initializer.Constant(0.05))
    net2.hybridize()
    with autograd.record():
        l2 = (net2(x) ** 2).sum()
    l2.backward()
    g2 = {k: v.grad().asnumpy() for k, v in net2.collect_params().items()}
    for (k1, a), (k2, b) in zip(sorted(g1.items()), sorted(g2.items())):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class _AuxNet(gluon.HybridBlock):
    """Convolution -> BatchNorm -> ReLU twice (``bn``) or without the
    BatchNorm, then one dense head, or two (``heads``) returned as a pair;
    for images of ``side`` x ``side``."""

    def __init__(self, bn=True, heads=1, side=6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.body = nn.HybridSequential()
            in_ch = 3
            for _ in range(2):
                self.body.add(nn.Conv2D(4, 3, padding=1, in_channels=in_ch))
                if bn:
                    self.body.add(nn.BatchNorm(in_channels=4))
                self.body.add(nn.Activation("relu"))
                in_ch = 4
            self.body.add(nn.Flatten())
            self.heads = nn.HybridSequential()
            for _ in range(heads):
                self.heads.add(nn.Dense(3, in_units=4 * side * side))

    def hybrid_forward(self, F, x):
        h = self.body(x)
        outs = tuple(head(h) for head in self.heads)
        return outs if len(outs) > 1 else outs[0]


def _aux_net(bn=True, heads=1, grad_req="write", like=None, side=6):
    net = _AuxNet(bn=bn, heads=heads, side=side)
    net.initialize(mx.initializer.Xavier())
    if like is not None:
        for p, q in zip(net.collect_params().values(),
                        like.collect_params().values()):
            p.set_data(q.data())
    for p in net.collect_params().values():
        if p.grad_req != "null":
            p.grad_req = grad_req
    return net


def _aux_data():
    rng = np.random.RandomState(7)
    x = mx.nd.array(rng.randn(2, 3, 6, 6).astype(np.float32))
    head_grad = mx.nd.array(rng.randn(2, 3).astype(np.float32))
    return x, head_grad


def _dispatch_counts(run):
    """The ``counts`` of every ``gluon/cached_op/dispatch`` span that
    ``run()`` closed, in order, with telemetry on for the call only."""
    from mxnet_tpu import telemetry
    telemetry.enable()
    telemetry.reset_span_records()
    try:
        run()
        return [r["counts"] for r in telemetry.span_records()
                if r["name"] == "gluon/cached_op/dispatch"]
    finally:
        telemetry.disable()
        telemetry.reset_span_records()


def _recorded_entry(net):
    """The cached entry of the block's forward under ``autograd.record``."""
    (entry,) = [e for (_sig, _train, recording), e in net._jit_cache.items()
                if recording]
    return entry


def _zero_cotangent_reference(entry, x, cts):
    """The gradients of the block's one compiled forward as the parent
    computed them: ``jax.vjp`` over ``entry.raw`` with respect to the outputs
    and the mutated statistics both, the statistics' cotangents explicit
    zeros; compiled as two programs the way the block's own are, the
    pullback's small residuals packed between them as the block's own are
    (on the CPU a residual that a consumer fuses is contracted otherwise
    than one that is a program output: an ulp here and there)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon import block as block_mod
    n = entry.n_params
    arrays = [p.data(entry.ctx)._data for p in entry.param_list] + [x._data]
    key = jax.random.PRNGKey(0)

    def fwd(key, *arrays):
        out, vjp_fn = jax.vjp(
            lambda *a: entry.raw(key, a[:n], a[n:]), *arrays)
        return out, block_mod._PackedPullback.pack(vjp_fn)

    with autograd.train_mode():     # raw traces in the mode it is called in
        (outs, mutated), vjp_fn = jax.jit(fwd)(key, *arrays)
    assert len(outs) == len(cts)
    zeros = tuple(jnp.zeros_like(m) for m in mutated)
    grads = jax.jit(lambda f, c: f(c))(vjp_fn, (tuple(cts), zeros))
    return dict(zip((p.name for p in entry.param_list), grads[:n])), mutated


@pytest.mark.parametrize("case", ["write", "add", "retain_graph",
                                  "unused_head", "no_batchnorm"])
def test_hybrid_aux_outputs_grads_match_zero_cotangent_reference(case):
    """The running statistics leave the recorded forward as auxiliary outputs
    of its vjp: same gradients, bit for bit, as differentiating them with
    zero cotangents; same statistics as the un-hybridized block."""
    import jax.numpy as jnp
    bn = case != "no_batchnorm"
    heads = 2 if case == "unused_head" else 1
    grad_req = "add" if case == "add" else "write"
    x, head_grad = _aux_data()
    net = _aux_net(bn, heads, grad_req)
    plain = _aux_net(bn, heads, grad_req, like=net)
    net.hybridize()

    def step(block):
        with autograd.record():
            out = block(x)
        head = out[0] if heads > 1 else out
        if case == "retain_graph":
            head.backward(head_grad, retain_graph=True)
        head.backward(head_grad)

    step(net)       # builds the entry; the reference reads the state it left
    step(plain)
    before = {p.name: p.grad().asnumpy()
              for p in net.collect_params().values() if p.grad_req != "null"}
    cts = [head_grad._data] + [jnp.zeros((2, 3), jnp.float32)] * (heads - 1)
    entry = _recorded_entry(net)
    want, want_stats = _zero_cotangent_reference(entry, x, cts)
    step(net)
    step(plain)

    mutated_idx = entry.mutated_idx_box[0]
    assert len(mutated_idx) == (4 if bn else 0)
    checked = 0
    for p in net.collect_params().values():
        if p.grad_req == "null":
            continue
        g = np.asarray(want[p.name])
        if case == "add":
            g = before[p.name] + g
        np.testing.assert_array_equal(p.grad().asnumpy(), g, err_msg=p.name)
        checked += 1
    assert checked == (8 if bn else 4) + 2 * heads
    # the unused head got a gradient of zeros, not none at all
    if heads > 1:
        unused = list(net.heads[1].collect_params().values())
        assert all(not p.grad().asnumpy().any() for p in unused)
    for idx, stat in zip(mutated_idx, want_stats):
        np.testing.assert_array_equal(
            entry.param_list[idx].data().asnumpy(), np.asarray(stat))
    for p, q in zip(net.collect_params().values(),
                    plain.collect_params().values()):
        if p.grad_req == "null":
            assert "running" in p.name
            np.testing.assert_allclose(p.data().asnumpy(), q.data().asnumpy(),
                                       rtol=1e-6, atol=1e-7, err_msg=p.name)


class _EmbeddingNet(gluon.HybridBlock):
    """Embedding -> mean over the positions -> Dense: the token ids are an
    integer residual of the gather."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(50, 8)
            self.out = nn.Dense(3, in_units=8)

    def hybrid_forward(self, F, x):
        return self.out(self.embed(x).mean(axis=1))


def _packing_case(case):
    """``(net, x, head_grads)`` of one case of the packing test: at 48x48 the
    convolutions' activations are over the packing limit and the per-channel
    leaves under it; every leaf of the perceptron and of the embedding net
    is under it."""
    rng = np.random.RandomState(11)
    if case == "embedding":
        net = _EmbeddingNet()
        net.initialize(mx.initializer.Xavier())
        x = mx.nd.array(rng.randint(0, 50, (4, 5)), dtype="int32")
    elif case == "all_small":
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=10),
                nn.Dense(3, in_units=16))
        net.initialize(mx.initializer.Xavier())
        x = mx.nd.array(rng.randn(8, 10).astype(np.float32))
    else:
        net = _aux_net(bn=case != "no_batchnorm",
                       heads=2 if case == "two_outputs" else 1, side=48)
        x = mx.nd.array(rng.randn(32, 3, 48, 48).astype(np.float32))
    if case != "embedding":
        x.attach_grad()
    n_out = 2 if case == "two_outputs" else 1
    head_grads = [mx.nd.array(rng.randn(x.shape[0], 3).astype(np.float32))
                  for _ in range(n_out)]
    return net, x, head_grads


def _unpacked_pullback_reference(entry, x, cts):
    """The gradients of ``entry.raw`` by ``jax.vjp`` with every residual a
    program output of its own, as before the packing: two programs, the
    forward handing the pullback to ``jit(lambda f, c: f(c))``."""
    import jax
    n = entry.n_params
    arrays = [p.data(entry.ctx)._data for p in entry.param_list] + [x._data]
    fwd = jax.jit(lambda key, *arrays: jax.vjp(
        lambda *a: entry.raw(key, a[:n], a[n:]), *arrays, has_aux=True))
    with autograd.train_mode():     # raw traces in the mode it is called in
        _outs, vjp_fn, _mutated = fwd(jax.random.PRNGKey(0), *arrays)
    grads = jax.jit(lambda f, c: f(c))(vjp_fn, tuple(cts))
    return ({p.name: g for p, g in zip(entry.param_list, grads[:n])},
            grads[n], len(jax.tree_util.tree_leaves(vjp_fn)))


@pytest.mark.parametrize("case", ["batchnorm", "no_batchnorm", "two_outputs",
                                  "embedding", "all_small"])
def test_hybrid_packed_residuals_grads_bit_equal_to_unpacked_pullback(case):
    """The pullback's residuals under 64 KiB cross from the recorded forward
    program to the pullback program in one buffer per dtype: the gradients
    are those of the same ``jax.vjp`` with every residual on its own, bit
    for bit, and a second ``backward`` over the same residuals repeats
    them."""
    import jax
    from mxnet_tpu.gluon import block as block_mod
    net, x, head_grads = _packing_case(case)
    net.hybridize()
    heads = []

    def forward():
        with autograd.record():
            out = net(x)
        heads.extend(out if isinstance(out, (list, tuple)) else [out])

    (counts,) = [c for c in _dispatch_counts(forward) if c]
    pullback = heads[0]._autograd_node.vjp_fn.__defaults__[0]
    assert isinstance(pullback, block_mod._PackedPullback)

    def grads_now():
        got = {p.name: p.grad().asnumpy()
               for p in net.collect_params().values() if p.grad_req != "null"}
        if x.grad is not None:
            got["data"] = x.grad.asnumpy()
        return got

    autograd.backward(heads, head_grads, retain_graph=True)
    first = grads_now()
    autograd.backward(heads, head_grads)
    second = grads_now()

    entry = _recorded_entry(net)
    want, want_x, n_leaves = _unpacked_pullback_reference(
        entry, x, [g._data for g in head_grads])
    assert set(first) - {"data"} == {
        p.name for p in entry.param_list if p.grad_req != "null"}
    for name, g in first.items():
        ref = want_x if name == "data" else want[name]
        np.testing.assert_array_equal(g, np.asarray(ref), err_msg=name)
        np.testing.assert_array_equal(second[name], g, err_msg=name)

    # what crossed the host, by the pullback and by the span's record
    leaves = jax.tree_util.tree_leaves(pullback)
    limit = block_mod._RESIDUAL_PACK_BYTES
    assert len(leaves) == len(pullback.large) + len(pullback.packed)
    assert len(pullback.slots) == n_leaves
    assert counts["mxnet_cached_op_residual_leaves_total"] == n_leaves
    assert counts["mxnet_cached_op_residual_buffers_total"] == len(leaves)
    assert n_leaves > len(leaves) >= 1
    assert all(b.ndim == 1 for b in pullback.packed)
    assert len({b.dtype for b in pullback.packed}) == len(pullback.packed)
    if case in ("all_small", "embedding"):
        assert not pullback.large
        assert {str(b.dtype) for b in pullback.packed} == {
            "float32", "int32" if case == "embedding" else "bool"}
    else:
        assert pullback.large and all(
            a.nbytes >= limit for a in pullback.large)


def test_hybrid_mobilenet_v2_residuals_cross_in_a_third_of_the_buffers():
    """The benchmark's Gluon model: of the 763 residuals of its recorded
    forward, the per-channel vectors, scalars and small weights (two thirds
    of them) travel in one float32 and one bool buffer."""
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.mobilenet_v2_1_0()
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(3).randn(2, 3, 224, 224)
                    .astype(np.float32))

    def forward():
        with autograd.record():
            net(x)

    records = _dispatch_counts(forward)
    # the children's dispatches of the dry run that finishes deferred
    # initialisation are not recorded forwards: they count nothing
    assert all(r is None for r in records[:-1])
    counts = records[-1]
    assert counts["mxnet_cached_op_aux_outputs_total"] == 106
    assert counts["mxnet_cached_op_residual_leaves_total"] == 763
    assert 2 <= counts["mxnet_cached_op_residual_buffers_total"] <= 260


def _backward_launches(head, head_grad, tmp_path):
    """Names of the programs jax launched on this thread between entering
    ``head.backward`` and its return."""
    import glob
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        head.backward(head_grad)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    return [event.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for event in line.events
            if event.name.startswith("PjitFunction(")]


@pytest.mark.parametrize("bn", [True, False], ids=["batchnorm", "no_batchnorm"])
def test_hybrid_backward_is_one_launch_and_counts_aux_outputs(
        bn, tmp_path, monkeypatch):
    """``backward()`` through a recorded hybridized block is one call of the
    shared pullback program with a cotangent per output: no zeros are built
    for the statistics."""
    from mxnet_tpu.gluon import block as block_mod
    x, head_grad = _aux_data()
    net = _aux_net(bn)
    net.hybridize()
    with autograd.record():
        net(x).backward(head_grad)      # compile both programs
    seen = []
    real = block_mod._BWD_EXEC
    monkeypatch.setattr(
        block_mod, "_BWD_EXEC",
        lambda vjp_fn, cts: seen.append(cts) or real(vjp_fn, cts))
    launches = []

    def forwards_and_backward():
        net(x)                          # not recording: nothing counted
        with autograd.record():
            out = net(x)
        launches.extend(_backward_launches(out, head_grad, tmp_path))

    records = _dispatch_counts(forwards_and_backward)
    assert "PjitFunction(broadcast_in_dim)" not in launches, launches
    assert launches.count("PjitFunction(<lambda>)") >= 1, launches
    (cts,) = seen
    assert isinstance(cts, tuple) and len(cts) == 1
    assert cts[0] is head_grad._data
    # two statistics a BatchNorm layer, two layers; every residual of this
    # small net is under the packing limit: one float32 buffer, and one of
    # bool for the two ReLU masks
    (first, counts) = records
    assert first is None
    assert counts.pop("mxnet_cached_op_residual_leaves_total") > 2
    assert counts == {"mxnet_cached_op_aux_outputs_total": 4 if bn else 0,
                      "mxnet_cached_op_residual_buffers_total": 2}


def test_trainer_updates():
    net = nn.Dense(1, in_units=2)
    net.initialize(mx.initializer.Constant(0.5))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0})
    x = mx.nd.array([[1.0, 2.0]])
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    w_before = net.weight.data().asnumpy().copy()
    trainer.step(1)
    w_after = net.weight.data().asnumpy()
    np.testing.assert_allclose(w_before - np.array([[1.0, 2.0]]), w_after,
                               rtol=1e-5)


def test_trainer_lr_scheduler():
    from mxnet_tpu.lr_scheduler import FactorScheduler
    net = nn.Dense(1, in_units=2)
    net.initialize()
    sched = FactorScheduler(step=1, factor=0.5, base_lr=1.0)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0, "lr_scheduler": sched})
    x = mx.nd.ones((1, 2))
    for _ in range(3):
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(1)
    assert trainer.learning_rate < 1.0


def test_save_load_parameters():
    net = nn.HybridSequential(prefix="model_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net.initialize(mx.initializer.Xavier())
    x = mx.nd.random.uniform(shape=(2, 4))
    out1 = net(x).asnumpy()
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "net.params")
        net.save_parameters(fname)
        net2 = nn.HybridSequential(prefix="model_")
        with net2.name_scope():
            net2.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
        net2.load_parameters(fname)
        out2 = net2(x).asnumpy()
    np.testing.assert_allclose(out1, out2, rtol=1e-6)


def test_losses():
    pred = mx.nd.random.uniform(shape=(5, 4))
    label_cls = mx.nd.array([0, 1, 2, 3, 0])
    label_reg = mx.nd.random.uniform(shape=(5, 4))

    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label_cls)
    assert l.shape == (5,)
    ref = -np.log(
        np.exp(pred.asnumpy()) /
        np.exp(pred.asnumpy()).sum(-1, keepdims=True))[
            np.arange(5), label_cls.asnumpy().astype(int)]
    np.testing.assert_allclose(l.asnumpy(), ref, rtol=1e-4, atol=1e-5)

    l2 = gluon.loss.L2Loss()(pred, label_reg)
    ref2 = 0.5 * ((pred.asnumpy() - label_reg.asnumpy()) ** 2).mean(-1)
    np.testing.assert_allclose(l2.asnumpy(), ref2, rtol=1e-4, atol=1e-6)

    l1 = gluon.loss.L1Loss()(pred, label_reg)
    ref1 = np.abs(pred.asnumpy() - label_reg.asnumpy()).mean(-1)
    np.testing.assert_allclose(l1.asnumpy(), ref1, rtol=1e-4, atol=1e-6)

    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    lbce = bce(pred, (label_reg > 0.5).astype("float32"))
    assert lbce.shape == (5,)

    hl = gluon.loss.HuberLoss()(pred, label_reg)
    assert hl.shape == (5,)

    hinge = gluon.loss.HingeLoss()(pred, (label_reg > 0.5) * 2 - 1)
    assert hinge.shape == (5,)

    kl = gluon.loss.KLDivLoss(from_logits=False)(
        pred, mx.nd.softmax(label_reg))
    assert kl.shape == (5,)


def test_sequential_slicing():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(3), nn.Dense(2))
    assert len(net) == 3
    assert isinstance(net[1], nn.Dense)
    sub = net[0:2]
    assert len(sub) == 2


def test_block_attr_registration():
    class Model(gluon.Block):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.dense0 = nn.Dense(5, in_units=5)
                self.dense1 = nn.Dense(5, in_units=5)

        def forward(self, x):
            return self.dense1(self.dense0(x))

    model = Model()
    assert len(model._children) == 2
    names = set(model.collect_params().keys())
    assert len(names) == 4
    model.initialize()
    out = model(mx.nd.zeros((2, 5)))
    assert out.shape == (2, 5)


def test_global_norm_clip():
    x1 = mx.nd.ones((3, 3))
    x2 = mx.nd.ones((4, 4))
    norm = gluon.utils.clip_global_norm([x1, x2], 1.0)
    assert norm == pytest.approx(5.0, rel=1e-4)
    assert x1.asnumpy().max() < 0.3


def test_split_and_load():
    data = mx.nd.arange(16).reshape((8, 2))
    splits = gluon.utils.split_and_load(data, [mx.cpu(0)])
    assert len(splits) == 1
    splits = gluon.utils.split_data(data, 4)
    assert len(splits) == 4
    assert splits[0].shape == (2, 2)


class TestGluonContrib:
    def test_concurrent_and_identity(self):
        from mxnet_tpu.gluon.contrib import nn as cnn
        from mxnet_tpu.gluon import nn as gnn
        net = cnn.HybridConcurrent(axis=-1)
        net.add(gnn.Dense(3), gnn.Dense(2), cnn.Identity())
        net.initialize()
        x = mx.nd.array(np.random.RandomState(0).randn(4, 5)
                        .astype(np.float32))
        out = net(x)
        assert out.shape == (4, 3 + 2 + 5)
        # identity branch is byte-exact
        np.testing.assert_allclose(out.asnumpy()[:, 5:], x.asnumpy(),
                                   rtol=1e-6)
        net.hybridize()
        out2 = net(x)
        np.testing.assert_allclose(out2.asnumpy(), out.asnumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_pixel_shuffle_2d(self):
        from mxnet_tpu.gluon.contrib import nn as cnn
        ps = cnn.PixelShuffle2D(2)
        x = np.arange(1 * 4 * 2 * 2, dtype=np.float32).reshape(1, 4, 2, 2)
        out = ps(mx.nd.array(x)).asnumpy()
        assert out.shape == (1, 1, 4, 4)
        # sub-pixel layout: out[0,0,0,0]=x[0,0,0,0], out[0,0,0,1]=x[0,1,0,0]
        assert out[0, 0, 0, 0] == x[0, 0, 0, 0]
        assert out[0, 0, 0, 1] == x[0, 1, 0, 0]
        assert out[0, 0, 1, 0] == x[0, 2, 0, 0]

    def test_pixel_shuffle_1d_3d_shapes(self):
        from mxnet_tpu.gluon.contrib import nn as cnn
        x1 = mx.nd.zeros((2, 6, 5))
        assert cnn.PixelShuffle1D(3)(x1).shape == (2, 2, 15)
        x3 = mx.nd.zeros((1, 8, 2, 3, 4))
        assert cnn.PixelShuffle3D(2)(x3).shape == (1, 1, 4, 6, 8)

    def test_sync_batchnorm_layer(self):
        from mxnet_tpu.gluon.contrib import nn as cnn
        sbn = cnn.SyncBatchNorm(in_channels=3)
        sbn.initialize()
        x = mx.nd.array(np.random.RandomState(1)
                        .randn(4, 3, 5, 5).astype(np.float32) * 2 + 1)
        with mx.autograd.record():
            out = sbn(x)
        o = out.asnumpy()
        assert abs(o.mean()) < 0.15 and abs(o.std() - 1) < 0.15

    def test_estimator_fit(self):
        from mxnet_tpu.gluon.contrib.estimator import Estimator
        from mxnet_tpu.gluon import nn as gnn
        from mxnet_tpu import gluon, io as mxio
        rng = np.random.RandomState(2)
        x = rng.randn(32, 8).astype(np.float32)
        y = (rng.rand(32) * 3).astype(np.float32) // 1
        it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=8)
        net = gnn.Dense(3)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        est = Estimator(net, metrics=mx.metric.create("acc"), trainer=tr)
        est.fit(it, epochs=2)
        vals = est.metric_values()
        assert "loss" in vals and "accuracy" in vals
        assert np.isfinite(vals["loss"])

    def test_pixel_shuffle_symbolic_path(self):
        """PixelShuffle must trace through the Symbol path (shape-free
        reshape special codes, like the reference)."""
        from mxnet_tpu.gluon.contrib import nn as cnn
        from mxnet_tpu import symbol as sym
        ps = cnn.PixelShuffle2D(2)
        out = ps(sym.var("x"))
        assert isinstance(out, sym.Symbol)

    def test_estimator_val_does_not_clobber_train_metrics(self):
        from mxnet_tpu.gluon.contrib.estimator import Estimator
        from mxnet_tpu.gluon import nn as gnn
        from mxnet_tpu import gluon, io as mxio
        rng = np.random.RandomState(3)
        x = rng.randn(16, 4).astype(np.float32)
        y = (rng.rand(16) * 2).astype(np.float32) // 1
        it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=8)
        val = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=8)
        net = gnn.Dense(2)
        net.initialize()
        est = Estimator(net, metrics=mx.metric.create("acc"),
                        trainer=gluon.Trainer(net.collect_params(), "sgd"))
        est.metric_values()  # callable before fit (no crash)
        est.fit(it, val_data=val, epochs=1)
        train_n = est.train_metrics[0].num_inst
        assert train_n == 16, "validation clobbered train metric state"
