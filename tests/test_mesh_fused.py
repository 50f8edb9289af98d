"""Mesh-fused distributed train step (ISSUE 9: parallel/fused.py).

Acceptance surface: one donated shard_map dispatch per K-step window
under the DeviceMesh, bitwise weights+optimizer-state parity with the
sequential per-param kvstore loop (SGD/momentum/Adam), bucketed
gradient collectives (<= ceil(total_MB/bucket_MB)+1 reduction ops per
step, not one per param), fsdp reduce-scatter/all-gather layout,
eligibility fallbacks, and the comm telemetry families."""
import os
import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import io as mxio
from mxnet_tpu.parallel import fused as F
from mxnet_tpu.parallel.mesh import make_mesh

_ENV_KEYS = ("MXNET_MESH_FUSED_STEP", "MXNET_SCAN_STEPS",
             "MXNET_SCAN_ACCUM", "MXNET_FUSED_STEP",
             "MXNET_COLLECTIVE_BUCKET_MB", "MXNET_COLLECTIVE_MODE",
             "MXNET_TELEMETRY")


@pytest.fixture(autouse=True)
def _restore_env():
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


def _data(nb, bs, feat=50, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(nb * bs, feat).astype(np.float32)
    y = rng.randint(0, 10, nb * bs).astype(np.float32)
    return x, y


def _state_arrays(state):
    return F._state_arrays(state)


# -- bucket planning ---------------------------------------------------------
def test_plan_buckets_size_and_boundaries():
    f32 = "float32"
    # 3 params x 1 MB each under a 2 MB budget -> ceil(3/2) = 2 buckets
    mb = (1 << 20) // 4  # elements per MB of f32
    plan = F.plan_buckets([(mb,), (mb,), (mb,)], [f32] * 3, 2.0)
    assert plan == [[0, 1], [2]]
    # dtype change forces a bucket boundary (flat concat is homogeneous)
    plan = F.plan_buckets([(8,), (8,), (8,)], [f32, "float16", f32], 64)
    assert plan == [[0], [1], [2]]
    # state-structure change forces a boundary (fsdp flat-state path)
    plan = F.plan_buckets([(8,), (8,)], [f32, f32], 64,
                          state_keys=["a", "b"])
    assert plan == [[0], [1]]
    # an oversized param still gets exactly one bucket
    plan = F.plan_buckets([(10 * mb,), (8,)], [f32] * 2, 1.0)
    assert plan == [[0], [1]]


def test_bucketed_all_reduce_op_count_and_bitwise():
    """<= ceil(total_MB / bucket_MB) + 1 reduction ops in the trace —
    NOT one per param — and per-element sums identical to per-param
    psums (bitwise)."""
    _need_devices(4)
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = make_mesh(dp=4)
    rng = np.random.RandomState(0)
    shapes = [(64, 50), (64,), (10, 64), (10,)]
    grads = [rng.randn(4, *s).astype(np.float32) for s in shapes]
    total_mb = sum(g[0].nbytes for g in grads) / (1 << 20)
    bucket_mb = total_mb / 1.5  # forces 2 buckets
    plan = F.plan_buckets(shapes, ["float32"] * 4, bucket_mb)
    assert 1 < len(plan) <= int(np.ceil(total_mb / bucket_mb)) + 1

    def body(gs):
        # each rank holds its (1, *shape) shard: drop the shard dim so
        # the reduction sums per-element across ranks
        return tuple(F.bucketed_all_reduce([g[0] for g in gs], "dp",
                                           plan))

    smapped = shard_map(body, mesh=mesh.jax_mesh,
                        in_specs=(tuple(P("dp") for _ in grads),),
                        out_specs=tuple(P() for _ in grads),
                        check_vma=False)
    jaxpr = str(jax.make_jaxpr(smapped)(tuple(grads)))
    n_psum = len(re.findall(r"\bpsum\[", jaxpr)) or \
        len(re.findall(r"\bpsum\b", jaxpr))
    assert n_psum == len(plan), jaxpr[:500]
    out = jax.jit(smapped)(tuple(grads))
    for g, o in zip(grads, out):
        np.testing.assert_array_equal(g.sum(0), np.asarray(o))


# -- parity with the sequential per-param kvstore loop -----------------------
@pytest.mark.parametrize("opt_name,opt_params", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
])
def test_mesh_fit_bitwise_parity_10_steps(opt_name, opt_params):
    """A 10-step mesh fused fit (dp=2,tp=2) is bitwise identical —
    weights AND optimizer state — to the sequential per-param kvstore
    loop (the acceptance gate)."""
    _need_devices(4)
    build, init, _rng = F._mesh_models()
    K, NB, BS = 5, 10, 16
    x, y = _data(NB, BS)
    p_mesh, s_mesh, counts, mod = F._run_mesh_fit(
        K, NB, BS, opt_name, opt_params, build, init, x, y)
    assert counts.get("mesh_window", 0) == NB // K
    assert counts.get("total", 0) <= NB // K + 1
    p_loop, s_loop = F._run_kv_loop(
        NB, BS, 4, opt_name, opt_params, build, init, x, y)
    for k in p_loop:
        np.testing.assert_array_equal(p_mesh[k], p_loop[k], err_msg=k)
    for i in s_loop:
        for a, b in zip(_state_arrays(s_mesh[i]),
                        _state_arrays(s_loop[i])):
            np.testing.assert_array_equal(a, b, err_msg=f"state {i}")


def test_mesh_fit_multi_bucket_dispatch_budget():
    """A bucket budget small enough to force multiple buckets keeps the
    one-dispatch-per-window contract and the parity."""
    _need_devices(4)
    os.environ["MXNET_COLLECTIVE_BUCKET_MB"] = "0.008"  # ~8 KB
    build, init, _rng = F._mesh_models()
    K, NB, BS = 4, 8, 16
    x, y = _data(NB, BS)
    p_mesh, _s, counts, mod = F._run_mesh_fit(
        K, NB, BS, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        build, init, x, y)
    assert len(mod._scan._plan) > 1  # the budget actually split
    assert counts.get("mesh_window", 0) == NB // K
    p_loop, _sl = F._run_kv_loop(
        NB, BS, 4, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        build, init, x, y)
    for k in p_loop:
        np.testing.assert_array_equal(p_mesh[k], p_loop[k], err_msg=k)


# -- fsdp layout -------------------------------------------------------------
def test_fsdp_layout_reduce_scatter_update():
    """The fsdp layout (reduce-scatter -> flat-shard update ->
    all-gather per bucket) matches the replicated layout to fp-
    reassociation tolerance and accounts reduce_scatter bytes."""
    _need_devices(4)
    from mxnet_tpu import telemetry as T

    build, init, _rng = F._mesh_models()
    K, BS = 2, 16
    x, y = _data(K, BS)
    os.environ["MXNET_FUSED_STEP"] = "0"

    def run(layout):
        mx.random.seed(0)
        mesh = make_mesh(dp=2, tp=2)
        mod = mx.mod.Module(build(), context=mx.cpu())
        mod.bind(data_shapes=[("data", (BS, 50))],
                 label_shapes=[("softmax_label", (BS,))])
        mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        fs = F.MeshFusedTrainStep(mod, mesh, scan_steps=K, layout=layout)
        batches = [mxio.DataBatch(
            data=[mx.nd.array(x[j * BS:(j + 1) * BS])],
            label=[mx.nd.array(y[j * BS:(j + 1) * BS])])
            for j in range(K)]
        sbatch = mxio.stage_super_batch(batches, mod._context)
        outs = fs.run_window(sbatch)
        assert outs is not False
        params, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in params.items()}, \
            {i: mod._updater.states[i]
             for i in range(len(mod._param_names))}

    before = T.REGISTRY.get("mxnet_collective_bytes_total").value(
        labels={"kind": "reduce_scatter"})
    p_rep, s_rep = run("replicated")
    p_fsdp, s_fsdp = run("fsdp")
    after = T.REGISTRY.get("mxnet_collective_bytes_total").value(
        labels={"kind": "reduce_scatter"})
    assert after > before  # fsdp window accounted reduce_scatter bytes
    for k in p_rep:
        # ring reduce-scatter may reassociate the shard sum: ~1 ulp
        np.testing.assert_allclose(p_fsdp[k], p_rep[k],
                                   rtol=2e-6, atol=2e-7, err_msg=k)
    for i in s_rep:
        for a, b in zip(_state_arrays(s_fsdp[i]),
                        _state_arrays(s_rep[i])):
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-7)


@pytest.mark.parametrize("backing", ["ndarray", "numpy"])
def test_mesh_window_staging_stays_on_the_host(backing):
    """``stage_super_batch(host=True)``, the mesh window's staging, still
    returns numpy stacks (``DeviceMesh.put_batch`` shards them itself),
    whatever backs the batches; the device staging of the same window
    holds the same values."""
    K, BS = 4, 16
    x, y = _data(K, BS)

    def wrap(a):
        return mx.nd.array(a) if backing == "ndarray" else a.copy()

    batches = [mxio.DataBatch(data=[wrap(x[j * BS:(j + 1) * BS])],
                              label=[wrap(y[j * BS:(j + 1) * BS])])
               for j in range(K)]
    staged = mxio.stage_super_batch(batches, mx.cpu(), host=True)
    on_dev = mxio.stage_super_batch(batches, mx.cpu())
    assert staged.count == on_dev.count == K
    for got, dev, want in zip(staged.data + staged.label,
                              on_dev.data + on_dev.label,
                              (x.reshape(K, BS, -1), y.reshape(K, BS))):
        assert type(got) is np.ndarray and got.flags.owndata
        assert isinstance(dev, jax.Array)
        assert np.array_equal(got, want) and np.array_equal(dev, want)


def test_fsdp_rejects_non_elementwise_optimizer():
    _need_devices(4)
    build, init, _rng = F._mesh_models()
    mod = mx.mod.Module(build(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 50))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert mod._optimizer.fused_elementwise  # the contract fsdp needs


# -- eligibility matrix ------------------------------------------------------
def _bound_module(bs=16, kvstore="dist_device_sync", optimizer="sgd"):
    build, init, _rng = F._mesh_models()
    mod = mx.mod.Module(build(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (bs, 50))],
             label_shapes=[("softmax_label", (bs,))])
    mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
    mod.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                       optimizer_params={"learning_rate": 0.1})
    return mod


def test_mesh_eligibility_matrix():
    _need_devices(4)
    os.environ["MXNET_MESH_FUSED_STEP"] = "1"
    # eligible: in-process dist store, divisible batch, fused optimizer
    mod = _bound_module()
    assert mod._mesh_fused_eligible()
    # no kvstore: the plain fused/scan path owns it
    assert not _bound_module(kvstore=None)._mesh_fused_eligible()
    # knob off
    os.environ["MXNET_MESH_FUSED_STEP"] = "0"
    assert not _bound_module()._mesh_fused_eligible()
    os.environ["MXNET_MESH_FUSED_STEP"] = "1"
    # batch not divisible by the mesh
    devs = len(jax.devices())
    assert not _bound_module(bs=devs + 1)._mesh_fused_eligible()
    # optimizer without fused_update keeps the loop
    assert not _bound_module(
        optimizer="lbsgd")._mesh_fused_eligible()
    # a real multi-worker client is never absorbed
    mod = _bound_module()
    mod._kvstore._client = object()
    assert not mod._kvstore.mesh_fusible
    assert not mod._mesh_fused_eligible()
    # monitors force the loop
    mod = _bound_module()
    mod._monitor = object()
    assert not mod._mesh_fused_eligible()


def test_mesh_fallback_then_plain_forward():
    """After mesh windows ran, a plain-executor use (score/predict/
    direct forward) must collapse the replicated buffers and work."""
    _need_devices(4)
    build, init, _rng = F._mesh_models()
    K, NB, BS = 2, 4, 16
    x, y = _data(NB, BS)
    p_mesh, _s, _c, mod = F._run_mesh_fit(
        K, NB, BS, "sgd", {"learning_rate": 0.1}, build, init, x, y)
    assert getattr(mod, "_mesh_arrays_live", False)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=BS,
                          label_name="softmax_label")
    res = mod.score(it, "acc")
    assert res and np.isfinite(res[0][1])
    assert not mod._mesh_arrays_live


# -- telemetry ---------------------------------------------------------------
def test_mesh_comm_telemetry_families_and_lane():
    _need_devices(4)
    from mxnet_tpu import telemetry as T

    os.environ["MXNET_TELEMETRY"] = "1"
    T.enable()
    try:
        build, init, _rng = F._mesh_models()
        K, NB, BS = 2, 4, 16
        x, y = _data(NB, BS)
        bytes_c = T.REGISTRY.get("mxnet_collective_bytes_total")
        ops_c = T.REGISTRY.get("mxnet_collective_ops_total")
        b0 = bytes_c.value(labels={"kind": "psum"})
        o0 = ops_c.value(labels={"kind": "psum"})
        T.reset_step_stats()
        _p, _s, _c, mod = F._run_mesh_fit(
            K, NB, BS, "sgd", {"learning_rate": 0.1}, build, init, x, y)
        plan_len = len(mod._scan._plan)
        grad_bytes = mod._scan._grad_bytes
        # per-rank ring-schedule wire bytes: 2 * B * (R-1)/R per step
        r = mod._scan._n_shards
        wire = 2 * int(grad_bytes * (r - 1) / r)
        assert bytes_c.value(labels={"kind": "psum"}) - b0 == \
            wire * NB
        assert ops_c.value(labels={"kind": "psum"}) - o0 == plan_len * NB
        bd = T.step_breakdown()
        assert "comm_collective" in bd["lanes"]
        # the reattribution keeps the lane sum within the step wall
        lane_sum = sum(bd["lanes"].values())
        assert lane_sum <= bd["wall_s"] * 1.05 + 1e-6
    finally:
        T.disable()


# -- spmd TrainStep integration ----------------------------------------------
def test_spmd_trainstep_bucketed_matches_pjit():
    _need_devices(8)
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.spmd import TrainStep

    x = mx.nd.random.uniform(shape=(16, 16))
    y = mx.nd.array(np.arange(16) % 10)

    def run(bucket_mb):
        mx.random.seed(7)
        np.random.seed(7)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
        net.initialize(mx.initializer.Xavier())
        net(x)
        for p in net.collect_params().values():
            p.data()[:] = mx.nd.random.uniform(-0.1, 0.1, p.shape)
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                         make_mesh(dp=8), example_batch=(x, y),
                         bucket_mb=bucket_mb)
        losses = [float(step(x, y)) for _ in range(4)]
        return losses, [np.asarray(p) for p in step.params], step

    l_ref, p_ref, _ = run(None)
    l_b, p_b, step_b = run(4.0)
    assert len(step_b._bucket_plan) == 1  # tiny net: one bucket
    np.testing.assert_allclose(l_b, l_ref, rtol=1e-5, atol=1e-6)
    for a, b in zip(p_b, p_ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_spmd_trainstep_bucketed_rejects_fsdp_and_bn():
    _need_devices(8)
    from mxnet_tpu import gluon
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.spmd import TrainStep

    x = mx.nd.random.uniform(shape=(16, 16))
    y = mx.nd.array(np.arange(16) % 10)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16), nn.Dense(10))
    net.initialize(mx.initializer.Xavier())
    with pytest.raises(MXNetError, match="param_axis"):
        TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                  {"learning_rate": 0.1}, make_mesh(dp=2, fsdp=4),
                  example_batch=(x, y), param_axis="fsdp", bucket_mb=4.0)
    bn = nn.HybridSequential()
    with bn.name_scope():
        bn.add(nn.Dense(16), nn.BatchNorm(), nn.Dense(10))
    bn.initialize(mx.initializer.Xavier())
    with pytest.raises(MXNetError, match="aux"):
        TrainStep(bn, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                  {"learning_rate": 0.1}, make_mesh(dp=8),
                  example_batch=(x, y), bucket_mb=4.0)


# -- collective compression (ISSUE 11) ---------------------------------------
def test_compression_2bit_shrinks_wire_bytes_and_trains():
    """MXNET_COLLECTIVE_COMPRESSION=2bit must (a) shrink the accounted
    wire bytes >= 3x vs the dense psum (32/R ring-schedule ratio: 4x at
    R=8), (b) keep training finite and tolerance-close to dense (error
    feedback bounds the drift), (c) keep the dispatch budget (the codec
    lives INSIDE the donated window)."""
    _need_devices(8)
    from mxnet_tpu import telemetry as T
    from mxnet_tpu.gradient_compression import codec_wire_bytes

    build, init, rng = F._mesh_models()
    K, NB, BS = 2, 8, 32
    x = rng.randn(NB * BS, 50).astype(np.float32)
    y = rng.randint(0, 10, NB * BS).astype(np.float32)
    opt = {"learning_rate": 0.1, "momentum": 0.9}

    bts = T.REGISTRY.counter("mxnet_collective_bytes_total")
    d0 = bts.value(labels={"kind": "psum"})
    q0 = bts.value(labels={"kind": "all_gather_q2bit"})
    p_dense, _s, _c, _m = F._run_mesh_fit(
        K, NB, BS, "sgd", opt, build, init, x, y, dp=8, tp=1)
    dense = bts.value(labels={"kind": "psum"}) - d0

    os.environ["MXNET_COLLECTIVE_COMPRESSION"] = "2bit"
    try:
        p_q, _s, counts, mod = F._run_mesh_fit(
            K, NB, BS, "sgd", opt, build, init, x, y, dp=8, tp=1)
    finally:
        os.environ.pop("MXNET_COLLECTIVE_COMPRESSION", None)
    comp = bts.value(labels={"kind": "all_gather_q2bit"}) - q0
    assert comp > 0 and dense > 0
    assert dense / comp >= 3.0, f"2bit shrink {dense / comp:.2f}x < 3x"
    # exact accounting: the ring-schedule helper, per window step
    gb, r = mod._scan._grad_bytes, mod._scan._n_shards
    assert comp == codec_wire_bytes(gb, r, "2bit") * NB
    # dispatch budget unchanged: codec is inside the trace
    assert counts.get("total", 0) / NB <= (1 + 0.25) / K
    # parity tolerance: quantized training drifts but must stay close
    for k in p_dense:
        assert np.isfinite(p_q[k]).all()
        np.testing.assert_allclose(p_q[k], p_dense[k], atol=0.08,
                                   err_msg=k)


def test_compression_fp16_half_bytes_tight_tolerance():
    _need_devices(8)
    from mxnet_tpu import telemetry as T

    build, init, rng = F._mesh_models()
    K, NB, BS = 2, 4, 32
    x = rng.randn(NB * BS, 50).astype(np.float32)
    y = rng.randint(0, 10, NB * BS).astype(np.float32)
    opt = {"learning_rate": 0.1, "momentum": 0.9}
    p_dense, _s, _c, _m = F._run_mesh_fit(
        K, NB, BS, "sgd", opt, build, init, x, y, dp=8, tp=1)
    bts = T.REGISTRY.counter("mxnet_collective_bytes_total")
    f0 = bts.value(labels={"kind": "psum_fp16"})
    os.environ["MXNET_COLLECTIVE_COMPRESSION"] = "fp16"
    try:
        p_h, _s, _c, mod = F._run_mesh_fit(
            K, NB, BS, "sgd", opt, build, init, x, y, dp=8, tp=1)
    finally:
        os.environ.pop("MXNET_COLLECTIVE_COMPRESSION", None)
    fp16 = bts.value(labels={"kind": "psum_fp16"}) - f0
    gb, r = mod._scan._grad_bytes, mod._scan._n_shards
    assert fp16 == int(gb * (r - 1) / r) * NB  # half the dense 2B(R-1)/R
    for k in p_dense:
        np.testing.assert_allclose(p_h[k], p_dense[k], rtol=2e-3,
                                   atol=2e-3, err_msg=k)


def test_compression_rejects_fsdp_and_unknown_codec():
    _need_devices(4)
    from mxnet_tpu.base import MXNetError

    build, init, _rng = F._mesh_models()
    os.environ["MXNET_FUSED_STEP"] = "0"
    mesh = make_mesh(dp=2, tp=2)
    mod = mx.mod.Module(build(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 50))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    with pytest.raises(MXNetError, match="replicated"):
        F.MeshFusedTrainStep(mod, mesh, scan_steps=2, layout="fsdp",
                             compression="2bit")
    with pytest.raises(MXNetError, match="compression"):
        F.MeshFusedTrainStep(mod, mesh, scan_steps=2,
                             compression="4bit")
