"""SPMD parallelism tests on the 8-device virtual CPU mesh (conftest sets
xla_force_host_platform_device_count=8). Parity intent: the reference tests
multi-device semantics via dist_sync_kvstore/multi_lenet; here the train
step's gradient psum and parameter sharding are exercised directly."""
import numpy as np
import os

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import DeviceMesh, make_mesh
from mxnet_tpu.parallel.spmd import TrainStep, functionalize, shard_batch


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


def _make_net():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize(mx.initializer.Xavier())
    return net


def test_mesh_basics():
    _need_devices(8)
    mesh = make_mesh(dp=4, tp=2)
    assert mesh.size() == 8
    assert mesh.size("dp") == 4
    sh = mesh.sharding("dp", None)
    assert sh.mesh.axis_names == ("dp", "tp")


def test_functionalize_matches_block():
    net = _make_net()
    x = mx.nd.random.uniform(shape=(4, 16))
    want = net(x).asnumpy()
    apply_fn, params, names = functionalize(net, x)
    import mxnet_tpu.random as r
    outs, mutated = jax.jit(apply_fn)(r.next_key(), params, (x._data,))
    np.testing.assert_allclose(np.asarray(outs[0]), want, rtol=1e-5,
                               atol=1e-6)
    assert len(names) == len(params) == 4


def test_dp_train_step_decreases_loss():
    _need_devices(8)
    mesh = make_mesh(dp=8)
    net = _make_net()
    x = mx.nd.random.uniform(shape=(16, 16))
    y = mx.nd.array(np.arange(16) % 10)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.5}, mesh, example_batch=(x, y))
    losses = [float(step(x, y)) for _ in range(10)]
    assert losses[-1] < losses[0]


def test_dp_matches_single_device():
    """DP over 8 devices must be numerically equal to 1-device training
    (the de-facto backend-equivalence check, reference check_consistency)."""
    _need_devices(8)
    x = mx.nd.random.uniform(shape=(16, 16))
    y = mx.nd.array(np.arange(16) % 10)

    def run(mesh):
        mx.random.seed(42)
        np.random.seed(42)
        net = _make_net()
        net(x)  # finish deferred init
        for p in net.collect_params().values():
            p.data()[:] = mx.nd.random.uniform(-0.1, 0.1, p.shape)
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9}, mesh,
                         example_batch=(x, y))
        ls = [float(step(x, y)) for _ in range(5)]
        return ls, [np.asarray(p) for p in step.params]

    l8, p8 = run(make_mesh(dp=8))
    l1, p1 = run(DeviceMesh({"dp": 1}, devices=jax.devices()[:1]))
    np.testing.assert_allclose(l8, l1, rtol=1e-5)
    for a, b in zip(p8, p1):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_fsdp_param_sharding():
    _need_devices(8)
    mesh = make_mesh(dp=2, fsdp=4)
    net = _make_net()
    x = mx.nd.random.uniform(shape=(8, 16))
    y = mx.nd.array(np.arange(8) % 10)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, mesh, example_batch=(x, y),
                     param_axis="fsdp")
    l0 = float(step(x, y))
    l1 = float(step(x, y))
    assert np.isfinite(l0) and np.isfinite(l1)
    # at least one parameter is actually sharded over fsdp
    specs = [p.sharding.spec for p in step.params]
    assert any("fsdp" in str(s) for s in specs), specs


def test_batchnorm_aux_updates_and_not_optimized():
    """BN running stats must advance each step (round-1 regression: TrainStep
    dropped `mutated`), and must NOT be fed through the optimizer."""
    _need_devices(8)
    mesh = make_mesh(dp=8)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16), nn.BatchNorm(), nn.Dense(10))
    net.initialize(mx.initializer.Xavier())
    x = mx.nd.random.uniform(shape=(16, 8))
    y = mx.nd.array(np.arange(16) % 10)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1, "wd": 1e-2}, mesh,
                     example_batch=(x, y))
    assert len(step._aux_idx) == 2, step._aux_idx  # running_mean + running_var
    aux_names = [step.param_names[i] for i in step._aux_idx]
    assert all("running" in n for n in aux_names), aux_names
    before = [np.asarray(a).copy() for a in step._aux_params]
    for _ in range(3):
        step(x, y)
    after = [np.asarray(a) for a in step._aux_params]
    assert any(not np.allclose(b, a) for b, a in zip(before, after)), \
        "running stats frozen"
    # optimizer state exists only for trainable params
    assert len(step.opt_state) == len(step._train_params)


def test_params_donated_no_double_buffer():
    """donate_argnums must be wired: the old param buffers are invalidated
    after a step (no 2x HBM residency)."""
    _need_devices(8)
    mesh = make_mesh(dp=8)
    net = _make_net()
    x = mx.nd.random.uniform(shape=(16, 16))
    y = mx.nd.array(np.arange(16) % 10)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, mesh, example_batch=(x, y))
    old = step._train_params
    step(x, y)
    assert any(getattr(p, "is_deleted", lambda: False)() for p in old), \
        "input param buffers were not donated"


def test_shard_batch_placement():
    _need_devices(8)
    mesh = make_mesh(dp=8)
    x = mx.nd.random.uniform(shape=(16, 4))
    xs = shard_batch(mesh, x)
    assert xs.sharding.is_fully_addressable
    assert len(xs.sharding.device_set) == 8


def _batch_arrays(path):
    from mxnet_tpu import telemetry
    return telemetry.REGISTRY.get("mxnet_spmd_batch_arrays_total").value(
        {"path": path})


def _no_device_slicing(*_a, **_k):
    raise AssertionError("a host batch went through shard_device_array")


@pytest.mark.parametrize("dtype", ["float32", "float64", "int64"])
@pytest.mark.parametrize("dp", [2, 4, 8])
def test_shard_batch_host_array_goes_shard_by_shard(dp, dtype, monkeypatch):
    """A host batch is sliced on the host: each device holds exactly its
    own rows, in the dtype jnp.asarray gives, and no device program
    slices a whole copy of the batch (jax's shard_device_array)."""
    import jax.numpy as jnp
    from jax._src import array as jax_array
    _need_devices(dp)
    mesh = make_mesh(devices=jax.devices()[:dp], dp=dp)
    x = (np.random.randn(4 * dp, 3, 5) * 100).astype(dtype)
    monkeypatch.setattr(jax_array, "shard_device_array", _no_device_slicing)
    host, device = _batch_arrays("host"), _batch_arrays("device")
    xs = shard_batch(mesh, x)
    assert _batch_arrays("host") - host == 1
    assert _batch_arrays("device") == device
    assert xs.dtype == jnp.asarray(x).dtype
    assert len(xs.addressable_shards) == dp
    for shard in xs.addressable_shards:
        rows = shard.index[0]
        assert rows.stop - rows.start == 4
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      x[rows].astype(xs.dtype))
    np.testing.assert_array_equal(np.asarray(xs), x.astype(xs.dtype))


def test_shard_batch_device_array_is_resharded():
    """An NDArray on one device keeps the device path: resharded onto the
    mesh, counted as path=device."""
    _need_devices(4)
    mesh = make_mesh(devices=jax.devices()[:4], dp=4)
    x = mx.nd.array(np.arange(32, dtype=np.float32).reshape(8, 4))
    host, device = _batch_arrays("host"), _batch_arrays("device")
    xs = shard_batch(mesh, x)
    assert _batch_arrays("device") - device == 1
    assert _batch_arrays("host") == host
    assert {s.device for s in xs.addressable_shards} == \
        set(jax.devices()[:4])
    for shard in xs.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      x.asnumpy()[shard.index])


def test_host_fed_step_equals_preplaced_step():
    """TrainStep on dp=4 fed host arrays (placed shard by shard from host
    memory) and fed the same batches placed the old way (jnp.asarray onto
    one device, then resharded) trains bit for bit alike."""
    import jax.numpy as jnp
    _need_devices(4)
    rng = np.random.RandomState(3)
    batches = [(rng.randn(16, 16), (np.arange(16) % 10).astype(np.float32))
               for _ in range(3)]
    example = tuple(mx.nd.array(a) for a in batches[0])

    def run(place):
        mx.random.seed(11)
        np.random.seed(11)
        mesh = make_mesh(devices=jax.devices()[:4], dp=4)
        net = _make_net()
        net(example[0])
        for p in net.collect_params().values():
            p.data()[:] = mx.nd.random.uniform(-0.1, 0.1, p.shape)
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9}, mesh,
                         example_batch=example)
        losses = [float(step(*(place(mesh, a) for a in b)))
                  for b in batches]
        return losses, [np.asarray(p) for p in step.params]

    host_l, host_p = run(lambda mesh, a: a)
    old_l, old_p = run(lambda mesh, a: jax.device_put(
        jnp.asarray(a), mesh.sharding("dp")))
    assert host_l == old_l
    for a, b in zip(host_p, old_p):
        np.testing.assert_array_equal(a, b)


def test_sync_to_block():
    mesh = DeviceMesh({"dp": 1}, devices=jax.devices()[:1])
    net = _make_net()
    x = mx.nd.random.uniform(shape=(4, 16))
    y = mx.nd.array([0, 1, 2, 3])
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.5}, mesh, example_batch=(x, y))
    pname = step.param_names[0]
    before = net.collect_params()[pname].data().asnumpy().copy()
    step(x, y)
    step.sync_to_block()
    after = net.collect_params()[pname].data().asnumpy()
    assert not np.allclose(before, after)


def test_remat_matches_plain():
    """remat=True (MXNET_BACKWARD_DO_MIRROR parity: recompute activations
    in backward) must be numerically identical to the plain step."""
    _need_devices(8)
    x = mx.nd.random.uniform(shape=(16, 16))
    y = mx.nd.array(np.arange(16) % 10)

    def run(remat):
        mx.random.seed(7)
        np.random.seed(7)
        net = _make_net()
        net(x)
        for p in net.collect_params().values():
            p.data()[:] = mx.nd.random.uniform(-0.1, 0.1, p.shape)
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9},
                         make_mesh(dp=8), example_batch=(x, y),
                         remat=remat)
        ls = [float(step(x, y)) for _ in range(5)]
        return ls, [np.asarray(p) for p in step.params]

    l_plain, p_plain = run(False)
    l_remat, p_remat = run(True)
    np.testing.assert_allclose(l_remat, l_plain, rtol=1e-5)
    for a, b in zip(p_remat, p_plain):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_multihost_env_contract():
    """init_multihost resolves the DMLC_* rendezvous contract; a
    single-worker setup is a clean no-op (parity: ps-lite env vars)."""
    import mxnet_tpu.parallel.multihost as mh
    mh._initialized = False
    old = {k: os.environ.get(k) for k in
           ("DMLC_PS_ROOT_URI", "DMLC_NUM_WORKER", "DMLC_RANK",
            "DMLC_WORKER_ID")}
    try:
        os.environ["DMLC_NUM_WORKER"] = "1"
        mh.init_multihost()          # no-op, must not try to rendezvous
        assert mh._initialized
        mh._initialized = False
        os.environ["DMLC_PS_ROOT_URI"] = "10.0.0.1"
        os.environ["DMLC_NUM_WORKER"] = "4"
        os.environ.pop("DMLC_RANK", None)
        os.environ.pop("DMLC_WORKER_ID", None)
        with pytest.raises(mx.MXNetError):
            mh.init_multihost()      # coordinator without rank: reject
    finally:
        mh._initialized = False
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert mh.process_count() >= 1
