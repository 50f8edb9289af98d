"""Streaming data plane (ISSUE 19: io_pipeline.py).

Acceptance surface (docs/data.md):

* shard-order determinism — the seeded per-epoch shard order is a
  function of (num_shards, seed, epoch) ONLY: 0/1/2/4 workers deliver
  the SAME batch sequence, so the pipeline can never change what a fit
  computes;
* bitwise fit parity — a K=8 scanned fit fed by the multi-worker
  window feed (``MXNET_DATA_WORKERS>0``) equals the serial inline path
  bit for bit: weights AND optimizer state, SGD and Adam, on the
  single-executor scan AND the dp x tp mesh window, with
  dispatches/step unchanged;
* dead-reader rebalance — a reader dying mid-epoch requeues its shards
  onto the survivors, every batch delivered exactly once, typed
  ``DataReaderError`` only when ALL readers are gone;
* bounded backpressure — a stalled consumer caps buffered batches at
  max_inflight x queue_depth (RSS stays flat no matter how slow the
  train thread is);
* PrefetchingIter.reset() regression — two epochs through a reset are
  identical sequences (the old code let a straggler thread from the
  previous generation produce into the new epoch's queues);
* observability — the ``data_starved`` alert rule ships in the default
  pack and the queue-depth probe reports live pipelines only;
* graftlint — the pipeline's thread/queue lifecycle proves clean under
  the v3 path-sensitive analysis (no waivers).
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mxio
from mxnet_tpu import io_pipeline as mxpipe
from mxnet_tpu import profiler as prof
from mxnet_tpu.chaos import failpoints as chaos

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _chaos_clean():
    chaos.reset()
    yield
    chaos.reset()


def _mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _init_params(seed=5):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, 20) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}


def _dataset(n, feat=20, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, feat).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.float32)
    return x, y


def _pipeline(x, y, workers, batch_size=16, batches_per_shard=2,
              seed=11, **kw):
    src = mxpipe.NDArraySource(x, y, batch_size=batch_size,
                               batches_per_shard=batches_per_shard)
    return mxpipe.DataPipeline(src, workers=workers, seed=seed, **kw)


def _drain_rows(p):
    """One epoch; returns the delivered row-index sequence."""
    rows = []
    for batch in p:
        rows.append(np.asarray(batch.index))
    return np.concatenate(rows)


# -- shard-order determinism --------------------------------------------------
def test_order_identical_across_worker_counts():
    """Worker count is a THROUGHPUT knob, never an order knob: 0/1/2/4
    workers deliver the same seeded batch sequence."""
    x, y = _dataset(256)
    seqs = {}
    for w in (0, 1, 2, 4):
        p = _pipeline(x, y, w)
        try:
            seqs[w] = _drain_rows(p)
        finally:
            p.close()
    for w in (1, 2, 4):
        np.testing.assert_array_equal(seqs[0], seqs[w],
                                      err_msg=f"workers={w}")
    assert sorted(seqs[0].tolist()) == list(range(256))


def test_epoch_advances_the_order_and_reset_replays_it():
    """The epoch index enters the permutation seed — successive epochs
    shuffle differently, while re-running the SAME epoch (a fresh
    pipeline) replays it exactly."""
    x, y = _dataset(256)
    p = _pipeline(x, y, 2)
    try:
        e0 = _drain_rows(p)
        p.reset()
        e1 = _drain_rows(p)
    finally:
        p.close()
    assert not np.array_equal(e0, e1), "epoch must advance the order"
    q = _pipeline(x, y, 3)
    try:
        np.testing.assert_array_equal(e0, _drain_rows(q))
    finally:
        q.close()


def test_epoch_shard_order_contract():
    """epoch_shard_order is a pure function of (num_shards, seed,
    epoch) sliced round-robin by (num_parts, part_index): the parts
    partition the permutation, and no worker count appears anywhere in
    the signature."""
    full = mxpipe.epoch_shard_order(64, seed=9, epoch=3)
    assert sorted(full) == list(range(64))
    parts = [mxpipe.epoch_shard_order(64, seed=9, epoch=3,
                                      num_parts=4, part_index=i)
             for i in range(4)]
    assert sorted(s for p in parts for s in p) == list(range(64))
    assert parts[1] == full[1::4]


# -- bitwise fit parity: pipeline on vs off -----------------------------------
def _fit(monkeypatch, workers, x, y, optimizer="sgd", opt_params=None,
         num_epoch=2, scan_steps=8):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_SCAN_STEPS", str(scan_steps))
    if workers:
        monkeypatch.setenv("MXNET_DATA_WORKERS", str(workers))
    else:
        monkeypatch.delenv("MXNET_DATA_WORKERS", raising=False)
    mx.random.seed(0)
    it = _pipeline(x, y, workers)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    prof.reset_dispatch_counts()
    try:
        mod.fit(it, num_epoch=num_epoch, optimizer=optimizer,
                optimizer_params=opt_params or {"learning_rate": 0.05},
                arg_params={k: v.copy()
                            for k, v in _init_params().items()})
    finally:
        it.close()
    params, _ = mod.get_params()
    return (mod, {k: v.asnumpy() for k, v in params.items()},
            prof.dispatch_counts().get("total", 0))


def _opt_state_leaves(mod):
    import pickle
    states = pickle.loads(mod.get_optimizer_states())
    leaves = {}
    for i in states:
        s = states[i] if isinstance(states[i], tuple) else (states[i],)
        leaves[i] = [x.asnumpy() for x in s if x is not None]
    return leaves


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_fit_parity_pipeline_on_off(monkeypatch, optimizer, opt_params):
    """The acceptance gate: a K=8 scanned 2-epoch fit with the window
    feed armed (MXNET_DATA_WORKERS=2) is bitwise identical — weights
    AND optimizer state — to the serial inline path, and issues the
    SAME number of dispatches (the pipeline moves staging off-thread,
    it never adds a dispatch)."""
    x, y = _dataset(256)  # 16 batches of 16 -> 2 windows of K=8
    m_on, p_on, d_on = _fit(monkeypatch, 2, x, y, optimizer, opt_params)
    assert m_on._scan is not None and m_on._scan.windows == 4, \
        "scanned windows did not engage under the feed"
    m_off, p_off, d_off = _fit(monkeypatch, 0, x, y, optimizer,
                               opt_params)
    for k in p_on:
        np.testing.assert_array_equal(p_on[k], p_off[k], err_msg=k)
    s_on, s_off = _opt_state_leaves(m_on), _opt_state_leaves(m_off)
    for i in s_on:
        for a, b in zip(s_on[i], s_off[i]):
            np.testing.assert_array_equal(a, b, err_msg=f"state {i}")
    assert d_on == d_off, "the feed changed the dispatch count"


@pytest.mark.parametrize("host", [False, True])
def test_window_feed_stages_what_the_train_thread_would(host):
    """The feed thread's staged window is bitwise the in-thread one:
    stacked on the module's device, or numpy stacks under ``host``
    (the mesh window), and counted as staged ahead."""
    import jax
    from mxnet_tpu import telemetry
    W, ctx = 4, mx.cpu(1)
    x, y = _dataset(16 * W)
    batches = [mxio.DataBatch(data=[mx.nd.array(x[i:i + 16])],
                              label=[mx.nd.array(y[i:i + 16])], pad=0)
               for i in range(0, len(x), 16)]
    ahead = telemetry.REGISTRY.get("mxnet_io_stage_windows_total")
    before = ahead.value({"when": "ahead"})
    feed = mxpipe.WindowFeed(iter(batches), W, ctx, lambda b: True,
                             host=host)
    try:
        kind, got, fed, _span = feed.get()
    finally:
        feed.close()
    assert kind == "window" and got == batches
    assert ahead.value({"when": "ahead"}) - before == 1
    here = mxio.stage_super_batch(batches, ctx, host=host)
    assert fed.count == here.count == W
    for a, b, want in zip(fed.data + fed.label, here.data + here.label,
                          (x.reshape(W, 16, -1), y.reshape(W, 16))):
        if host:
            assert type(a) is type(b) is np.ndarray
        else:
            assert isinstance(a, jax.Array) and isinstance(b, jax.Array)
            assert a.devices() == b.devices() == {ctx.jax_device}
        assert np.array_equal(np.asarray(a), want)
        assert np.array_equal(np.asarray(b), want)


def test_fit_parity_mesh_window(monkeypatch):
    """Same gate on the dp=2 x tp=2 mesh window path (host-staged
    super-batches): feed on == feed off, weights AND updater state."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from mxnet_tpu.parallel import fused as F
    from mxnet_tpu.parallel.mesh import make_mesh

    build, init, _rng = F._mesh_models()
    rng = np.random.RandomState(1)
    x = rng.randn(16 * 16, 50).astype(np.float32)
    y = rng.randint(0, 10, 16 * 16).astype(np.float32)

    def fit(workers):
        monkeypatch.setenv("MXNET_MESH_FUSED_STEP", "1")
        monkeypatch.setenv("MXNET_SCAN_STEPS", "8")
        if workers:
            monkeypatch.setenv("MXNET_DATA_WORKERS", str(workers))
        else:
            monkeypatch.delenv("MXNET_DATA_WORKERS", raising=False)
        mx.random.seed(0)
        mesh = make_mesh(dp=2, tp=2)
        it = _pipeline(x, y, workers)
        mod = mx.mod.Module(build(), context=mx.cpu())
        try:
            with mesh:
                mod.fit(it, num_epoch=1, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9},
                        kvstore="dist_device_sync",
                        arg_params={k: v.copy()
                                    for k, v in init.items()})
            assert mod._mesh is not None, "mesh path did not engage"
        finally:
            it.close()
        params, _ = mod.get_params()
        return ({k: v.asnumpy() for k, v in params.items()},
                {i: [np.asarray(a) for a in
                     F._state_arrays(mod._updater.states[i])]
                 for i in range(len(mod._param_names))})

    p_on, s_on = fit(2)
    p_off, s_off = fit(0)
    for k in p_on:
        np.testing.assert_array_equal(p_on[k], p_off[k], err_msg=k)
    for i in s_on:
        for a, b in zip(s_on[i], s_off[i]):
            np.testing.assert_array_equal(a, b, err_msg=f"state {i}")


# -- dead-reader rebalance ----------------------------------------------------
def test_dead_reader_rebalances_exactly_once():
    """One reader dying mid-epoch is INVISIBLE to the consumer: the
    survivors absorb its shards, the delivered sequence equals the
    healthy baseline (exactly once, same order), and the rebalance
    counter ticks."""
    from mxnet_tpu import telemetry
    x, y = _dataset(512)
    p = _pipeline(x, y, 0)
    try:
        baseline = _drain_rows(p)
    finally:
        p.close()
    reb0 = telemetry._DATA_REBALANCE.value()
    chaos.arm("io/reader/read", "raise", hits=9, count=1)
    p = _pipeline(x, y, 3)
    try:
        seq = _drain_rows(p)
    finally:
        p.close()
    np.testing.assert_array_equal(seq, baseline)
    assert telemetry._DATA_REBALANCE.value() - reb0 >= 1


def test_all_readers_dead_is_typed_never_a_stall():
    """Only when EVERY reader is gone does the pipeline raise — and it
    raises the typed DataReaderError promptly instead of wedging the
    train thread."""
    x, y = _dataset(256)
    chaos.arm("io/reader/read", "raise", hits=1)  # every read raises
    p = _pipeline(x, y, 3)
    t0 = time.perf_counter()
    try:
        with pytest.raises(mxpipe.DataReaderError):
            _drain_rows(p)
    finally:
        p.close()
    assert time.perf_counter() - t0 < 30.0


# -- bounded backpressure -----------------------------------------------------
def test_backpressure_bounded_under_stalled_consumer():
    """A consumer that never shows up caps the buffered batches at
    max_inflight x queue_depth; draining afterwards still yields the
    full epoch."""
    x, y = _dataset(1024)
    p = _pipeline(x, y, 2, queue_depth=2, max_inflight=3)
    try:
        first = p.next()  # starts the pool, consumes one batch
        time.sleep(0.5)   # readers run ahead into the bound
        assert p.buffered() <= 3 * 2, \
            f"buffered {p.buffered()} > max_inflight*depth"
        rows = [np.asarray(first.index)]
        for batch in p:
            rows.append(np.asarray(batch.index))
        assert sorted(np.concatenate(rows).tolist()) == list(range(1024))
    finally:
        p.close()


# -- PrefetchingIter.reset() regression ---------------------------------------
def test_prefetching_iter_reset_identical_epochs():
    """Regression: reset() used to leave the OLD generation's threads
    joinable-but-alive long enough to produce a stale batch into the
    new epoch's queues.  Two epochs through a reset must be identical
    sequences, every time."""
    base = np.arange(128).reshape(128, 1)
    for _ in range(5):
        it = mxio.NDArrayIter(base.copy(), None, 16)
        pit = mxio.PrefetchingIter(it)
        a = [b.data[0].asnumpy().ravel() for b in pit]
        pit.reset()
        b = [b.data[0].asnumpy().ravel() for b in pit]
        assert len(a) == len(b) == 8
        np.testing.assert_array_equal(np.concatenate(a),
                                      np.concatenate(b))


# -- observability ------------------------------------------------------------
def test_data_starved_rule_ships_and_probe_tracks_live_pipelines():
    from mxnet_tpu.telemetry import alerts
    rules = {r.name: r for r in alerts.default_rules()}
    assert "data_starved" in rules
    assert rules["data_starved"].severity == "warn"
    assert rules["data_starved"].kind == "rate"
    x, y = _dataset(128)
    p = _pipeline(x, y, 2)
    try:
        p.next()  # pool is live and fresh
        assert any(lbl.get("role") == "shards"
                   for lbl, _v in mxpipe.queue_depth_samples())
    finally:
        p.close()


# -- lint ---------------------------------------------------------------------
@pytest.mark.slow
def test_graftlint_clean():
    """The pipeline's thread/queue lifecycle proves clean under the v3
    path-sensitive analysis — no new waivers rode in with this layer."""
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "graftlint.py"),
         os.path.join(_REPO, "mxnet_tpu", "io_pipeline.py"), "--json"],
        capture_output=True, text=True, timeout=300)
    import json
    doc = json.loads(r.stdout)
    assert doc["findings"] == [], doc["findings"]
