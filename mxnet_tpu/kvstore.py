"""KVStore: key-value store for parameter synchronization.

Re-design of reference src/kvstore/* + python/mxnet/kvstore.py. The reference
stack (CommDevice GPU trees: comm.h:451, NCCL: kvstore_nccl.h, ps-lite
workers/servers: kvstore_dist.h) is replaced by:

- 'local'/'device'/'nccl': single-process store; cross-device reduce is an
  explicit sum (a Module trains on one device; under a
  mesh the SPMD path in mxnet_tpu.parallel does reduction as XLA psum and
  this store only orchestrates).
- 'ici': SPMD facade — parameters live sharded on a DeviceMesh; push/pull
  are no-ops because the train step's psum already synchronized gradients
  (the reference's "comm overlaps compute" falls out of one fused program).
- 'dist_sync'/'dist_async'/'dist_device_sync': multi-worker semantics.
  Rank/size come from DMLC_ROLE/DMLC_NUM_WORKER env (same contract as
  ps-lite); the transport is the mxnet_tpu.kvstore_server socket protocol
  on localhost/DCN. With a single worker they degrade to 'local'.

Updater semantics preserved: set_optimizer installs the optimizer in-store
(update_on_kvstore), matching kvstore_dist_server.h ApplyUpdates.
"""
from __future__ import annotations

import os
import pickle

from . import ndarray as nd
from . import optimizer as opt
from .base import MXNetError
from .ndarray import NDArray


def _ctx_key(ctx):
    return (ctx.device_type, ctx.device_id)


def _account_wire(op, grouped_values):
    """Telemetry: logical payload bytes entering/leaving the store
    (``mxnet_kvstore_bytes_total{op=push|pull}``).  Shape x itemsize host
    arithmetic only — never a device sync; sparse arrays count their
    logical (dense) shape."""
    import numpy as _np

    from . import telemetry as _telemetry
    total = n = 0
    for vlist in grouped_values:
        if not isinstance(vlist, (list, tuple)):
            vlist = [vlist]
        for v in vlist:
            shape = getattr(v, "shape", None)
            dtype = getattr(v, "dtype", None)
            if shape is None or dtype is None:
                continue
            total += int(_np.prod(shape, dtype=_np.int64)) * \
                _np.dtype(dtype).itemsize
            n += 1
    _telemetry.record_kvstore(op, total, n)
    # the store path IS gradient communication: mirror it into the
    # collective families so mxnet_collective_bytes_total{kind} covers
    # both the mesh-fused step and this residual per-param path
    _telemetry.record_collective(f"kvstore_{op}", total, 0.0, n)


class KVStore:
    """Single-process key-value store (parity: include/mxnet/kvstore.h:59 +
    kvstore_local.h)."""

    def __init__(self, kv_type="local"):
        self._type = kv_type
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._str_key_dict = {}
        self._compression_params = None
        self._compression = None

    # -- identity ----------------------------------------------------------
    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    @property
    def mesh_fusible(self):
        """True when ``Module.fit`` may absorb this store's per-step
        gradient synchronization into the mesh-fused train step
        (parallel/fused.py): the store then serves only init/broadcast
        and optimizer-state fetch, and gradient reduction runs as
        bucketed XLA collectives inside the donated window.  False when
        the store carries semantics the traced collectives would drop
        (gradient compression's quantize/residual cycle)."""
        return getattr(self, "_compression", None) is None

    # -- data --------------------------------------------------------------
    def init(self, key, value):
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                continue
            self._store[k] = v.copy()

    def push(self, key, value, priority=0):
        """Sum values across devices, optionally run the in-store updater
        (parity: KVStoreLocal::Push → Comm*::Reduce; row_sparse values
        reduce sparsely and reach the updater as row_sparse so lazy
        optimizer updates touch only the pushed rows)."""
        from .ndarray import sparse as _sp
        keys, values = _key_grouped(key, value)
        _account_wire("push", values)
        for k, vlist in zip(keys, values):
            if k not in self._store:
                raise MXNetError(f"key {k} was not init()ed")
            stored = self._store[k]
            if any(isinstance(v, _sp.BaseSparseNDArray) for v in vlist):
                merged = vlist[0]
                for v in vlist[1:]:
                    merged = _sp.elemwise_add(merged, v)
                if self._updater is not None:
                    self._updater(_updater_key(k), merged, stored)
                elif isinstance(merged, _sp.BaseSparseNDArray) and \
                        not isinstance(stored, _sp.BaseSparseNDArray):
                    stored._set_data(merged.todense()._data)
                else:
                    self._store[k] = merged.copy()
                continue
            if getattr(self, "_compression", None) is not None:
                vlist = [self._compress_cycle(k, i, v)
                         for i, v in enumerate(vlist)]
            merged = vlist[0].copyto(stored.ctx) if len(vlist) == 1 else \
                nd.add_n(*[v.as_in_context(stored.ctx) for v in vlist])
            if self._updater is not None:
                self._updater(_updater_key(k), merged, stored)
            else:
                stored._set_data(merged._data)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        """Broadcast stored value to out arrays (parity: pull → Broadcast).

        Sparse outs are skipped when ignore_sparse (reference behavior) and
        rejected otherwise — a dense broadcast into a RowSparseNDArray would
        desync its indices; use row_sparse_pull."""
        from .ndarray.sparse import BaseSparseNDArray
        assert out is not None
        keys, outs = _key_grouped(key, out)
        _account_wire("pull", outs)
        for k, olist in zip(keys, outs):
            stored = self._store[k]
            for o in olist:
                if isinstance(o, BaseSparseNDArray):
                    if ignore_sparse:
                        continue
                    raise MXNetError(
                        "pull into a sparse NDArray is not defined; use "
                        "row_sparse_pull(key, out, row_ids=...)")
                o._set_data(stored.as_in_context(o.ctx)._data)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def broadcast(self, key, value, out, priority=0):
        self.init(key, value)
        self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull ONLY the requested rows as RowSparseNDArray(s) (parity:
        KVStore::PullRowSparse, kvstore_dist.h:243 — the bandwidth win for
        embedding-style parameters)."""
        import numpy as np
        import jax.numpy as jnp
        from .ndarray.sparse import RowSparseNDArray
        assert out is not None and row_ids is not None
        keys, outs = _key_grouped(key, out)
        ids_list = row_ids if isinstance(row_ids, (list, tuple)) else \
            [row_ids] * len(keys)
        for k, olist, rid in zip(keys, outs, ids_list):
            stored = self._store[k]
            rows = np.unique(np.asarray(
                rid.asnumpy() if isinstance(rid, NDArray) else rid
            ).astype(np.int64).ravel())
            vals = self._fetch_rows(k, stored, rows)
            for o in olist:
                if not isinstance(o, RowSparseNDArray):
                    raise MXNetError(
                        "row_sparse_pull requires row_sparse out arrays "
                        "(a dense scatter would zero the un-pulled rows)")
                o._indices = jnp.asarray(rows)
                o._set_data(jnp.asarray(vals))

    def _fetch_rows(self, k, stored, rows):
        import jax.numpy as jnp
        data = stored.todense()._data \
            if getattr(stored, "stype", "default") != "default" \
            else stored._data
        return data[jnp.asarray(rows)]

    # -- updater / optimizer ----------------------------------------------
    def set_optimizer(self, optimizer):
        """Run this optimizer in-store on push (parity: update_on_kvstore;
        dist servers receive it pickled, kvstore_dist_server.h:155)."""
        self._optimizer = optimizer
        self._set_updater(opt.get_updater(optimizer))

    def _set_updater(self, updater):
        self._updater = updater

    def _send_command_to_servers(self, head, body):
        pass  # single-process: nothing to send

    def get_num_dead_node(self, node_id=0, timeout=60):
        """Non-dist stores have no remote peers to lose (parity:
        KVStore::get_num_dead_node, include/mxnet/kvstore.h:353)."""
        return 0

    def get_optimizer_states(self, dump_optimizer=False):
        """Optimizer state as bytes — the file-free primitive the
        checkpoint subsystem stores in its manifest-tracked blobs (dist
        stores fetch from the server, where the updater actually ran)."""
        assert self._updater is not None, "updater is not set"
        return self._updater.get_states(dump_optimizer)

    def set_optimizer_states(self, states):
        """Install optimizer state bytes (inverse of
        get_optimizer_states)."""
        assert self._updater is not None, "updater is not set"
        self._updater.set_states(states)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        data = self.get_optimizer_states(dump_optimizer)
        # atomic temp + os.replace: same no-torn-writes contract as
        # nd.save / the checkpoint subsystem
        tmp = f"{fname}.tmp-{os.getpid()}"
        with open(tmp, "wb") as fout:
            fout.write(data)
        os.replace(tmp, fname)

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as f:
            self.set_optimizer_states(f.read())

    # -- compression / barrier --------------------------------------------
    def set_gradient_compression(self, compression_params):
        """Arm 2-bit gradient compression (parity: kvstore.py
        set_gradient_compression — device/dist stores only; the reference
        raises for plain local too)."""
        if not ("device" in self._type or "dist" in self._type):
            raise MXNetError(
                "gradient compression is only supported for 'device' and "
                "'dist*' kvstores")
        from . import gradient_compression as gc
        self._compression_params = dict(compression_params)
        self._compression = gc.create(compression_params)

    def _compress_cycle(self, k, i, value):
        """Local stores quantize+dequantize each pushed value (with
        per-(key, device) residual) so compressed training semantics are
        identical whether the grads cross a wire or not (parity: the
        reference's CommDevice compressed reduce path)."""
        import numpy as np
        gc = getattr(self, "_compression", None)
        if gc is None:
            return value
        deq = gc.dequantize(gc.quantize((k, i), value.asnumpy()),
                            tuple(value.shape), np.float32)
        return nd.array(deq, ctx=value.ctx, dtype=value.dtype)

    def barrier(self):
        nd.waitall()


class KVStoreICI(KVStore):
    """XLA-collective store (SURVEY.md §5 'KVStore(ici)' north star).

    Gradient allreduce runs as ONE jitted XLA computation over the devices
    holding the pushed copies: per-device arrays are assembled into a
    sharded jax.Array over a throwaway 1-axis mesh and summed with
    replicated out_shardings — XLA lowers that to an all-reduce riding the
    ICI torus (CommDevice/NCCL equivalent, zero host round-trips). pull
    hands back each device's replicated shard without any transfer.
    gluon.Trainer / Module.fit select it with kvstore='ici'."""

    def __init__(self):
        super().__init__("ici")
        self._fn_cache = {}
        self._replicated = {}  # key -> replicated jax.Array after push

    def _allreduce(self, vlist):
        import jax
        import numpy as _np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        devs = tuple(next(iter(v._data.devices())) for v in vlist)
        if len(set(devs)) != len(devs):
            # duplicate devices (e.g. tests faking multi-device on one
            # chip): reduce on the first copy's device — mixed partial
            # duplication would otherwise feed jit incompatible devices
            total = vlist[0]._data
            for v in vlist[1:]:
                total = total + jax.device_put(v._data, devs[0])  # graftlint: disable=per-param-collective -- duplicate-device fallback (tests faking multi-device): a handful of copies once, not a per-step loop
            return None, total
        shape = tuple(vlist[0].shape)
        ckey = (devs, shape, str(vlist[0].dtype))
        entry = self._fn_cache.get(ckey)
        if entry is None:
            mesh = Mesh(_np.array(devs), ("dp",))
            fn = jax.jit(lambda x: x.sum(0),
                         out_shardings=NamedSharding(mesh, P()))
            entry = (mesh, fn)
            self._fn_cache[ckey] = entry
        mesh, fn = entry
        shards = [v._data[None] for v in vlist]  # (1,)+shape, on-device
        stacked = jax.make_array_from_single_device_arrays(
            (len(vlist),) + shape, NamedSharding(mesh, P("dp")), shards)
        return fn(stacked), None

    def push(self, key, value, priority=0):
        from .ndarray import sparse as _sp
        keys, values = _key_grouped(key, value)
        for k, vlist in zip(keys, values):
            if k not in self._store:
                raise MXNetError(f"key {k} was not init()ed")
            if any(isinstance(v, _sp.BaseSparseNDArray) for v in vlist) or \
                    len(vlist) == 1:
                # sparse or single-device: the local reduction is optimal
                # (super().push accounts these bytes itself)
                self._replicated.pop(k, None)
                super().push(k, vlist, priority)  # graftlint: disable=per-param-collective -- per-KEY delegation of the multi-key API; each key reduces once in-store
                continue
            _account_wire("push", [vlist])
            replicated, plain = self._allreduce(vlist)
            stored = self._store[k]
            if replicated is None:
                merged_dev0 = plain
            else:
                # the shard on the stored array's device (no transfer)
                sdev = next(iter(stored._data.devices()))
                merged_dev0 = None
                for shard in replicated.addressable_shards:
                    if shard.device == sdev:
                        merged_dev0 = shard.data
                        break
                if merged_dev0 is None:
                    merged_dev0 = replicated.addressable_shards[0].data
            merged = NDArray(merged_dev0, stored.ctx)
            if self._updater is not None:
                self._replicated.pop(k, None)  # weights changed: rebroadcast
                self._updater(_updater_key(k), merged, stored)
            else:
                stored._set_data(merged._data)
                if replicated is not None:
                    self._replicated[k] = replicated

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        from .ndarray.sparse import BaseSparseNDArray
        assert out is not None
        keys, outs = _key_grouped(key, out)
        _account_wire("pull", outs)
        for k, olist in zip(keys, outs):
            replicated = self._replicated.get(k)
            stored = self._store[k]
            for o in olist:
                if isinstance(o, BaseSparseNDArray):
                    if ignore_sparse:
                        continue
                    raise MXNetError("pull into sparse: use row_sparse_pull")
                odev = next(iter(o._data.devices()))
                shard_data = None
                if replicated is not None:
                    for shard in replicated.addressable_shards:
                        if shard.device == odev:
                            shard_data = shard.data
                            break
                if shard_data is not None:
                    o._set_data(shard_data)
                else:
                    import jax
                    o._set_data(jax.device_put(stored._data, odev))  # graftlint: disable=per-param-collective -- boundary transfer per out array after the in-store allreduce; the mesh fused step removes pulls from eligible hot paths


class KVStoreDist(KVStore):
    """Multi-worker store. Rank/size from DMLC_* env (contract parity with
    ps-lite, ps::StartAsync); transport via kvstore_server when a scheduler
    address is configured, else single-worker degradation."""

    def __init__(self, kv_type):
        super().__init__(kv_type)
        self._rank = int(os.environ.get("DMLC_RANK",
                                        os.environ.get("DMLC_WORKER_ID", 0)))
        self._num_workers = int(os.environ.get("DMLC_NUM_WORKER", 1))
        self._client = None
        self._chunked = {}  # key -> chunk layout (None = unchunked)
        root_uri = os.environ.get("DMLC_PS_ROOT_URI")
        if self._num_workers > 1 and root_uri:
            from .kvstore_server import KVClient
            port = int(os.environ.get("DMLC_PS_ROOT_PORT", 9091))
            self._client = KVClient(root_uri, port, self._rank,
                                    self._num_workers)

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    @property
    def mesh_fusible(self):
        """Only the single-process degradation may fuse: with a live
        multi-worker client the server-side sum over DCN is the sync
        mechanism and must keep running per push."""
        return self._client is None and \
            getattr(self, "_compression", None) is None

    @staticmethod
    def _layout_from_rows_per(k, shape, rows_per):
        """Materialize the chunk-key plan for a given rows-per-chunk.
        The single authority for the ``k#chunkN`` namespace — used both by
        the local bound computation and by workers adopting rank 0's
        recorded layout, so the namespaces cannot diverge."""
        if rows_per <= 0:
            return [(k, 0, shape[0] if shape else 0)]
        return [(f"{k}#chunk{i}", start, min(start + rows_per, shape[0]))
                for i, start in enumerate(range(0, shape[0], rows_per))]

    @classmethod
    def _chunk_layout(cls, k, shape):
        """Row-chunk plan for a big dense array under derived keys
        (parity: kvstore_dist.h big-array key sharding over servers,
        MXNET_KVSTORE_BIGARRAY_BOUND). Bounds the wire frame size and
        lets chunk pushes pipeline through the server. Returns
        [(key, row_start, row_stop)] — a single entry means unchunked."""
        from .config import get as _cfg
        import numpy as np
        bound = _cfg("MXNET_KVSTORE_BIGARRAY_BOUND")
        size = int(np.prod(shape)) if shape else 1
        if size <= bound or not shape or shape[0] < 2:
            return cls._layout_from_rows_per(k, shape, 0)
        rows_per = max(int(bound // max(size // shape[0], 1)), 1)
        return cls._layout_from_rows_per(k, shape, rows_per)

    def init(self, key, value):
        if self._client is None:
            return super().init(key, value)
        import numpy as np
        keys, values = _key_value(key, value)
        batch = []  # one init_many RPC for all keys + layout records
        for k, v in zip(keys, values):
            self._store[k] = v.copy()
            # the chunk decision is made ONCE here and remembered: every
            # later access (push/pull/row_sparse/compressed) must agree on
            # the server key namespace. Compression writes whole keys, so
            # a compressed store never chunks.
            if self._compression is None:
                layout = self._chunk_layout(k, tuple(v.shape))
            else:
                layout = [(k, 0, v.shape[0] if v.shape else 0)]
            self._chunked[k] = layout if len(layout) > 1 else None
            if self._rank == 0:
                # record the chosen layout server-side: workers launched
                # with a different MXNET_KVSTORE_BIGARRAY_BOUND would
                # otherwise address a divergent k vs k#chunkN namespace and
                # deadlock dist_sync push aggregation with no diagnostic.
                rows_per = (layout[0][2] - layout[0][1]
                            if self._chunked[k] is not None else 0)
                batch.append((f"__layout__{k}",
                              np.array([rows_per], dtype=np.int64)))
                if self._chunked[k] is None:
                    batch.append((k, v.asnumpy()))
                else:
                    arr = v.asnumpy()
                    batch.extend((ck, arr[b:e]) for ck, b, e in layout)
        if batch:
            self._client.init_many(batch)
        self._client.barrier()
        if self._rank != 0 and keys:
            # adopt rank 0's layout so every worker agrees on the namespace
            recs = self._client.pull_many(
                [f"__layout__{k}" for k in keys])
            for k, rec in zip(keys, recs):
                layout = self._layout_from_rows_per(
                    k, tuple(self._store[k].shape), int(rec[0]))
                self._chunked[k] = layout if len(layout) > 1 else None

    def attach(self, key, value):
        """Adopt already-initialized server state for ``key`` WITHOUT the
        init barrier — the elastic-resume path.

        ``init`` ends in a full-group barrier, which can never complete
        for a replacement worker joining after its peers initialized (or
        exited): the round-5 failure-recovery contract (kvstore.h:353
        dead-node surfacing) needs rejoining workers to come up solo.
        ``value`` supplies only the shape/dtype for the local layout
        record; the live weights stay whatever the server holds.
        """
        if self._client is None:
            return super().init(key, value)
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            self._store[k] = v.copy()
            rec = self._client.pull_many([f"__layout__{k}"])[0]
            layout = self._layout_from_rows_per(
                k, tuple(v.shape), int(rec[0]))
            self._chunked[k] = layout if len(layout) > 1 else None

    def push(self, key, value, priority=0):
        if self._client is None:
            return super().push(key, value, priority)
        from .ndarray import sparse as _sp
        keys, values = _key_grouped(key, value)
        _account_wire("push", values)
        sync = self._type in ("dist_sync", "dist_device_sync")
        for k, vlist in zip(keys, values):
            if any(isinstance(v, _sp.BaseSparseNDArray) for v in vlist):
                if getattr(self, "_compression", None) is not None:
                    raise MXNetError(
                        "gradient compression does not support row_sparse "
                        "pushes (reference kvstore_dist parity)")
                merged = vlist[0]
                for v in vlist[1:]:
                    merged = _sp.elemwise_add(merged, v)
                import numpy as np
                idx = np.asarray(merged._indices).astype(np.int64)
                vals = np.asarray(merged._data)
                layout = self._chunked.get(k)
                if layout is None:
                    self._client.push_rs(k, idx, vals,
                                         tuple(merged.shape), sync=sync)
                else:
                    # chunked key: split rows by chunk range; EVERY chunk
                    # gets a (possibly empty) push so sync aggregation
                    # counts line up across workers
                    for ck, b, e in layout:
                        m = (idx >= b) & (idx < e)
                        self._client.push_rs(
                            ck, idx[m] - b, vals[m],
                            (e - b,) + tuple(merged.shape[1:]), sync=sync)
                continue
            merged = vlist[0] if len(vlist) == 1 else nd.add_n(
                *[v.as_in_context(vlist[0].ctx) for v in vlist])
            gc = getattr(self, "_compression", None)
            if gc is not None:
                # 2-bit codes + error-feedback residual on this worker
                # (parity: KVStoreDist::PushCompressed)
                self._check_not_chunked(k, "compressed push")
                self._client.push_compressed(
                    k, gc.encode_push(k, merged.asnumpy()), sync=sync)
            else:
                layout = self._chunked.get(k)
                if layout is None:
                    self._client.push(k, merged.asnumpy(), sync=sync)  # graftlint: disable=per-param-collective -- one wire frame per key is the multi-worker protocol; big keys batch via push_many, and mesh-fusible setups bypass this loop entirely
                else:  # pipelined chunk pushes: one in-flight window
                    arr = merged.asnumpy()
                    self._client.push_many(
                        [(ck, arr[b:e]) for ck, b, e in layout], sync=sync)

    def _check_not_chunked(self, k, what):
        if self._chunked.get(k) is not None:
            raise MXNetError(
                f"{what} on key {k!r} is incompatible with big-array "
                "chunking (array exceeds MXNET_KVSTORE_BIGARRAY_BOUND "
                "elements); raise the bound for this key's workflow, or "
                "enable compression before init")

    def _fetch_rows(self, k, stored, rows):
        # only the requested rows cross the wire (kvstore_dist.h:243);
        # on a chunked key each chunk serves its own row range
        if self._client is None:
            return super()._fetch_rows(k, stored, rows)
        import numpy as np
        import jax.numpy as jnp
        rows_np = np.asarray(rows).astype(np.int64)
        layout = self._chunked.get(k)
        if layout is None:
            return jnp.asarray(self._client.pull_rows(k, rows_np))
        out = np.empty((len(rows_np),) + tuple(stored.shape[1:]),
                       np.dtype(str(stored.dtype)))
        for ck, b, e in layout:
            m = (rows_np >= b) & (rows_np < e)
            if not m.any():
                continue
            out[m] = self._client.pull_rows(ck, rows_np[m] - b)
        return jnp.asarray(out)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if self._client is None:
            return super().pull(key, out, priority, ignore_sparse)
        import numpy as np
        keys, outs = _key_grouped(key, out)
        _account_wire("pull", outs)
        for k, olist in zip(keys, outs):
            layout = self._chunked.get(k)
            if layout is None:
                arr = self._client.pull(k)  # graftlint: disable=per-param-collective -- one wire frame per key is the multi-worker protocol; chunked keys batch via pull_many
            else:  # big array: pipelined chunk pulls, reassembled
                parts = self._client.pull_many([ck for ck, _b, _e in layout])
                arr = np.concatenate(parts, axis=0)
            for o in olist:
                o[:] = arr

    def set_optimizer(self, optimizer):
        if self._client is None:
            return super().set_optimizer(optimizer)
        if self._rank == 0:
            self._client.send_command("set_optimizer",
                                      pickle.dumps(optimizer))
        self._client.barrier()

    def get_optimizer_states(self, dump_optimizer=False):
        """Dist resume: fetch the SERVER-side optimizer state (that is
        where update_on_kvstore ran the updater), so a rank-0 checkpoint
        can capture momentum/Adam state that never existed worker-side."""
        if self._client is None:
            return super().get_optimizer_states(dump_optimizer)
        resp = self._client.command("get_optimizer_states",
                                    pickle.dumps(bool(dump_optimizer)))
        return resp["value"]

    def set_optimizer_states(self, states):
        """Dist resume: install checkpointed optimizer state into the
        live server (requires set_optimizer to have run there)."""
        if self._client is None:
            return super().set_optimizer_states(states)
        self._client.command("set_optimizer_states", states)

    def _send_command_to_servers(self, head, body):
        """Generic server command (parity: KVStore::SendCommandToServers,
        include/mxnet/kvstore.h:377; carries e.g. the profiler commands —
        see profiler.set_kvstore_handle)."""
        if self._client is not None:
            self._client.send_command(head, body)

    def get_num_dead_node(self, node_id=0, timeout=60):
        """Number of workers whose heartbeats stopped (parity:
        KVStore::get_num_dead_node, include/mxnet/kvstore.h:353)."""
        if self._client is None:
            return 0
        return self._client.num_dead_node(timeout)

    def barrier(self):
        if self._client is not None:
            self._client.barrier()
        nd.waitall()


def _updater_key(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


def _key_value(key, value):
    if isinstance(key, (str, int)):
        if isinstance(value, (list, tuple)):
            # init with one value per key is the contract; a list for a
            # single key means per-device copies — take the first
            return [key], [value[0]]
        return [key], [value]
    assert isinstance(value, (list, tuple)) and len(key) == len(value)
    return list(key), list(value)


def _key_grouped(key, value):
    """Normalize (key(s), value(s)) to (keys, list-of-lists)."""
    if isinstance(key, (str, int)):
        if isinstance(value, NDArray):
            return [key], [[value]]
        return [key], [list(value)]
    out_keys, out_vals = [], []
    n_per = len(value) // len(key)
    for i, k in enumerate(key):
        v = value[i]
        if isinstance(v, NDArray):
            out_vals.append([v])
        else:
            out_vals.append(list(v))
        out_keys.append(k)
    return out_keys, out_vals


def create(name="local"):
    """Create a KVStore (parity: kvstore.py create / factory
    src/kvstore/kvstore.cc:48-64)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    if name in ("local", "local_update_cpu", "local_allreduce_cpu",
                "local_allreduce_device", "device", "nccl"):
        return KVStore("device" if name in ("device", "nccl") else "local")
    if name == "ici":
        return KVStoreICI()
    if name.startswith("dist"):
        return KVStoreDist(name)
    raise MXNetError(f"unknown KVStore type {name!r}")
