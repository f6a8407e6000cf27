"""Single-process KVStore ('local'/'device'/'nccl').

Reference parity: python/mxnet/kvstore/kvstore.py over src/kvstore/
kvstore_local.h (GroupKVPairs push/pull grouping, merge buffers,
CommCPU/CommDevice reduce at src/kvstore/comm.h:104,474) and kvstore_nccl.h.

TPU-native design: values live as jax Arrays (possibly sharded over the local
mesh). 'Reduce' is a jnp tree-sum — when the per-device values are shards of
a mesh-sharded array, XLA emits the ICI all-reduce; there is no host staging,
which is what CommDevice's P2P ring approximates on GPU. Per-key updaters
(optimizer-on-kvstore) match the reference's semantics.
"""
from __future__ import annotations

import pickle

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError
from ..numpy.multiarray import ndarray, _wrap
from .base import KVStoreBase


class KVStore(KVStoreBase):
    """In-process key-value store with device reduction."""

    def __init__(self, name="device"):
        self._type = name
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._updater_states = {}

    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    @staticmethod
    def is_capable(capability):
        return capability in ("optimizer",)

    # -- core ops ----------------------------------------------------------
    def init(self, key, value):
        keys, values = self._normalize(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                continue
            self._store[k] = v.copy() if isinstance(v, ndarray) else v

    def _normalize(self, key, value):
        if isinstance(key, (list, tuple)):
            return list(key), list(value)
        return [key], [value]

    @staticmethod
    def _one_device(v):
        ds = v._data.devices() if hasattr(v._data, "devices") else set()
        return next(iter(ds)) if len(ds) == 1 else None

    @staticmethod
    def _reduce_parts(vals):
        """Sum a list of per-device arrays (CommDevice::Reduce analog,
        src/kvstore/comm.h:474).

        When each value lives on a distinct device, the sum is ONE XLA
        all-reduce over a mesh of those devices (psum rides ICI on real
        chips), and the result list keeps one reduced copy resident on each
        contributing device — the CommDevice reduce+broadcast without host
        staging. Otherwise falls back to a tree-sum on the common device.
        Returns a list aligned with ``vals``.
        """
        if len(vals) == 1:
            return [vals[0]]
        devs = []
        for v in vals:
            d = KVStore._one_device(v)
            if d is None or d in devs or v.shape != vals[0].shape:
                devs = None
                break
            devs.append(d)
        if devs is None:
            acc = vals[0]._data
            for v in vals[1:]:
                acc = acc + v._data
            merged = _wrap(acc)
            return [merged] * len(vals)

        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import functools

        from jax import shard_map

        n, shape = len(vals), tuple(vals[0].shape)
        mesh = Mesh(onp.array(devs), ("kv",))
        glob = jax.make_array_from_single_device_arrays(
            (n,) + shape, NamedSharding(mesh, P("kv")),
            [v._data[None] for v in vals])

        @functools.partial(shard_map, mesh=mesh, in_specs=P("kv"),
                           out_specs=P("kv"))
        def _psum(x):
            return jax.lax.psum(x, "kv")

        out = _psum(glob)
        shards = sorted(out.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return [_wrap(s.data.reshape(shape)) for s in shards]

    @staticmethod
    def _reduce(vals):
        """Merged value of a push (single reduced copy). Row-sparse values
        reduce sparsely (reference: comm.h ReduceRowSparse)."""
        from ..ndarray.sparse import BaseSparseNDArray, add as _sp_add
        if isinstance(vals, (ndarray, BaseSparseNDArray)):
            return vals
        if any(isinstance(v, BaseSparseNDArray) for v in vals):
            merged = vals[0]
            for v in vals[1:]:
                merged = _sp_add(merged, v)
            return merged
        return KVStore._reduce_parts(vals)[0]

    def push(self, key, value, priority=0):
        keys, values = self._normalize(key, value)
        for k, vs in zip(keys, values):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized")
            merged = self._reduce(vs)
            if self._updater is not None:
                self._updater(self._key_int(k), merged, self._store[k])
            else:
                from ..ndarray.sparse import BaseSparseNDArray
                if isinstance(merged, BaseSparseNDArray):
                    merged = merged.tostype("default")
                self._store[k]._rebind(merged._data.astype(self._store[k].dtype))

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, outs = self._normalize(key, out)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized")
            src = self._store[k]
            targets = o if isinstance(o, (list, tuple)) else [o]
            for t in targets:
                t._rebind(src._data.astype(t.dtype))

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull (reference: kvstore.h PushPull; the fast path
        Trainer uses when update_on_kvstore=False)."""
        keys, values = self._normalize(key, value)
        merged_list = []
        for k, vs in zip(keys, values):
            if isinstance(vs, ndarray):
                parts = [vs]
            else:
                parts = self._reduce_parts(vs)
            merged = parts[0]
            if self._updater is not None:
                if k not in self._store:
                    raise MXNetError(f"key {k} not initialized")
                self._updater(self._key_int(k), merged, self._store[k])
                merged, parts = self._store[k], None
            merged_list.append((merged, parts))
        if out is None:
            return
        _, outs = self._normalize(key, out)
        for (merged, parts), o in zip(merged_list, outs):
            targets = o if isinstance(o, (list, tuple)) else [o]
            if parts is not None and len(targets) == len(parts):
                # per-device reduced copies: each target keeps its placement
                for t, part in zip(targets, parts):
                    t._rebind(part._data.astype(t.dtype))
            else:
                for t in targets:
                    t._rebind(merged._data.astype(t.dtype))

    def broadcast(self, key, value, out, priority=0):
        """init + pull (reference: kvstore/base.py broadcast)."""
        self.init(key, value)
        self.pull(key, out=out, priority=priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the rows in ``row_ids`` (reference: kvstore.h
        PullRowSparse / python kvstore.py row_sparse_pull). Returns (and
        writes into row_sparse ``out`` targets) a RowSparseNDArray holding
        just those rows — the distributed-embedding fast path."""
        if row_ids is None:
            return self.pull(key, out=out, priority=priority)
        from ..ndarray.sparse import RowSparseNDArray
        keys, _ = self._normalize(key, None)
        rids = (row_ids if isinstance(row_ids, (list, tuple))
                else [row_ids] * len(keys))
        results = []
        for k, rid in zip(keys, rids):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized")
            src = self._store[k]
            from ..ndarray.sparse import _IDX
            ids = jnp.unique(rid._data if isinstance(rid, ndarray)
                             else jnp.asarray(rid)).astype(_IDX)
            vals = src._data[ids]
            results.append(RowSparseNDArray(_wrap(vals), _wrap(ids),
                                            src.shape))
        if out is not None:
            _, outs = self._normalize(key, out)
            for rsp, o in zip(results, outs):
                targets = o if isinstance(o, (list, tuple)) else [o]
                for t in targets:
                    if isinstance(t, RowSparseNDArray):
                        t.data = rsp.data
                        t.indices = rsp.indices
                        t.shape = rsp.shape
                    else:  # dense target: retained rows, zeros elsewhere
                        t._rebind(rsp.tostype("default")._data.astype(t.dtype))
        return results[0] if not isinstance(key, (list, tuple)) else results

    # -- updater / optimizer ----------------------------------------------
    @staticmethod
    def _key_int(k):
        try:
            return int(k)
        except (TypeError, ValueError):
            return k

    def _set_updater(self, updater):
        self._updater = updater

    set_updater = _set_updater

    def set_optimizer(self, optimizer):
        from ..optimizer import get_updater
        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no updater/optimizer set")
        from .. import serialization
        serialization.atomic_write_bytes(
            fname, self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no updater/optimizer set")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    def set_gradient_compression(self, compression_params):
        """Reference: kvstore.h SetGradientCompression. As in the reference,
        compression only applies to the cross-process push path — a dist
        kvstore (see dist.py); single-process stores reject it."""
        raise MXNetError(
            "gradient compression requires a dist kvstore "
            "(reference: src/kvstore/kvstore_dist.h only)")
