"""XLA collectives layer.

Reference parity: src/kvstore/comm.h (CommCPU/CommDevice reduce+broadcast),
comm_tree.h (topology-aware tree allreduce), kvstore_nccl.h, and ps-lite's
cross-host path — all collapsed into XLA AllReduce/AllGather/ReduceScatter/
CollectivePermute over mesh axes: ICI within a slice, DCN across slices.
Topology solving (gpu_topology.h) is the ICI fabric's job; nothing to port.

These free functions are the standalone/kvstore entry points.  The ZeRO
update in ``train.ShardedTrainStep`` uses the same shard_map idioms but
keeps its reduce-scatter/all-gather INSIDE the jitted step (an in_spec
``P(dp)`` on logically-reduced grads is the reduce-scatter under GSPMD;
``jax.lax.all_gather(..., tiled=True)`` with ``check_vma=False``
re-assembles params, exactly as :func:`allgather` below) so XLA can
overlap them with compute; what they move is in a profiler trace's
``all-reduce`` / ``all-gather`` rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map


def allreduce(x, mesh, axis="dp", op="sum"):
    """AllReduce x (replicated per-device values as a leading-axis stack or a
    sharded array) over a mesh axis via psum inside shard_map."""
    reducer = {"sum": jax.lax.psum, "max": jax.lax.pmax,
               "min": jax.lax.pmin, "mean": jax.lax.pmean}[op]

    @functools.partial(shard_map, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis))
    def _ar(v):
        return reducer(v, axis)
    return _ar(x)


def allgather(x, mesh, axis="dp", tiled=True):
    # check_vma=False: all_gather output IS replicated over `axis`, but the
    # static varying-mesh-axes check can't infer that
    @functools.partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(),
                       check_vma=False)
    def _ag(v):
        return jax.lax.all_gather(v, axis, tiled=tiled)
    return _ag(x)


def reduce_scatter(x, mesh, axis="dp"):
    @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(axis))
    def _rs(v):
        return jax.lax.psum_scatter(v, axis, tiled=True)
    return _rs(x)


def quantized_mean(c, axis, n, mode):
    """Inside a shard_map over ``axis`` (``n`` ranks): quantize this rank's
    f32 ``c`` against a SHARED scale (pmax of the absmax over ranks, so
    dequantization after the reduce is exact w.r.t. what was sent), psum,
    and return ``(mean, sent)`` — ``sent`` is what this rank's ``c``
    became, so ``c - sent`` is its error-feedback residual."""
    if mode == "int8":
        s = jax.lax.pmax(jnp.max(jnp.abs(c)), axis) / 127.0
        s = jnp.where(s > 0.0, s, jnp.float32(1.0))
        q = jnp.clip(jnp.round(c / s), -127.0, 127.0)
        # the payload is int8-VALUED but reduced as f32 operands: the f32
        # psum of integer values is exact below 2^24, so dequant-after-
        # reduce equals the mean of per-rank dequants bitwise
        sent = q * s
        return jax.lax.psum(q, axis) * s / n, sent
    # bf16: value-snap through bf16, reduce in f32
    sent = c.astype(jnp.bfloat16).astype(jnp.float32)
    return jax.lax.psum(sent, axis) / n, sent


def compressed_allreduce(x, mesh, axis="dp", mode="int8", residual=None):
    """Error-feedback compressed mean-allreduce of per-rank values.

    ``x`` is a per-rank stack (leading dim = mesh axis size, as in
    :func:`allreduce`): each rank's contribution plus its carried
    ``residual`` goes through :func:`quantized_mean` (int8- or
    bf16-valued payload, reduced as f32 operands).  Returns ``(mean, new_residual)`` where
    ``new_residual`` (same per-rank stack layout) carries the
    quantization error into the next call — EF-SGD: the error
    telescopes across steps instead of biasing the trajectory.

    The standalone/kvstore entry point for the same arithmetic
    ``ShardedTrainStep(grad_compress=...)`` fuses into its jitted step
    (train.py ``_compressed_fwd_bwd``), where per-bucket psums overlap
    with backward compute.
    """
    if mode not in ("int8", "bf16"):
        raise ValueError(f"mode must be 'int8' or 'bf16', got {mode!r}")
    n = int(mesh.shape[axis])
    if residual is None:
        residual = jnp.zeros(x.shape, jnp.float32)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=(P(), P(axis)), check_vma=False)
    def _car(v, res):
        c = v[0].astype(jnp.float32) + res[0]
        red, sent = quantized_mean(c, axis, n, mode)
        return red, (c - sent)[None]

    return _car(x, residual)


def ppermute(x, mesh, axis, perm):
    @functools.partial(shard_map, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis))
    def _pp(v):
        return jax.lax.ppermute(v, axis, perm)
    return _pp(x)


def allreduce_across_processes(x):
    """Cross-host sum of per-process values (the DCN path of KVStoreDist;
    jax.distributed replaces the ps-lite scheduler rendezvous).

    Each process contributes its local x; result is the sum over processes,
    replicated. Implementation: every local device holds x / local_device_count
    as one shard of a global (n_devices, *shape) array sharded over a 1-d
    global mesh; a shard_map psum over that axis rides DCN between hosts and
    ICI within a host.
    """
    import numpy as onp
    devs = jax.devices()
    n = len(devs)
    if n == 1 and jax.process_count() == 1:
        return x
    mesh = Mesh(onp.array(devs), ("dcn",))
    local = jax.local_devices()
    contrib = (x / len(local))[None]
    shards = [jax.device_put(contrib, d) for d in local]
    global_arr = jax.make_array_from_single_device_arrays(
        (n,) + tuple(x.shape), NamedSharding(mesh, P("dcn")), shards)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("dcn"), out_specs=P())
    def _ar(v):
        return jax.lax.psum(v, "dcn")

    out = _ar(global_arr)
    # the psum result is replicated across ALL processes' devices; callers
    # feed it back into single-process eager ops, so hand back this
    # process's own copy (fully addressable) rather than the global array
    return out.addressable_data(0)[0]
