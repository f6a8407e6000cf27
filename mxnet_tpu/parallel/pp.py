"""Pipeline parallelism — GPipe-style microbatch pipelining over a mesh axis.

Reference parity: none (the reference has no PP — SURVEY §2.3 marks it a
TPU-native extension). Design (scaling-book recipe): each device along the
'pp' axis holds ONE stage's parameters (stacked pytree leading axis sharded
over 'pp'); microbatch activations rotate stage-to-stage with
lax.ppermute inside shard_map. The whole schedule is differentiable —
ppermute's transpose is the reverse permute, so jax.grad yields the 1F1B
communication pattern automatically instead of hand-written send/recv like
GPU frameworks need.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map


def gpipe(stage_fn, stage_params, xs, mesh, axis="pp"):
    """Run a pipeline of S identical-shape stages over M microbatches.

    stage_fn(params_slice, x) -> y        one stage's forward; x/y same shape
    stage_params: pytree whose leaves have leading dim S (stacked stages),
        sharded (or shardable) over `axis`.
    xs: (M, mb, ...) microbatched input (resident on every device; only
        stage 0 reads it).
    Returns (M, mb, ...) outputs of the last stage.

    Schedule: M + S - 1 ticks; at tick t, stage s computes microbatch
    t - s (when in range). Activations move s -> s+1 between ticks via
    ppermute; a device's compute at tick t overlaps the permute XLA issues
    for tick t+1 (latency-hiding scheduler).
    """
    n_stages = mesh.shape[axis]
    n_micro = xs.shape[0]
    leaves = jax.tree_util.tree_leaves(stage_params)
    for leaf in leaves:
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} != pp axis size "
                f"{n_stages}: each device holds exactly one stage (a "
                f"divisible multiple would silently drop stages)")
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(axis), stage_params,
                                         is_leaf=lambda x: x is None),
                  P()),
        out_specs=P(),
        check_vma=False)
    def _pipe(params, xs_rep):
        # params leaves arrive as (1, ...) blocks — drop the stage dim
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs_rep.shape[1:]

        def tick(t, carry):
            buf, ys = carry
            # stage 0 ingests microbatch t; others use the permuted carry
            x_in = jnp.where(
                stage == 0,
                xs_rep[jnp.clip(t, 0, n_micro - 1)],
                buf)
            y = stage_fn(params, x_in)
            # microbatch id this stage just computed: t - stage
            mb_id = t - stage
            is_last = stage == n_stages - 1
            valid = (mb_id >= 0) & (mb_id < n_micro) & is_last
            ys = jax.lax.cond(
                valid,
                lambda ys: jax.lax.dynamic_update_index_in_dim(
                    ys, y, jnp.clip(mb_id, 0, n_micro - 1), 0),
                lambda ys: ys, ys)
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, ys

        buf0 = jnp.zeros(mb_shape, xs_rep.dtype)
        if hasattr(jax.lax, "pcast"):
            buf0 = jax.lax.pcast(buf0, (axis,), to="varying")
        ys0 = jnp.zeros((n_micro,) + mb_shape, xs_rep.dtype)
        if hasattr(jax.lax, "pcast"):
            ys0 = jax.lax.pcast(ys0, (axis,), to="varying")
        _, ys = jax.lax.fori_loop(0, n_micro + n_stages - 1, tick,
                                  (buf0, ys0))
        # every device returns ys; only the last stage's is populated —
        # psum broadcasts it (all other stages contribute zeros)
        return jax.lax.psum(ys, axis)

    return _pipe(stage_params, xs)


def stack_stage_params(param_list):
    """Stack per-stage pytrees (list of S identical-structure trees) into
    one tree with leading stage dim, ready for sharding over 'pp'."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_list)


def shard_stages(stacked, mesh, axis="pp"):
    sh = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), stacked)
