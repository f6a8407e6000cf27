"""Device time an update of the operations that make a forward value
again for the backward pass behind a recomputation boundary
(``hybridize(remat=...)`` on a block, ``ShardedTrainStep(remat=...)``):
those whose ``op_name`` holds ``rematted_computation``, the name JAX's
``jax.checkpoint`` gives the region it replays (the lowered step shows
``transpose(jvp(mx.fwd))/.../checkpoint/rematted_computation/...``; the
forward's first run is under ``checkpoint`` alone).  They lie under the
step's backward (``bwd_ms.train`` holds them).  Device 0, whole updates
of the traced window.  None for a program that recomputes nothing."""
import program_trace

NAME = "rematted_computation"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: NAME in o["op_name"] and not o["collective"])
