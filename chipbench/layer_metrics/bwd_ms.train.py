"""Device time an update of the operations under
``transpose(jvp(mx.fwd))`` (the backward JAX derives from the ``mx.fwd``
scope of ``ShardedTrainStep``), device 0, whole updates of the traced
window, collectives left out."""
import program_trace


def read(obs):
    return program_trace.scope_ms(obs, "bwd")
