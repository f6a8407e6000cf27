"""Device time an update of the operations under the step's ``mx.fwd`` scope
and not under its transpose (the forward of ``ShardedTrainStep``: the
net and the loss), device 0, whole updates of the traced window,
collectives left out."""
import program_trace


def read(obs):
    return program_trace.scope_ms(obs, "fwd")
