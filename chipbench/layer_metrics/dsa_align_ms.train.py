"""Device time an update of the operations under ``mx.dsa.align`` (the
indexer's loss: a second pass over q and k in query blocks for the
attention's probabilities on the selected keys, the KL against the
softmax of the indexer's scores, and the closed-form gradient the
backward pass scales), all layers together, device 0, whole updates of
the traced window.  None for a program that has no such scope."""
import program_trace

SCOPE = "mx.dsa.align"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
