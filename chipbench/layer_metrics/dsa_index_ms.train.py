"""Device time an update of the operations under ``mx.dsa.index`` (the
sparse indexer's three projections, its norm, rotary and the blocked
per-head score products with their weighted sum, forward and backward —
JAX names the backward ``transpose(jvp(...))`` round the same scope), all
layers together, device 0, whole updates of the traced window.  None for
a program that has no such scope."""
import program_trace

SCOPE = "mx.dsa.index"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
