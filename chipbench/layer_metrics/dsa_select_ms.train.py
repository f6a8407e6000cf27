"""Device time an update of the operations under ``mx.dsa.select`` (the
top-k threshold of every query's scores by bisection, the selection mask
and its counts; forward only, a selection has no gradient), all layers
together, device 0, whole updates of the traced window.  None for a
program that has no such scope."""
import program_trace

SCOPE = "mx.dsa.select"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
