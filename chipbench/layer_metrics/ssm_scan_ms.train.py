"""Device time an update of the operations under ``mx.ssm.scan`` (the
chunked state-space scan of ``ops.ssm.ssd_scan``: decays, the
triangular product inside a chunk, the chunks' states, the state carried
from chunk to chunk and its output — forward, made again in the backward
pass, and the backward itself), all mixers together, device 0, whole
updates of the traced window.  None for a program that has no such
scope."""
import program_trace

SCOPE = "mx.ssm.scan"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
