"""Device time an update of the operations under ``mx.ssm.conv`` (the
mixers' causal depthwise convolution with its bias and SiLU, forward,
made again in the backward pass, and the backward itself), all mixers
together, device 0, whole updates of the traced window.  None for a
program that has no such scope."""
import program_trace

SCOPE = "mx.ssm.conv"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
