"""Device time an update of the operations under ``mx.ssm`` (the body of
``nn.Mamba2Mixer``: in-projection, convolution, scan, gated norm and
out-projection, forward and backward — JAX names the backward
``transpose(jvp(...))`` round the same scope, and the scan and the
convolution made again for their gradients carry it too), all mixers
together, device 0, whole updates of the traced window.  None for a
program that has no such scope."""
import program_trace

SCOPE = "mx.ssm"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
