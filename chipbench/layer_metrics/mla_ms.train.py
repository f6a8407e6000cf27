"""Device time an update of the operations under ``mx.mla`` (the body of
``nn.LatentAttention``: the two down-projections, the latent norms, the
two up-projections, the rotary on the ``rope`` parts and the assembly of
q and k, the attention core — ``mx.attn`` with its three flash kernels
lies inside it — and the output projection, forward and backward: JAX
names the backward ``transpose(jvp(...))`` round the same scope), all
layers together, device 0, whole updates of the traced window.  None for
a program that has no such scope."""
import program_trace

SCOPE = "mx.mla"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
