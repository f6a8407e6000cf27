"""Device time an update of the operations under ``mx.mla.assemble``
(inside ``nn.LatentAttention``: what exists only because the keys are
latent — the rotary on the 64-wide ``rope`` parts of q and of the one
shared key, the split of ``W_kvb``'s output into ``k_nope`` and ``v``,
the repeat of the rotary key over the heads and the concatenations that
make q and k whole in HBM — forward and backward), all layers together,
device 0, whole updates of the traced window.  A kernel that took the
rotary key as an operand of its own would leave most of it nothing to
do.  None for a program that has no such scope."""
import program_trace

SCOPE = "mx.mla.assemble"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
