"""Device time an update of the operations under ``mx.exit`` (the final
norm after each pass, the exit gate and the exit distribution, the four
exits' heads and cross-entropies and the loss over them, forward and
backward), device 0, whole updates of the traced window.  None for a
program that has no such scope."""
import program_trace

SCOPE = "mx.exit"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
