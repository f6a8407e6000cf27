"""Device time an update of the operations under ``mx.loop`` (the looped
stack of ``model_zoo/ouro.py``: every layer application of every pass,
forward, the forward made again behind the layers' recomputation
boundaries, and backward — JAX names the last two
``transpose(jvp(...))`` round the same scope) and not under ``mx.exit``
(the final norm after each pass lies inside the loop and is the exit
head's: ``exit_head_ms.train``), device 0, whole updates of the traced
window.  None for a program that has no such scope."""
import program_trace

SCOPE, NOT = "mx.loop", "mx.exit"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and NOT not in o["op_name"]
        and not o["collective"])
