"""Median device time of one execution of the engine's decode program
(``jit__decode_fn`` on the trace's ``XLA Modules`` line, device 0) inside
the traced stretch: every slot of ``max_slots`` advanced one token, the
idle ones masked."""
import serve_trace
from common import median


def read(obs):
    return median(serve_trace.module_ms(obs, serve_trace.DECODE))
