"""Mean device time of one prefill program's execution
(``jit__prefill_fn``, any bucket) inside the traced stretch, device 0: a
prompt padded to its bucket through every block, its K and V written to
the slot's rows, the head over every position."""
import serve_trace


def read(obs):
    ms = serve_trace.module_ms(obs, serve_trace.PREFILL)
    return sum(ms) / len(ms) if ms else None
