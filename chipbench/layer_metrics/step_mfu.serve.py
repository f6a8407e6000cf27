"""The traced stretch's needed operations, decode and prefill, over its
seconds and the chip's peak (``serve_trace.stretch_mfu``): the whole
step's share beside ``decode_roofline.serve``.  It reads low: decoding
is bound by bytes."""
import serve_trace


def read(obs):
    return serve_trace.stretch_mfu(obs)
