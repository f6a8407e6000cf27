"""The decode step's share of its roofline over the traced stretch
(``serve_trace.decode_roofline``), as what bounds ``serve_tok_s``: the
bytes a step must move over 819 GB/s against the decode program's device
time."""
import serve_trace


def read(obs):
    return serve_trace.decode_roofline(obs)
