"""Bytes the window's traffic keeps in use on the chip, in GB: the
weights and the cache rows that live slots hold, mean over the window's
steps (``serve_trace.live_bytes``) — beside ``hbm_peak_gb.serve``, most
of which is the cache's reservation whether a row is used or not."""
import serve_trace


def read(obs):
    live = serve_trace.live_bytes(obs)
    return live / 1e9 if live else None
