"""Seconds of set-up inside the back-end's compile-or-load, over all
programs, from the compile path's own records
(``_compile_cache.report()``): with every program found in the
persistent cache it is the time to load them."""
import program_trace


def read(obs):
    return program_trace.setup_seconds(obs, "backend_s")
