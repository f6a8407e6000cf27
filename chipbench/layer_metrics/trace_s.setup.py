"""Seconds of set-up inside jaxpr tracing, over all programs, from the
compile path's own records (``_compile_cache.report()``); with
``lower_s.setup`` and ``cache_load_s.setup`` it sums to ``compile_s``."""
import program_trace


def read(obs):
    return program_trace.setup_seconds(obs, "trace_s")
