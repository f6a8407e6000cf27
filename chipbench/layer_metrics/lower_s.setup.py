"""Seconds of set-up inside jaxpr -> MLIR lowering, over all programs, from
the compile path's own records (``_compile_cache.report()``)."""
import program_trace


def read(obs):
    return program_trace.setup_seconds(obs, "lower_s")
