"""Starting and stopping the profiler around the traced part of a
window; shared by the drivers."""
from __future__ import annotations

import glob
import os
import shutil

from common import span


def start_trace(ctx):
    import jax
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    os.makedirs(ctx["trace_dir"], exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(ctx["trace_dir"], profiler_options=opts)
    window = span("window")
    window.__enter__()
    return window


def stop_trace(ctx, window):
    import jax
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        ctx["trace_dir"], "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {ctx['trace_dir']}")
    return found[-1]
