"""JAX persistent compilation cache: where it lives and what it reports.

Reference parity: the reference ships compiled-op caches keyed on op
signatures in-process; on a compiler-backed stack the expensive artifact
is the XLA executable, and JAX can persist those to disk so *repeated
runs* — the CI re-run, the resumed preemptible job, the hyperparameter
sweep over one model — skip compilation entirely.

Placement rule (:func:`cache_dir`): a cache placed from outside wins.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory in code.  Otherwise the directory is the
``compilation_cache_dir`` knob (env alias ``MXNET_COMPILE_CACHE``), or
the path an entry script passes — ``chip_smoke.py`` and ``bench.py`` pass
one fixed path inside the checkout, because the path is part of the
cache key and a directory that moves never hits.  Importing the package
arms nothing unless the knob is set.  Cache activity is mirrored into
``mx.telemetry``'s ``compile.*`` metrics, next to the in-process
recompile detector (telemetry.note_compile).

Set-up split (:func:`report`): whether or not a cache is armed, the
module keeps one record per program JAX compiles or loads — its name and
the seconds it spent being traced to a jaxpr, lowered to MLIR, and
compiled or loaded by the back-end, with the cache's own retrieval time
and whether it hit.  A few dictionary updates per compiled program,
nothing per step; it is what tells a slow start apart into tracing,
lowering and loading.

Threshold note: JAX by default only persists programs that took >1s to
compile and are >minimal size; we zero both thresholds — an armed cache
should cache everything, tiny test programs included, or it looks broken
on small models.
"""
from __future__ import annotations

import os
import time

from . import config as _config
from . import telemetry as _telemetry

__all__ = ["CHECKOUT_CACHE", "cache_dir", "configure", "report"]

#: what the entry scripts of a checkout (chip_smoke.py, bench.py) pass to
#: :func:`configure` when nobody placed a cache from outside: one fixed
#: path beside the package — the path is part of the cache key, so it is
#: never a temp, pid or time-derived name
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_telemetry.declare_metric(
    "compile.persistent_cache_requests_total", "counter",
    "XLA compilations that consulted the persistent cache")
_telemetry.declare_metric(
    "compile.persistent_cache_hits_total", "counter",
    "XLA compilations served from the persistent cache (miss count = "
    "requests - hits)")
_telemetry.declare_metric(
    "compile.persistent_cache_retrieval_seconds", "histogram",
    "time to load one cached executable from disk",
    buckets=_telemetry.TIME_BUCKETS)

_listener_installed = False

_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits":
        "compile.persistent_cache_hits_total",
    "/jax/compilation_cache/compile_requests_use_cache":
        "compile.persistent_cache_requests_total",
}


#: jax.monitoring duration events -> the field of a program's record
_DURATION_FIELDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}

_programs = []      # closed records, in the order JAX finished them
_open = None        # the program being traced / lowered / compiled now


def _open_record():
    global _open
    if _open is None:
        _open = {"fun_name": None, "trace_s": 0.0, "lower_s": 0.0,
                 "backend_s": 0.0, "cache_retrieval_s": 0.0, "hit": None,
                 "at": 0.0}
    return _open


def report():
    """One record per program JAX compiled or loaded in this process, in
    order: ``fun_name`` (as JAX names it, ``jit(step)``), seconds in
    ``trace_s`` (jaxpr tracing; a nested jit's trace counts for the
    program it is traced into, as JAX reports each), ``lower_s`` (jaxpr
    to MLIR), ``backend_s`` (XLA compile, or the load from the persistent
    cache), ``cache_retrieval_s`` (the cache's own part of ``backend_s``
    on a hit), ``hit`` (True / False; None when no cache was asked) and
    ``at``, the ``time.perf_counter()`` at which the program was ready.
    Tracing or lowering that has not reached the back-end yet (a bare
    ``.lower()``) is the last record, with ``backend_s`` 0."""
    out = [dict(r) for r in _programs]
    if _open is not None:
        out.append(dict(_open))
    return out


def _install_listeners():
    global _listener_installed
    if _listener_installed:
        return
    from jax import monitoring

    def on_event(event, *args, **kwargs):
        name = _EVENT_COUNTERS.get(event)
        if name is None:
            return
        rec = _open_record()
        if event == "/jax/compilation_cache/cache_hits":
            rec["hit"] = True
        elif rec["hit"] is None:
            rec["hit"] = False
        if _telemetry._active:
            _telemetry.inc(name)

    def on_duration(event, duration, *args, **kwargs):
        global _open
        field = _DURATION_FIELDS.get(event)
        if field is None:
            return
        rec = _open_record()
        rec[field] += duration
        rec["at"] = time.perf_counter()
        # the latest event names the record: the back-end's, once there
        rec["fun_name"] = kwargs.get("fun_name", rec["fun_name"])
        if field == "backend_s":
            _programs.append(rec)
            _open = None
        elif field == "cache_retrieval_s" and _telemetry._active:
            _telemetry.observe("compile.persistent_cache_retrieval_seconds",
                               duration)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _listener_installed = True


def cache_dir(path=None):
    """The directory the persistent cache lives in, or None when there
    is none: ``JAX_COMPILATION_CACHE_DIR`` if set, else ``path``, else
    the ``compilation_cache_dir`` knob."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or path
            or _config.get("compilation_cache_dir"))
    return os.path.abspath(os.path.expanduser(path)) if path else None


def configure(path=None):
    """Arm JAX's persistent compilation cache at :func:`cache_dir` and
    return that directory (None, and nothing armed, when there is none).
    Idempotent; safe to call after arrays exist (only future
    compilations consult the cache)."""
    directory = cache_dir(path)
    if directory is None:
        return None
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _install_listeners()
    return directory


# the set-up split is kept whether or not a cache is armed
_install_listeners()
