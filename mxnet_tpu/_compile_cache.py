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

Threshold note: JAX by default only persists programs that took >1s to
compile and are >minimal size; we zero both thresholds — an armed cache
should cache everything, tiny test programs included, or it looks broken
on small models.
"""
from __future__ import annotations

import os

from . import config as _config
from . import telemetry as _telemetry

__all__ = ["CHECKOUT_CACHE", "cache_dir", "configure"]

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


def _install_listeners():
    global _listener_installed
    if _listener_installed:
        return
    from jax import monitoring

    def on_event(event, *args, **kwargs):
        if not _telemetry._active:
            return
        name = _EVENT_COUNTERS.get(event)
        if name is not None:
            _telemetry.inc(name)

    def on_duration(event, duration, *args, **kwargs):
        if not _telemetry._active:
            return
        if event == "/jax/compilation_cache/cache_retrieval_time_sec":
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
