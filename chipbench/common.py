"""What the drivers share: spans, the compile watch, device readings and
small statistics.  Nothing here knows a configuration, a cell or a
metric by name."""
from __future__ import annotations

import jax

SPAN_PREFIX = "chipbench/"


def span(name):
    """A host span in the profiler's own trace (a no-op costing about a
    microsecond when no trace is being taken)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class CompileWatch:
    """Counts what JAX compiles or loads from its persistent cache, and
    the seconds spent there, from ``jax.monitoring`` events."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0        # backend compile-or-load calls
        self.requests = 0        # of those, how many asked the cache
        self.hits = 0
        from jax import monitoring
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, *a, **k):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, seconds, *a, **k):
        if event in self._DURATIONS:
            self.seconds += seconds
            if event == self._DURATIONS[2]:
                self.programs += 1

    def snapshot(self):
        return {"seconds": self.seconds, "programs": self.programs,
                "requests": self.requests, "hits": self.hits}


def device_info(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest device; 0 where the back-end
    keeps no statistics (the CPU rehearsal)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def median(values):
    vals = sorted(values)
    if not vals:
        return None
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])


class Check:
    """The numbers ``correct`` is decided from, each beside its limit."""

    def __init__(self):
        self.rows = []

    def at_most(self, name, value, where=None, *, limit):
        """``where`` says which leaf, request or step gave the value."""
        ok = value is not None and value == value and value <= limit
        row = {"name": name, "value": value, "limit": limit,
               "holds": bool(ok)}
        if where is not None:
            row["where"] = where
        self.rows.append(row)
        return ok

    def exactly(self, name, value, wanted):
        ok = value == wanted
        self.rows.append({"name": name, "value": value, "limit": wanted,
                          "holds": bool(ok)})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["holds"] for r in self.rows)


def deep_merge(base, over):
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = v
    return out
