"""Set-up as the program itself saw it, for the six ``*_s.setup`` pieces
that divide ``setup_s`` with no rest.

``setup_s`` is read on ``time.perf_counter()``: ``run.py``'s ``T_PROCESS``
to the window's open.  ``mxnet_tpu.trace.startup()`` keeps the program's
first host spans on that clock whether or not a profiler is on (the
benchmark's profiler starts at the end of the window, so set-up's spans
are in no trace), and ``_compile_cache.report()`` stamps every program it
made ready with the same clock.  The interval is cut at four stamps of the
thread that imported the package::

    T_PROCESS | import | ... | train.init | ... first train.call | ... | open
       pre_import  import  net_build  step_init     first_call   checked_updates

Six pieces, each the distance between two neighbouring stamps, so they sum
to ``setup_s`` by construction.  A piece whose stamps are missing or out
of order (the package imported before ``T_PROCESS``: pytest; a program
that builds no ``ShardedTrainStep``) reads ``None``, never 0.  A program
without ``mx.trace.startup`` (the parent of the PR that added it) gives
``None`` everywhere and prints nothing.

Once a run, whichever reader comes first, one comment line ``# setup
{...}``: the pieces, the programs made ready inside each (how many, and
the sum of their ``trace_s + lower_s + backend_s`` — events, where the
piece is wall time), and every kept span of the interval with its
duration, its self time (duration less what its child spans cover), its
counts and its programs.  ``kept_after_open`` 0 with ``kept`` at the
record's bound (``mx.trace.STARTUP_SPANS``) means set-up outran the record.
"""
from __future__ import annotations

import json

PIECES = ("pre_import_s.setup", "import_s.setup", "net_build_s.setup",
          "step_init_s.setup", "first_call_s.setup",
          "checked_updates_s.setup")


def _first(spans, name, after):
    return next((s for s in spans
                 if s["name"] == name and s["start_s"] >= after), None)


def cut(t_process, opened, startup):
    """``(pieces, stamps, spans)``: the six pieces of ``[t_process,
    opened]`` by name; the seven stamps they lie between (None where the
    record has no such span); and the spans of the importing thread that
    touch the interval, clipped to it, oldest first, ``parent``
    re-indexed into that list."""
    pieces = dict.fromkeys(PIECES)
    imp = next((s for s in startup if s["name"] == "import"), None)
    if imp is None:
        return pieces, [t_process] + [None] * 5 + [opened], []
    index, mine, spans = {}, [], []
    for i, s in enumerate(startup):
        if s["thread"] != imp["thread"]:
            continue
        mine.append(s)
        if s["end_s"] >= t_process and s["start_s"] <= opened:
            index[i] = len(spans)
            spans.append(dict(s, start_s=max(s["start_s"], t_process),
                              end_s=min(s["end_s"], opened),
                              parent=index.get(s["parent"])))
    init = _first(mine, "train.init", imp["end_s"])
    call = init and _first(mine, "train.call", init["end_s"])
    stamps = [t_process, imp["start_s"], imp["end_s"],
              init and init["start_s"], init and init["end_s"],
              call and call["end_s"], opened]
    for name, a, b in zip(PIECES, stamps, stamps[1:]):
        if a is not None and b is not None and t_process <= a <= b <= opened:
            pieces[name] = b - a
    return pieces, stamps, spans


def _programs(report, a, b):
    inside = [r for r in report if a < r["at"] <= b]
    return [len(inside), round(sum(r["trace_s"] + r["lower_s"]
                                   + r["backend_s"] for r in inside), 6)]


def line(t_process, opened, startup, report):
    """What ``# setup`` prints; times in seconds, ``at`` from
    ``t_process``."""
    pieces, stamps, spans = cut(t_process, opened, startup)
    # a span kept after the open says the record held the set-up whole
    out = {"pieces": pieces, "programs": {}, "kept": len(startup),
           "kept_after_open": sum(s["start_s"] > opened for s in startup),
           "spans": []}
    for name, a, b in zip(PIECES, stamps, stamps[1:]):
        if pieces[name] is not None:
            out["programs"][name] = _programs(report, a, b)
    covered = [0.0] * len(spans)
    for s in spans:
        if s["parent"] is not None:
            covered[s["parent"]] += s["end_s"] - s["start_s"]
    for s, child_s in zip(spans, covered):
        dur = s["end_s"] - s["start_s"]
        row = {"name": s["name"], "at": round(s["start_s"] - t_process, 6),
               "s": round(dur, 6), "self_s": round(dur - child_s, 6)}
        if s["attrs"]:
            row["counts"] = s["attrs"]
        n, seconds = _programs(report, s["start_s"], s["end_s"])
        if n:
            row["programs"] = [n, seconds]
        out["spans"].append(row)
    return out


def timeline(obs):
    """The run's pieces by name, or None where the program keeps no
    start-up record.  Computed once a run and kept in ``obs``; the
    ``# setup`` line is printed then."""
    if "_setup_timeline" not in obs:
        from mxnet_tpu import _compile_cache, trace
        startup = getattr(trace, "startup", None)
        if startup is None:
            obs["_setup_timeline"] = None
        else:
            out = line(obs["ctx"]["t_process"], obs["window"][0],
                       obs.get("startup_record") or startup(),
                       obs.get("compile_report") or _compile_cache.report())
            print("# setup " + json.dumps(out), flush=True)
            obs["_setup_timeline"] = out["pieces"]
    return obs["_setup_timeline"]


def piece(obs, name):
    pieces = timeline(obs)
    return pieces and pieces[name]
