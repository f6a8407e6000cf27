"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.  Needs nothing but JAX (``jax.profiler.ProfileData``).

What a TPU trace holds (looked at by hand on the v5e, PR 23): one plane
per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per
executed HLO operation (the TensorCore runs them one at a time) and
whose line ``XLA Modules`` has one event per executed program; and the
host's plane ``/host:CPU`` with one line per thread, where the
benchmark's own ``chipbench/...`` spans (``common.span``) appear on the
same clock.  The span ``chipbench/window`` brackets the traced part of
the measured window; everything is clipped to it.

The program gives its kernels no stable name yet (no ``name=`` on a
``pallas_call``, no ``jax.named_scope``), so Mosaic kernels are found by
what XLA calls them — see ``is_mosaic``.
"""
from __future__ import annotations

import bisect
import functools
import re

SPAN_PREFIX = "chipbench/"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather")
_SUFFIX = re.compile(r"[.\d]+$")
#: an XLA Ops event is named by its whole HLO line:
#: ``%fusion.12 = f32[8,128]{1,0:T(8,128)} fusion(f32[...] %p), kind=kLoop``
_HLO = re.compile(r"^%(?P<short>[\w.\-]+) = .*?\s(?P<opcode>[a-z][a-z0-9\-]*)\(")


@functools.lru_cache(maxsize=None)
def parse_op(text):
    """HLO line -> (short name, opcode, is a Mosaic kernel)."""
    m = _HLO.match(text)
    if not m:
        return text[:80], "", "tpu_custom_call" in text
    return (m.group("short"), m.group("opcode"),
            "tpu_custom_call" in text)


def is_collective(op):
    return bool(_COLLECTIVE.search(op["opcode"] or op["name"]))


#: operations that only wrap others (their bodies' operations are on the
#: same line beside them).  ``reduce`` drops them first of all: a stall
#: inside a ``while`` is idle time, its body's operations are the work
CONTAINERS = ("while", "conditional", "call")


def is_container(op):
    return op["opcode"] in CONTAINERS


def step_modules(modules):
    """The executions of the cell's main program among a device's
    modules: those at least half as long as the longest (a train loop
    also launches micro-programs a few microseconds long each step)."""
    if not modules:
        return []
    longest = max(m["end"] - m["start"] for m in modules)
    return [m for m in modules if m["end"] - m["start"] >= 0.5 * longest]


def is_mosaic(op):
    """A Pallas/Mosaic kernel on the XLA Ops line: a custom call whose
    target is ``tpu_custom_call``."""
    return op["mosaic"]


def family_of(name):
    """``fusion.123`` -> ``fusion``: one row per kind of operation."""
    return _SUFFIX.sub("", name) or name


def union_length(intervals):
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The (start, end) stretches of [lo, hi] no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def merged(intervals):
    """The union of (start, end) intervals as disjoint, sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def subtract(intervals, cover):
    """Length of ``intervals`` not covered by ``cover`` (both lists of
    (start, end)).  One pass over the merged cover for each interval's
    own stretch of it: a traced update of a large model has some 10^4
    operations a chip, so nothing here may look at every pair."""
    cover = merged(cover)
    ends = [c[1] for c in cover]
    total = 0
    for s, e in intervals:
        total += e - s
        i = bisect.bisect_right(ends, s)
        while i < len(cover) and cover[i][0] < e:
            total -= min(e, cover[i][1]) - max(s, cover[i][0])
            i += 1
    return total


def covering_span(spans):
    """``f(s, e)`` -> the name of the span that overlaps [s, e) longest
    (of equals the one that starts last, which is the inner one); looks
    only at the spans that can reach the gap (host spans nest little,
    so that is one or two)."""
    spans = sorted(spans, key=lambda sp: sp["start"])
    starts = [sp["start"] for sp in spans]
    reach, far = [], None          # the farthest end up to each span
    for sp in spans:
        far = sp["end"] if far is None else max(far, sp["end"])
        reach.append(far)

    def find(s, e):
        best, best_len = "(no chipbench span)", 0
        i = bisect.bisect_left(starts, e) - 1
        while i >= 0 and reach[i] > s:
            ov = min(e, spans[i]["end"]) - max(s, spans[i]["start"])
            if ov > best_len:
                best, best_len = spans[i]["name"], ov
            i -= 1
        return best

    return find


def _events(line):
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        out.append((ev.name, start, start + int(ev.duration_ns), ev))
    return out


def _stat(ev, key):
    try:
        for k, v in ev.stats:
            if k == key:
                return v
    except Exception:       # an event without readable stats
        return None
    return None


def read(path):
    """{"devices": [{"ops": [...], "modules": [...]}, ...], "spans":
    [...]} with times in ns on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans, cpu_ops = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for name, s, e, ev in _events(line):
                        short, opcode, mosaic = parse_op(name)
                        dev["ops"].append({
                            "name": short, "start": s, "end": e,
                            "opcode": opcode, "mosaic": mosaic})
                elif line.name == "XLA Modules":
                    for name, s, e, _ in _events(line):
                        dev["modules"].append(
                            {"name": name, "start": s, "end": e})
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e, ev in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        spans.append({"name": name[len(SPAN_PREFIX):],
                                      "start": s, "end": e})
                    elif _stat(ev, "hlo_op") is not None:
                        # the CPU back-end's own executions: only the
                        # --tiny rehearsal reads these, as a stand-in
                        cpu_ops.append({"name": name, "start": s, "end": e,
                                        "opcode": "", "mosaic": False})
    devs = [devices[k] for k in sorted(
        devices, key=lambda n: int(n.rsplit(":", 1)[1]))]
    if not devs and cpu_ops:
        devs = [{"name": "/host:CPU (rehearsal)", "ops": cpu_ops,
                 "modules": []}]
    return {"devices": devs, "spans": spans}


def _clip(items, lo, hi):
    out = []
    for it in items:
        s, e = max(it["start"], lo), min(it["end"], hi)
        if e > s:
            out.append(dict(it, start=s, end=e))
    return out


def reduce(path, n_devices):
    """The reduction every traced run prints from."""
    return reduce_raw(read(path), n_devices, path)


def reduce_raw(raw, n_devices, path="trace"):
    """``reduce`` on what ``read`` returns (tests hand in their own)."""
    devs = raw["devices"][:n_devices]
    if not devs:
        raise RuntimeError(f"{path}: no device plane in the trace")
    win = [s for s in raw["spans"] if s["name"] == "window"]
    if win:
        lo, hi = win[0]["start"], win[0]["end"]
    else:
        lo = min(o["start"] for d in devs for o in d["ops"])
        hi = max(o["end"] for d in devs for o in d["ops"])
    for d in devs:
        d["ops"] = [o for o in _clip(d["ops"], lo, hi)
                    if not is_container(o)]
        d["modules"] = [m for m in d["modules"]
                        if m["start"] >= lo and m["end"] <= hi]
    spans = _clip([s for s in raw["spans"] if s["name"] != "window"], lo, hi)
    busy = [union_length([(o["start"], o["end"]) for o in d["ops"]])
            for d in devs]
    if not any(busy):
        raise RuntimeError(f"{path}: no operation ran on a device inside "
                           "the traced window")

    d0 = devs[0]
    by_family = {}
    for o in d0["ops"]:
        key = family_of(o["name"])
        if o["opcode"] and o["opcode"] not in key:
            key = f"{key} [{o['opcode']}]"
        if is_mosaic(o):
            key = "mosaic-kernel " + key
        by_family[key] = by_family.get(key, 0) + (o["end"] - o["start"])
    device_ops = sorted(([k, v / 1e9] for k, v in by_family.items()),
                        key=lambda kv: -kv[1])

    idle = {}
    host_span = covering_span(spans)
    for s, e in gaps([(o["start"], o["end"]) for o in d0["ops"]], lo, hi):
        best = host_span(s, e)
        idle[best] = idle.get(best, 0) + (e - s)
    idle_gaps = sorted(([k, v / 1e9] for k, v in idle.items()),
                       key=lambda kv: -kv[1])

    exposed = []
    for d in devs:
        coll = [(o["start"], o["end"]) for o in d["ops"] if is_collective(o)]
        comp = [(o["start"], o["end"]) for o in d["ops"]
                if not is_collective(o)]
        exposed.append(subtract(coll, comp))

    for d in devs:
        d["step_modules"] = step_modules(d["modules"])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "device_ops": device_ops, "idle_gaps": idle_gaps,
        "devices": devs, "spans": spans,
        "collective_exposed_s": [x / 1e9 for x in exposed],
    }
