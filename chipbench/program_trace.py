"""What the program itself says about a run, for the per-layer metrics
that read its own names: a second, memoised read of the traced run's
``.xplane.pb`` that keeps what ``xplane.py`` drops — each device
operation's ``op_name`` (the JAX name stack, where the program's
``jax.named_scope``s live) and the host's ``mx/...`` spans — and the
compile path's own record of set-up (``mxnet_tpu._compile_cache.report``).

What the trace holds (looked at by hand on the v5e, PR 25): a device
plane's ``event_metadata`` has one entry per HLO operation, named by its
whole HLO line (the name ``xplane.py`` parses), with the statistics
``program_id`` and ``tf_op`` — the operation's ``op_name`` metadata,
``jit(step)/jit(main)/jvp(mx.fwd)/.../mx.attn/dot_general:``.  The
Python reader (``jax.profiler.ProfileData``) shows an event's own
statistics only, so the metadata is read from the file's bytes, lines
skipped unread: a dozen thousand entries whatever the trace's length.
The program's host spans (``mx.trace.span`` -> ``TraceAnnotation``) are
events of the ``/host:CPU`` plane named ``mx/<name>``, on the device
events' clock.

A program that names nothing (the parent of the PR that named it) gives
empty maps, and every reader built on this returns ``None``.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

import xplane

SPAN_PREFIX = "mx/"
#: the scopes of a train step; ``mx.attn`` lies inside ``mx.fwd``
FWD, OPTIMIZER, ATTN = "mx.fwd", "mx.optimizer", "mx.attn"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ---- the trace's bytes -------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a view of its bytes, not parsed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def _map_values(plane, field):
    """The values of a ``map<int64, Message>`` field of an XPlane."""
    for f, _, entry in _fields(plane):
        if f == field:
            for ef, _, val in _fields(entry):
                if ef == 2:
                    yield val


def _text(view):
    return bytes(view).decode("utf-8", "replace")


@functools.lru_cache(maxsize=2)
def op_names(path):
    """{program id: {operation's short name: op_name}} of the first
    device plane (XSpace.planes=1; XPlane.name=2, .event_metadata=4,
    .stat_metadata=5; XEventMetadata.name=2, .stats=5; XStat.metadata_id
    =1, .uint64_value=3, .int64_value=4, .str_value=5, .ref_value=7)."""
    with open(path, "rb") as file:
        space = memoryview(file.read())
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name = next((_text(v) for pf, _, v in _fields(plane) if pf == 2), "")
        if name != "/device:TPU:0":
            continue
        stat_names = {}
        for meta in _map_values(plane, 5):
            d = {mf: v for mf, _, v in _fields(meta)}
            stat_names[d.get(1, 0)] = _text(d.get(2, b""))
        out = {}
        for meta in _map_values(plane, 4):
            hlo, program, op_name = None, None, None
            for mf, _, v in _fields(meta):
                if mf == 2:
                    hlo = _text(v)
                elif mf == 5:
                    st = {sf: sv for sf, _, sv in _fields(v)}
                    key = stat_names.get(st.get(1))
                    if key == "program_id":
                        program = st.get(3, st.get(4))
                    elif key == "tf_op":
                        op_name = _text(st[5]) if 5 in st \
                            else stat_names.get(st.get(7), "")
            if hlo and program is not None and op_name:
                out.setdefault(int(program), {})[xplane.parse_op(hlo)[0]] \
                    = op_name
        return out
    return {}


@functools.lru_cache(maxsize=2)
def host_spans(path):
    """The program's own host spans inside the benchmark's window:
    ``mx/<name>`` -> {"name", "start", "end"} in ns on the trace's
    clock."""
    from jax.profiler import ProfileData
    spans, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    start = int(ev.start_ns)
                    spans.append({"name": name[len(SPAN_PREFIX):],
                                  "start": start,
                                  "end": start + int(ev.duration_ns)})
                elif name == xplane.SPAN_PREFIX + "window":
                    start = int(ev.start_ns)
                    window = (start, start + int(ev.duration_ns))
    if window:
        spans = [s for s in spans
                 if s["start"] >= window[0] and s["end"] <= window[1]]
    return spans


def trace_file(obs):
    """The run's ``.xplane.pb`` (as ``drivers_trace.stop_trace`` finds
    it), or None."""
    if obs.get("xplane"):
        return obs["xplane"]
    found = sorted(glob.glob(os.path.join(
        obs["ctx"]["trace_dir"], "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


# ---- one update's operations, by the program's names ---------------------

def scope_of(op_name):
    """``"bwd"``, ``"fwd"``, ``"optimizer"`` or None for an op_name: the
    backward is the path component that holds ``mx.fwd`` under a
    ``transpose(`` (JAX writes ``transpose(jvp(mx.fwd))``)."""
    for part in op_name.split("/"):
        if FWD in part:
            return "bwd" if "transpose(" in part else "fwd"
        if OPTIMIZER in part:
            return "optimizer"
    return None


def update_ops(obs):
    """Device 0's operations inside whole updates of the traced window
    (``xplane.reduce`` has clipped them and dropped the containers), each
    with ``op_name``, ``scope`` and ``collective``; and the number of
    those updates.  (None, 0) where there is no such update.  Computed
    once a run and kept in ``obs``."""
    if "_update_ops" in obs:
        return obs["_update_ops"]
    d0 = obs["device_trace"]["devices"][0]
    steps = sorted(d0["step_modules"], key=lambda m: m["start"])
    path = trace_file(obs) if steps else None
    if not steps or path is None:
        obs["_update_ops"] = (None, 0)
        return obs["_update_ops"]
    by_program = op_names(path)
    names = {}
    for name in {m["name"] for m in steps}:
        pid = _PROGRAM_ID.search(name)
        if pid:
            names.update(by_program.get(int(pid.group(1)), {}))
    starts = [m["start"] for m in steps]
    ops = []
    for o in d0["ops"]:
        i = bisect.bisect_right(starts, o["start"]) - 1
        if i < 0 or o["end"] > steps[i]["end"]:
            continue
        op_name = names.get(o["name"], "")
        ops.append(dict(o, op_name=op_name, scope=scope_of(op_name),
                        collective=xplane.is_collective(o)))
    obs["_update_ops"] = (ops, len(steps))
    return obs["_update_ops"]


def ms_per_update(obs, keep):
    """Summed device time of the updates' operations ``keep(op)`` holds,
    in ms an update; None where nothing matches (a program without the
    names, a cell without the kernel)."""
    ops, n = update_ops(obs)
    if not ops:
        return None
    total = sum(o["end"] - o["start"] for o in ops if keep(o))
    return total / 1e6 / n if total else None


def scope_ms(obs, scope):
    """One of the step's scopes, collectives left out: they are
    ``coll_exposed_ms.train``'s."""
    return ms_per_update(
        obs, lambda o: o["scope"] == scope and not o["collective"])


def kernel_ms(obs, kernel):
    """One named Mosaic kernel (``name=`` of its ``pallas_call``)."""
    return ms_per_update(
        obs, lambda o: o["mosaic"] and xplane.family_of(o["name"]) == kernel)


# ---- set-up, from the compile path's own records --------------------------

def setup_seconds(obs, field):
    """Seconds of ``field`` (``trace_s``, ``lower_s``, ``backend_s``) over
    the programs that were ready before the window opened; None where
    the program keeps no such record."""
    from mxnet_tpu import _compile_cache
    report = getattr(_compile_cache, "report", None)
    if report is None:
        return None
    opened = obs["window"][0]
    return sum(r[field] for r in obs.get("compile_report") or report()
               if r["at"] <= opened)
