#!/usr/bin/env python
"""Disabled-observability fast-path overhead budget (CI stages).

The contract (mxnet_tpu/telemetry.py, mxnet_tpu/trace.py and
mxnet_tpu/blackbox.py, mirroring fault.py): with the registry/recorder
off, every instrumentation hook in the stack is ONE module attribute
read + branch.  This benchmark
measures that cost against a tight eager-op loop and fails if the probes
add more than the budget (default 2%) — the guard that keeps future
instrumentation honest.  The trace-enabled path is also measured and
reported (informational: enabling tracing is a deliberate choice, only
the disabled paths are gated).  So is ``mx.trace.span()`` with the
recorder off: once the start-up record is full it may cost no more than
the budget over the bare ``TraceAnnotation`` it was before the record
existed; the cost of one of the record's 256 kept spans is reported.

Method: time a tight eager add loop (N ops, synced once) as the
baseline, then the same loop with K extra disabled probes per iteration
(telemetry and trace each), scale the measured per-probe cost down to
the ~1 probe a real dispatch performs, and compare medians of R repeats
(medians + many probes per iteration keep the number stable on noisy CI
hosts).

Usage: python benchmark/telemetry_overhead.py [--budget 0.02] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _loop(a, n, probes_per_op, telemetry):
    """One timed run: n eager adds, probes_per_op gated probes each."""
    t0 = time.perf_counter()
    out = a
    if probes_per_op == 0:
        for _ in range(n):
            out = out + a
    else:
        probe = range(probes_per_op)
        for _ in range(n):
            out = out + a
            for _ in probe:
                if telemetry._active:  # the hook pattern under test
                    # mxlint: disable=REG003(measures the disabled fast path; the metric must stay undeclared so no registry slot is ever touched)
                    telemetry.inc("bench.never")
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _trace_loop(a, n, probes_per_op, trace):
    """Same shape, probing the mx.trace disabled gate instead."""
    t0 = time.perf_counter()
    out = a
    probe = range(probes_per_op)
    for _ in range(n):
        out = out + a
        for _ in probe:
            if trace._active:  # the hook pattern under test
                trace.emit("bench.never", 0, 0)
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _blackbox_loop(a, n, probes_per_op, blackbox):
    """Same shape, probing the mx.blackbox disabled gate instead (the
    pattern every flight-recorder trigger site uses)."""
    t0 = time.perf_counter()
    out = a
    probe = range(probes_per_op)
    for _ in range(n):
        out = out + a
        for _ in probe:
            if blackbox._active:  # the hook pattern under test
                blackbox.dump(trigger="manual", reason="bench.never")
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _resolve_loop(a, n, probes_per_op, resolve_blocks):
    """Same shape, probing the UNTUNED autotune.resolve_blocks fast path
    (the routing every Pallas kernel call site takes at trace time)."""
    t0 = time.perf_counter()
    out = a
    probe = range(probes_per_op)
    for _ in range(n):
        out = out + a
        for _ in probe:
            resolve_blocks("flash_attention", (256, 256, 64))
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _stream_loop(a, n, probes_per_op, note_served):
    """Same shape, probing mx.stream's per-record hot-path hook (the
    exact function its read path calls once per served record)."""
    t0 = time.perf_counter()
    out = a
    probe = range(probes_per_op)
    for _ in range(n):
        out = out + a
        for _ in probe:
            note_served(1)  # gates on telemetry._active internally
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _servefleet_loop(a, n, probes_per_op, servefleet):
    """Same shape, probing the mx.servefleet disabled gate instead (the
    pattern ServeEngine.step runs once per decode step when no fleet
    group exists in the process)."""
    t0 = time.perf_counter()
    out = a
    probe = range(probes_per_op)
    for _ in range(n):
        out = out + a
        for _ in probe:
            if servefleet._active:  # the hook pattern under test
                servefleet.note_step(None)
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _goodput_loop(a, n, probes_per_op, goodput):
    """Same shape, probing the mx.goodput disabled gate instead (the
    pattern every ledger claim site uses)."""
    t0 = time.perf_counter()
    out = a
    probe = range(probes_per_op)
    for _ in range(n):
        out = out + a
        for _ in probe:
            if goodput._active:  # the hook pattern under test
                goodput.note("compute", 0.0)
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _span_loop(a, n, probes_per_op, make):
    """Same shape, one span entered and left per probe, recorder off:
    ``make`` is ``mx.trace.span`` with the start-up record full, or the
    bare ``_Annotation`` that was all of the off path before the record."""
    t0 = time.perf_counter()
    out = a
    probe = range(probes_per_op)
    for _ in range(n):
        out = out + a
        for _ in probe:
            with make("bench.op"):
                pass
    out._data.block_until_ready()
    return time.perf_counter() - t0


def _kept_span_ns(trace):
    """ns for one of the start-up record's kept spans (recorder off): the
    record is emptied for the timing and put back after it."""
    kept, room = trace._startup[:], trace._startup_room
    trace._startup[:], trace._startup_room = [], trace.STARTUP_SPANS
    try:
        t0 = time.perf_counter()
        for _ in range(trace.STARTUP_SPANS):
            with trace.span("bench.op"):
                pass
        return (time.perf_counter() - t0) / trace.STARTUP_SPANS * 1e9
    finally:
        trace._startup[:], trace._startup_room = kept, room


def _trace_enabled_loop(a, n, trace):
    """Eager loop with one real recorded span per op (tracing ON)."""
    t0 = time.perf_counter()
    out = a
    for _ in range(n):
        with trace.span("bench.op"):
            out = out + a
    out._data.block_until_ready()
    return time.perf_counter() - t0


def run(n=2000, probes_per_op=32, repeats=7, budget=0.02):
    import mxnet_tpu as mx
    from mxnet_tpu import blackbox, goodput, servefleet, telemetry, trace
    from mxnet_tpu.autotune.kernels import resolve_blocks, _TUNED
    from mxnet_tpu.stream import _note_served

    telemetry.disable()
    trace.disable()
    blackbox.disable()
    goodput.disable()
    assert not telemetry.active() and not trace.active() \
        and not blackbox.active() and not goodput.active()
    assert not servefleet._active, \
        "servefleet gate measures the no-fleet path"
    assert not _TUNED, "resolve_blocks gate measures the UNTUNED path"
    a = mx.np.ones((8, 8))
    _loop(a, 200, 0, telemetry)          # warmup: compile + caches hot
    resolve_blocks("flash_attention", (256, 256, 64))  # static table fill
    base_s, probed_s, tprobed_s, bprobed_s = [], [], [], []
    rprobed_s, sprobed_s, gprobed_s, fprobed_s, ton_s = [], [], [], [], []
    bare_s, full_s, kept_ns = [], [], []
    trace._startup_room = 0             # span() past the record's bound
    for _ in range(repeats):
        bare_s.append(_span_loop(
            a, n, probes_per_op, lambda name: trace._Annotation("mx/" + name)))
        full_s.append(_span_loop(a, n, probes_per_op, trace.span))
        kept_ns.append(_kept_span_ns(trace))
        base_s.append(_loop(a, n, 0, telemetry))
        probed_s.append(_loop(a, n, probes_per_op, telemetry))
        tprobed_s.append(_trace_loop(a, n, probes_per_op, trace))
        bprobed_s.append(_blackbox_loop(a, n, probes_per_op, blackbox))
        rprobed_s.append(_resolve_loop(a, n, probes_per_op, resolve_blocks))
        sprobed_s.append(_stream_loop(a, n, probes_per_op, _note_served))
        gprobed_s.append(_goodput_loop(a, n, probes_per_op, goodput))
        fprobed_s.append(_servefleet_loop(a, n, probes_per_op, servefleet))
        trace.enable(buffer=max(1024, n))
        ton_s.append(_trace_enabled_loop(a, n, trace))
        trace.disable()
        trace.clear()
    base = statistics.median(base_s)
    probed = statistics.median(probed_s)
    tprobed = statistics.median(tprobed_s)
    bprobed = statistics.median(bprobed_s)
    rprobed = statistics.median(rprobed_s)
    sprobed = statistics.median(sprobed_s)
    gprobed = statistics.median(gprobed_s)
    fprobed = statistics.median(fprobed_s)
    ton = statistics.median(ton_s)
    bare = statistics.median(bare_s)
    full = statistics.median(full_s)
    # cost of the K probes, scaled to the ~1 probe a real dispatch adds
    per_probe = max(0.0, probed - base) / probes_per_op
    per_trace_probe = max(0.0, tprobed - base) / probes_per_op
    per_blackbox_probe = max(0.0, bprobed - base) / probes_per_op
    per_resolve_probe = max(0.0, rprobed - base) / probes_per_op
    per_stream_probe = max(0.0, sprobed - base) / probes_per_op
    per_goodput_probe = max(0.0, gprobed - base) / probes_per_op
    per_servefleet_probe = max(0.0, fprobed - base) / probes_per_op
    ratio = per_probe / base
    trace_ratio = per_trace_probe / base
    blackbox_ratio = per_blackbox_probe / base
    resolve_ratio = per_resolve_probe / base
    stream_ratio = per_stream_probe / base
    goodput_ratio = per_goodput_probe / base
    servefleet_ratio = per_servefleet_probe / base
    # what span() costs with the recorder off over the bare annotation
    span_ratio = max(0.0, full - bare) / probes_per_op / base
    return {"ops": n, "probes_per_op": probes_per_op, "repeats": repeats,
            "baseline_s": round(base, 6), "probed_s": round(probed, 6),
            "trace_probed_s": round(tprobed, 6),
            "blackbox_probed_s": round(bprobed, 6),
            "resolve_probed_s": round(rprobed, 6),
            "stream_probed_s": round(sprobed, 6),
            "goodput_probed_s": round(gprobed, 6),
            "servefleet_probed_s": round(fprobed, 6),
            "trace_enabled_s": round(ton, 6),
            "per_op_probe_overhead_ns": round(per_probe / n * 1e9, 2),
            "per_op_trace_probe_overhead_ns":
                round(per_trace_probe / n * 1e9, 2),
            "per_op_blackbox_probe_overhead_ns":
                round(per_blackbox_probe / n * 1e9, 2),
            "per_op_resolve_probe_overhead_ns":
                round(per_resolve_probe / n * 1e9, 2),
            "per_op_stream_probe_overhead_ns":
                round(per_stream_probe / n * 1e9, 2),
            "per_op_goodput_probe_overhead_ns":
                round(per_goodput_probe / n * 1e9, 2),
            "per_op_servefleet_probe_overhead_ns":
                round(per_servefleet_probe / n * 1e9, 2),
            "overhead_ratio": round(ratio, 6),
            "trace_overhead_ratio": round(trace_ratio, 6),
            "blackbox_overhead_ratio": round(blackbox_ratio, 6),
            "resolve_overhead_ratio": round(resolve_ratio, 6),
            "stream_overhead_ratio": round(stream_ratio, 6),
            "goodput_overhead_ratio": round(goodput_ratio, 6),
            "servefleet_overhead_ratio": round(servefleet_ratio, 6),
            "trace_enabled_ratio": round(max(0.0, ton - base) / base, 6),
            "span_off_bare_annotation_ns":
                round(max(0.0, bare - base) / probes_per_op / n * 1e9, 2),
            "span_off_record_full_ns":
                round(max(0.0, full - base) / probes_per_op / n * 1e9, 2),
            "span_off_record_kept_ns": round(statistics.median(kept_ns), 2),
            "span_off_overhead_ratio": round(span_ratio, 6),
            "budget": budget,
            "ok": ratio < budget and trace_ratio < budget
                  and blackbox_ratio < budget and resolve_ratio < budget
                  and stream_ratio < budget and goodput_ratio < budget
                  and servefleet_ratio < budget and span_ratio < budget}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=2000)
    ap.add_argument("--probes-per-op", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--budget", type=float, default=0.02,
                    help="max disabled-probe cost as a fraction of the "
                         "eager loop (CI enforces the default 2%%)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    r = run(args.ops, args.probes_per_op, args.repeats, args.budget)
    if args.json:
        print(json.dumps(r))
    else:
        print(f"baseline eager loop   {r['baseline_s'] * 1e3:9.2f} ms "
              f"({r['ops']} ops)")
        print(f"with {r['probes_per_op']}x disabled telemetry probes/op "
              f"{r['probed_s'] * 1e3:9.2f} ms")
        print(f"with {r['probes_per_op']}x disabled trace probes/op "
              f"{r['trace_probed_s'] * 1e3:9.2f} ms")
        print(f"with {r['probes_per_op']}x disabled blackbox probes/op "
              f"{r['blackbox_probed_s'] * 1e3:9.2f} ms")
        print(f"with {r['probes_per_op']}x untuned resolve_blocks/op "
              f"{r['resolve_probed_s'] * 1e3:9.2f} ms")
        print(f"with {r['probes_per_op']}x disabled stream probes/op "
              f"{r['stream_probed_s'] * 1e3:9.2f} ms")
        print(f"with {r['probes_per_op']}x disabled goodput probes/op "
              f"{r['goodput_probed_s'] * 1e3:9.2f} ms")
        print(f"with {r['probes_per_op']}x disabled servefleet probes/op "
              f"{r['servefleet_probed_s'] * 1e3:9.2f} ms")
        print(f"with tracing ENABLED (1 span/op) "
              f"{r['trace_enabled_s'] * 1e3:9.2f} ms "
              f"(+{r['trace_enabled_ratio'] * 100:.2f}%, informational)")
        print(f"telemetry overhead ratio {r['overhead_ratio'] * 100:9.4f} % "
              f"(budget {r['budget'] * 100:g}%)")
        print(f"trace overhead ratio     "
              f"{r['trace_overhead_ratio'] * 100:9.4f} % "
              f"(budget {r['budget'] * 100:g}%)")
        print(f"blackbox overhead ratio  "
              f"{r['blackbox_overhead_ratio'] * 100:9.4f} % "
              f"(budget {r['budget'] * 100:g}%)")
        print(f"resolve_blocks ratio     "
              f"{r['resolve_overhead_ratio'] * 100:9.4f} % "
              f"(budget {r['budget'] * 100:g}%)")
        print(f"stream overhead ratio    "
              f"{r['stream_overhead_ratio'] * 100:9.4f} % "
              f"(budget {r['budget'] * 100:g}%)")
        print(f"goodput overhead ratio   "
              f"{r['goodput_overhead_ratio'] * 100:9.4f} % "
              f"(budget {r['budget'] * 100:g}%)")
        print(f"servefleet overhead ratio "
              f"{r['servefleet_overhead_ratio'] * 100:9.4f} % "
              f"(budget {r['budget'] * 100:g}%)")
        print(f"span() recorder off: bare annotation "
              f"{r['span_off_bare_annotation_ns']:.0f} ns, start-up record "
              f"full {r['span_off_record_full_ns']:.0f} ns, one of its "
              f"256 kept spans {r['span_off_record_kept_ns']:.0f} ns")
        print(f"span() off-path ratio    "
              f"{r['span_off_overhead_ratio'] * 100:9.4f} % "
              f"(over the bare annotation; budget {r['budget'] * 100:g}%)")
    if not r["ok"]:
        print("FAIL: a disabled observability fast path exceeds the "
              "overhead budget", file=sys.stderr)
        return 1
    print("OK: disabled telemetry + trace + blackbox + untuned "
          "resolve_blocks + stream + goodput + servefleet fast paths and "
          "span() past the start-up record within budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
