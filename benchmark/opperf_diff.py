#!/usr/bin/env python
"""Round-over-round diff of opperf JSON artifacts.

Reference analog: benchmark/opperf/ emits per-op timings but ships no
regression tooling; CI consumers diff runs by hand. This closes that loop:

    python benchmark/opperf_diff.py OPPERF_prev.json OPPERF.json \
        [--threshold 0.25] [--metric e2e_us]

Prints ops that regressed/improved by more than `threshold` (fractional),
plus ops that appeared, disappeared, or changed error status. Exits 1 if
any regression exceeds the threshold so CI can gate on it. Sub-threshold
noise is suppressed: run-to-run microbench jitter is easily ±10%, so the
default gate is 25%.
"""
from __future__ import annotations

import argparse
import json
import sys


def _load(path):
    with open(path) as f:
        rows = json.load(f)
    if isinstance(rows, dict):  # {'platform': ..., 'rows': [...]} wrapper
        rows = rows["rows"]
    return {r["op"]: r for r in rows}


def diff(prev, cur, metric="e2e_us", threshold=0.25):
    """Return (regressions, improvements, status_changes) row lists."""
    regs, imps, status = [], [], []
    for op in sorted(set(prev) | set(cur)):
        p, c = prev.get(op), cur.get(op)
        if p is None:
            status.append((op, "NEW", c.get(metric, c.get("error"))))
            continue
        if c is None:
            status.append((op, "REMOVED", p.get(metric, p.get("error"))))
            continue
        p_err, c_err = "error" in p, "error" in c
        if p_err != c_err:
            status.append((op, "NOW-ERROR" if c_err else "FIXED",
                           c.get("error", c.get(metric))))
            continue
        if p_err:  # both error: nothing to compare
            continue
        pv, cv = p.get(metric), c.get(metric)
        if pv is None or cv is None:  # artifact predates this metric
            status.append((op, "NO-METRIC", metric))
            continue
        if pv <= 0:
            continue
        rel = (cv - pv) / pv
        if rel > threshold:
            regs.append((op, pv, cv, rel))
        elif rel < -threshold:
            imps.append((op, pv, cv, rel))
    return regs, imps, status


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("prev")
    ap.add_argument("cur")
    ap.add_argument("--metric", default="e2e_us",
                    choices=["e2e_us", "dispatch_us"])
    ap.add_argument("--threshold", type=float, default=0.25)
    args = ap.parse_args()

    prev_map, cur_map = _load(args.prev), _load(args.cur)
    regs, imps, status = diff(prev_map, cur_map,
                              args.metric, args.threshold)
    for op, kind, detail in status:
        print(f"{kind:10s} {op:24s} {detail}")
    for op, pv, cv, rel in sorted(imps, key=lambda r: r[3]):
        print(f"{'IMPROVED':10s} {op:24s} {pv:10.2f} -> {cv:10.2f} "
              f"({rel:+.0%})")
    for op, pv, cv, rel in sorted(regs, key=lambda r: -r[3]):
        print(f"{'REGRESSED':10s} {op:24s} {pv:10.2f} -> {cv:10.2f} "
              f"({rel:+.0%})")
    n_err = sum(1 for op, k, _ in status
                if k == "NOW-ERROR"
                or (k == "NEW" and "error" in cur_map[op]))
    print(f"# {len(regs)} regressions, {len(imps)} improvements, "
          f"{len(status)} status changes ({args.metric}, "
          f"gate {args.threshold:.0%})")
    sys.exit(1 if (regs or n_err) else 0)


if __name__ == "__main__":
    main()
