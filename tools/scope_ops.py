"""After a traced run of a benchmark cell (``chipbench/run.py --workload
<cell> --trace 1``, which leaves ``.chipbench_trace/<cell>/``): device time
an update, by kind of operation, of what lies under each of the given
scopes, under no scope, and by pass of the step.

    python tools/scope_ops.py <cell> [scope ...] [--top 14] [--names scope]

A scope is a substring of an operation's ``op_name`` (``mx.loop``,
``mx.exit``, ``rematted_computation``, ``mx.loop.t1``); the default is
those three.  ``--names s`` lists the largest single instructions under
``s`` with the end of their ``op_name``: how a row such as
``bitcast_dynamic-update-slice_fusion`` was found to be the head's ``dW``
product (PERF.md section 5, PR 44).  Reads through ``chipbench/xplane.py``
and ``chipbench/program_trace.py``, as the benchmark's own readers do.
"""
import argparse
import collections
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("scopes", nargs="*",
                    default=["mx.loop", "mx.exit", "rematted_computation"])
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--names", default=None)
    args = ap.parse_args()
    import program_trace
    import xplane

    found = sorted(glob.glob(os.path.join(
        ROOT, ".chipbench_trace", args.cell, "plugins", "profile", "*",
        "*.xplane.pb")))
    if not found:
        raise SystemExit(f"no trace of {args.cell} under .chipbench_trace/")
    obs = {"xplane": found[-1], "device_trace": xplane.reduce(found[-1], 1)}
    ops, n = program_trace.update_ops(obs)
    ms = lambda o: (o["end"] - o["start"]) / 1e6 / n

    def table(title, keep):
        total, count = collections.Counter(), collections.Counter()
        for o in ops:
            if keep(o):
                kind = ("mosaic " if xplane.is_mosaic(o) else "") \
                    + xplane.family_of(o["name"])
                total[kind] += ms(o)
                count[kind] += 1
        print(f"## {title}: {sum(total.values()):.2f} ms an update")
        for kind, v in total.most_common(args.top):
            print(f"   {v:9.3f} ms  x{count[kind] / n:<7.1f} {kind}")

    print(f"# {args.cell}: {n} traced updates, "
          f"{sum(map(ms, ops)):.2f} ms of operations an update")
    for side in ("fwd", "bwd", "optimizer"):
        print(f"   {side}: "
              f"{sum(ms(o) for o in ops if o['scope'] == side):.2f} ms")
    for scope in args.scopes:
        table(scope, lambda o: scope in o["op_name"])
        for side in ("fwd", "bwd"):
            part = sum(ms(o) for o in ops
                       if scope in o["op_name"] and o["scope"] == side)
            print(f"   of which {side}: {part:.2f} ms")
    table("no scope", lambda o: o["scope"] is None and not o["collective"])
    if args.names:
        big = collections.Counter()
        for o in ops:
            if args.names in o["op_name"]:
                big[(o["name"], o["op_name"][-120:])] += ms(o)
        print(f"## the largest instructions under {args.names}")
        for (name, where), v in big.most_common(args.top):
            print(f"   {v:9.3f} ms {name} :: {where}")


if __name__ == "__main__":
    main()
