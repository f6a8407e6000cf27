"""Spreads of a cell's end-to-end metrics over two sets of runs, as the
bound rule reads them: quartile distance over the median
(``statistics.quantiles(values, n=4)``), the wider of the two sets.

    python chipbench/dev/spread.py runs.jsonl   # lines: {"set", "seed", "line"}
"""
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path):
    rows = [json.loads(l) for l in open(path) if l.strip()]
    sets = sorted({r["set"] for r in rows})
    names = sorted({n for r in rows for n in r["line"]["metrics"]})
    print("correct:", [r["line"]["correct"] for r in rows])
    for n in names:
        out = []
        for s in sets:
            vals = [r["line"]["metrics"][n]["value"] for r in rows
                    if r["set"] == s and n in r["line"]["metrics"]]
            if n == "setup_s":
                vals = vals[1:] if s == sets[0] else vals
            out.append((statistics.median(vals), spread(vals), vals))
        widest = max(o[1] for o in out)
        print(f"{n}: widest spread {100 * widest:.3f} % -> bound "
              f"{100 * max(0.01, 5 * widest):.2f} %")
        for s, (med, sp, vals) in zip(sets, out):
            print(f"   set {s}: median {med:.6g} spread {100 * sp:.3f} % "
                  f"values {[round(v, 4) for v in vals]}")
        if len(out) == 2:
            print(f"   second median vs first: "
                  f"{100 * (out[1][0] / out[0][0] - 1):+.3f} %")


if __name__ == "__main__":
    main(sys.argv[1])
