"""The knee sweep: one serve cell's engine under its own traffic at a list
of rates, to find the highest rate it sustains.  Run on the chip, once,
when a serve cell is defined or after a change that moves its capacity;
the cell's two fixed rates (0.8 x and 1.5 x the knee) then go into the
traffic files with this table beside them (PERF.md section 4).

    python chipbench/dev/sweep.py --workload gpt2m-serve-steady \\
        --rates 7,7.5,8,8.5,9,10 --seeds 1,2,3 --seconds 30 \\
        --ttft-ms 1000 --tpot-ms 200

One process, one engine (weights from the first seed; the traffic's
seeds vary): set-up is paid once.  Each (rate, seed) is the driver's own
lead-in and window (``drivers/serve_requests.drive``) followed by a full
drain, so the next rate starts on an empty engine.  A row: requests due
in the window, the share of them whose TTFT (from the due time) and TPOT
both met the limits (a request that failed or never finished misses),
tokens/s drained inside the window, the tails, the queue at the close
and the most slots live at once.  The traffic is a Poisson stream drawn
from the seed, so one seed's window is one draw: sweep three seeds or
more.  The knee is the highest rate at which every seed's share holds
(``--share``, 0.9); the steady cell then runs at 0.8 x the knee, or
lower where a seed there still fills every slot (``live_max`` =
``max_slots``): its tails are judged, and a slot-full episode is a
second regime, not a spread.

``--tiny`` (with ``JAX_PLATFORMS=cpu``) rehearses the path on the cell's
tiny stand-in; its numbers say nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="requests a second, comma-separated")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--ttft-ms", type=float, default=1000.0)
    ap.add_argument("--tpot-ms", type=float, default=200.0)
    ap.add_argument("--share", type=float, default=0.9)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import common
    import run as harness
    from mxnet_tpu import _compile_cache

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    entry, cell, cfg, mix = harness.resolve(bench, args.workload, args.tiny)
    devices = jax.devices()
    if not args.tiny and devices[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU (or --tiny for a rehearsal)")
    _compile_cache.configure(os.path.join(ROOT, ".jax_cache"))
    driver = harness.load_module("drivers", mix["driver"])
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = {"cell": cell, "cfg": cfg, "seed": seeds[0],
           "family": harness.load_module("families", cfg["family"])}
    net, eng, _ = driver.build(ctx)
    driver.first_requests(eng, cfg, seeds[0],
                          cell["check"]["first_request_tokens"])
    lead_in = float(mix["lead_in_s"])
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            at = common.deep_merge(mix, {"arrivals": {"rate_per_s": rate}})
            records = driver.records(at, seed, lead_in + args.seconds,
                                     cfg["vocab_size"])
            w = driver.drive(eng, records, lead_in, args.seconds)
            records = records[:w["submitted"]]
            drain_s = driver.drain(eng, records, "due", 300.0)
            mine, failed, ttft, tpot = driver.window_numbers(
                records, w, "due")
            window_s = w["t_close"] - w["t_open"]
            met = sum(
                1 for r in mine if r not in failed
                and 1e3 * (r["req"].t_first - w["t_zero"] - r["due"])
                <= args.ttft_ms
                and (len(r["req"].generated) < 2
                     or 1e3 * (r["req"].t_done - r["req"].t_first)
                     / (len(r["req"].generated) - 1) <= args.tpot_ms))
            busy = [s for s in w["steps"] if s["live"]]
            row = {
                "rate_per_s": rate, "seed": seed, "due": len(mine),
                "failed": len(failed),
                "met_share": met / len(mine) if mine else None,
                "tok_s": (sum(w["out_close"]) - sum(w["out_open"]))
                / window_s,
                "ttft_ms_p50": driver.percentile(ttft, 50),
                "ttft_ms_p95": driver.percentile(ttft, 95),
                "tpot_ms_p50": driver.percentile(tpot, 50),
                "tpot_ms_p95": driver.percentile(tpot, 95),
                "queued_at_close": w["steps"][-1]["queued"]
                if w["steps"] else None,
                "live_mean": sum(s["live"] for s in busy) / len(busy)
                if busy else 0.0,
                "live_max": max((s["live"] for s in busy), default=0),
                "step_ms": 1e3 * window_s / len(busy) if busy else None,
                "drain_s": drain_s,
                "post_warmup_compiles": eng.post_warmup_compiles,
            }
            rows.append(row)
            print("SWEEP " + json.dumps(row), flush=True)
    # a rate holds where every seed's share does
    by_rate = {}
    for r in rows:
        by_rate.setdefault(r["rate_per_s"], []).append(r["met_share"])
    ok = [rate for rate, shares in by_rate.items()
          if all(x is not None and x >= args.share for x in shares)]
    out = {"workload": args.workload, "device": common.device_info(devices),
           "seconds": args.seconds, "limits_ms": {"ttft": args.ttft_ms,
                                                  "tpot": args.tpot_ms},
           "share": args.share, "rows": rows,
           "highest_rate_meeting_share": max(ok) if ok else None}
    if args.tiny:
        out["rehearsal"] = True
    path = args.out or os.path.join(ROOT, "chiprun_out",
                                    f"sweep_{args.workload}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))


if __name__ == "__main__":
    main()
