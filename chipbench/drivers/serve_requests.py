"""Serving traffic: an open loop of requests round ``ServeEngine.step()``.

The mix's file gives the arrivals (``rate_per_s``, Poisson), the two
length distributions (clipped lognormals), the lead-in before the window
opens and what the drain after its close waits for.  The cell's file
gives the engine's arguments, the parameters' types and the check.

**Traffic** (``schedule``) is what independent users send: the gaps
between arrivals are drawn from the exponential of the mix's rate (a
Poisson process), prompt lengths and output budgets from its clipped
lognormals, the token ids uniformly, all i.i.d. from ``--seed`` — the
same seed gives the same requests, another seed other requests from the
same distributions, bursts and lulls included.  A request's *due* time
is fixed before the run; one thread submits every request whose due
time has passed before each ``eng.step()`` and records how late it was.

**Clock.** Set-up ends where the traffic starts (``setup_s``: process
start to there).  The same traffic runs through a lead-in of
``lead_in_s`` so that the window opens on a filled engine; the window is
``--seconds``; after its close nothing is submitted and the drain waits
(steady: until every request due before the close has finished; flood:
not at all — what is queued at the close is neither attempted work nor
a failure, its tokens drained inside the window count).  TTFT is counted
from the due time, not from ``submit``.

**The check** runs after the window, the engine freed: the plain
reference once over each sampled request's prompt with the tokens the
engine served (``reference/<family>.serve.py``).
"""
from __future__ import annotations

import gc
import json
import math
import sys
import time

import numpy as onp

from common import Check, memory_peak_bytes, span
from drivers_trace import start_trace, stop_trace
from serve_trace import percentile


# ---- traffic -------------------------------------------------------------

def _lognormal(spec, rng, n):
    """``n`` draws of the lognormal with this median and sigma, rounded
    to whole tokens and clipped to [min, max]."""
    draws = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return onp.clip(onp.rint(draws), spec["min"], spec["max"]).astype(int)


def schedule(mix, seed, horizon_s, vocab):
    """Every request due in [0, horizon_s): ``(due_s, prompt ids, output
    budget)``, oldest first, all drawn i.i.d. from the seed: exponential
    gaps at ``arrivals.rate_per_s``, lognormal prompt lengths and
    budgets (a budget cut where prompt + budget would pass
    ``max_total_tokens``), uniform ids."""
    rate = float(mix["arrivals"]["rate_per_s"])
    rng = onp.random.default_rng([int(seed) % (2 ** 32), int(seed) >> 32])
    n = int(rate * horizon_s + 8 * math.sqrt(rate * horizon_s) + 16)
    due = onp.cumsum(rng.exponential(1.0 / rate, n))
    prompts = _lognormal(mix["prompt_tokens"], rng, n)
    budgets = onp.minimum(_lognormal(mix["output_tokens"], rng, n),
                          mix["max_total_tokens"] - prompts)
    return [(float(t), rng.integers(0, vocab, int(p), dtype=onp.int32),
             int(g))
            for t, p, g in zip(due, prompts, budgets) if t < horizon_s]


def records(mix, seed, horizon_s, vocab):
    """``schedule``'s requests as the rows ``drive`` fills in."""
    return [{"due": d, "prompt": p, "budget": g, "req": None, "late": None,
             "failed": False, "slot": None}
            for d, p, g in schedule(mix, seed, horizon_s, vocab)]


# ---- the program side ----------------------------------------------------

def build(ctx):
    """Weights on the device from the seed in the cell's type, the zoo's
    net, the engine.  Returns (net, engine, the stamps set-up is cut
    at)."""
    from mxnet_tpu.serve import ServeEngine

    cell, cfg, family = ctx["cell"], ctx["cfg"], ctx["family"]
    e = cell["engine"]
    with span("setup.weights"):
        net = family.build_net(
            cfg, family.make_weights(cfg, ctx["seed"], cell["weights"]))
    t_init = time.perf_counter()
    with span("setup.engine"):
        eng = ServeEngine(
            net, max_slots=e["max_slots"], max_seq=e["max_seq"],
            buckets=e["buckets"], cache_dtype=e["cache_dtype"],
            temperature=0.0, eos_id=None, quantize=e.get("quantize"),
            prefix_cache=False, seed=ctx["seed"] % (2 ** 31))
    t_warm = time.perf_counter()
    with span("setup.warmup"):
        eng.warmup()
    return net, eng, (t_init, t_warm, time.perf_counter())


def first_requests(eng, cfg, seed, budget):
    """One request a prefill bucket, served before the traffic starts:
    every executable has run once, and the check has requests of every
    bucket whatever the window brings."""
    rng = onp.random.default_rng([7, int(seed) % (2 ** 32)])
    reqs, low = [], 1
    for b in eng.buckets:
        n = min(b, eng.max_seq - budget)
        if n >= low:
            ids = rng.integers(0, cfg["vocab_size"], int(n), dtype=onp.int32)
            reqs.append(eng.submit(ids, max_new_tokens=budget))
        low = b + 1
    with span("setup.first_requests"):
        eng.run()
    return reqs


def drive(eng, records, lead_in, seconds, trace_at=None, start=None):
    """The lead-in and the window: one thread, submit what is due, step.
    ``records`` (``{"due", "prompt", "budget"}``, oldest first) gain
    ``req``, ``late``, ``failed`` and the ``slot`` they were admitted
    to.  Returns the clock's stamps, a row an ``eng.step()`` of the
    window, and every request's served-token count at the window's open
    and close."""
    from mxnet_tpu.serve.engine import EngineBusy

    tracing, t_traced, steps, nxt = None, None, [], 0
    t_open = None
    t_zero = time.perf_counter()          # set-up ends, traffic starts
    while True:
        now = time.perf_counter() - t_zero
        if t_open is None and now >= lead_in:
            t_open = t_zero + now
            out_open = [len(r["req"].generated) if r["req"] else 0
                        for r in records[:nxt]]
        if now >= lead_in + seconds:
            t_close = t_zero + now
            break
        if trace_at is not None and tracing is None and now >= trace_at:
            tracing = start()
            t_traced = time.perf_counter()
        while nxt < len(records) and records[nxt]["due"] <= now:
            r = records[nxt]
            r["late"] = now - r["due"]
            with span("serve.submit"):
                try:
                    r["req"] = eng.submit(r["prompt"],
                                          max_new_tokens=r["budget"])
                except EngineBusy:
                    r["failed"] = True
            nxt += 1
            now = time.perf_counter() - t_zero
        t0 = time.perf_counter()
        with span("serve.step"):
            worked = eng.step()
        t1 = time.perf_counter()
        live = contexts = queued = 0
        for r in records[:nxt]:
            q = r["req"]
            if q is None or q.finished:
                continue
            if q.slot is None:
                queued += 1
            else:
                r["slot"] = q.slot
                live += 1
                contexts += len(q.prompt) + len(q.generated)
        if t_open is not None:
            steps.append({"t0": t0, "t1": t1, "live": live,
                          "contexts": contexts, "queued": queued})
        if not worked:
            with span("serve.wait"):
                time.sleep(max(0.0, min(
                    0.002, records[nxt]["due"] - (t1 - t_zero)))
                    if nxt < len(records) else 0.002)
    out_close = [len(r["req"].generated) if r["req"] else 0
                 for r in records[:nxt]]
    return {"t_zero": t_zero, "t_open": t_open, "t_close": t_close,
            "t_traced": t_traced, "tracing": tracing, "steps": steps,
            "submitted": nxt,
            "out_open": out_open + [0] * (nxt - len(out_open)),
            "out_close": out_close}


def drain(eng, records, policy, max_s):
    """After the close nothing is submitted.  ``due``: step until every
    submitted request has finished (at most ``max_s``); ``none``: only
    fetch what the engine has dispatched.  Returns the seconds it
    took."""
    t = time.perf_counter()
    if policy == "due":
        while any(r["req"] is not None and not r["req"].finished
                  for r in records) and time.perf_counter() - t < max_s:
            eng.step()
            for r in records:
                if r["req"] is not None and r["req"].slot is not None:
                    r["slot"] = r["req"].slot
    eng.drain()
    return time.perf_counter() - t


def window_numbers(records, run, drain_policy):
    """The requests due inside the window, those of them that failed,
    and the tails' samples in ms."""
    t_zero, t_open, t_close = run["t_zero"], run["t_open"], run["t_close"]
    mine = [r for r in records[:run["submitted"]]
            if t_open - t_zero <= r["due"] < t_close - t_zero]
    failed = [r for r in mine if r["failed"]
              or (drain_policy == "due" and not r["req"].finished)]
    ttft = [1e3 * (r["req"].t_first - (t_zero + r["due"]))
            for r in mine if r["req"] is not None
            and r["req"].t_first is not None]
    tpot = [1e3 * (r["req"].t_done - r["req"].t_first)
            / (len(r["req"].generated) - 1)
            for r in mine if r["req"] is not None and r["req"].finished
            and len(r["req"].generated) > 1]
    return mine, failed, ttft, tpot


def run(ctx):
    cell, cfg, mix = ctx["cell"], ctx["cfg"], ctx["traffic"]
    watch = ctx["watch"]
    seconds, lead_in = ctx["seconds"], float(mix["lead_in_s"])
    flops = _serve_module(ctx, "flops")

    net, eng, (t_init, t_warm, t_warmed) = build(ctx)
    firsts = first_requests(eng, cfg, ctx["seed"],
                            cell["check"]["first_request_tokens"])
    rows = records(mix, ctx["seed"], lead_in + seconds, cfg["vocab_size"])
    setup = watch.snapshot()

    # ---- lead-in, window, drain -----------------------------------------
    trace_at = lead_in + seconds - min(mix["trace_seconds"], seconds) \
        if ctx["trace"] else None
    w = drive(eng, rows, lead_in, seconds, trace_at,
              lambda: start_trace(ctx))
    t_zero, t_open, t_close = w["t_zero"], w["t_open"], w["t_close"]
    setup_s = t_zero - ctx["t_process"]
    xplane = stop_trace(ctx, w["tracing"]) if w["tracing"] is not None \
        else None
    in_window = watch.snapshot()
    peak = memory_peak_bytes(ctx["devices"])
    rows = rows[:w["submitted"]]
    drain_s = drain(eng, rows, mix["drain"], mix["drain_max_s"])
    post_warmup_compiles = eng.post_warmup_compiles

    # ---- the window's numbers -------------------------------------------
    window_s = t_close - t_open
    mine, failed, ttft, tpot = window_numbers(rows, w, mix["drain"])
    attempted, n_failed = len(mine), len(failed)
    tokens_out = needed = 0
    finished_in_window = []
    for r, a, b in zip(rows, w["out_open"], w["out_close"]):
        if r["req"] is None:
            continue
        tokens_out += b - a
        needed += flops.request_flops(cfg, len(r["prompt"]), a, b)
        if r["req"].finished and r["req"].t_done <= t_close and b > a:
            finished_in_window.append(r)
    serve_tok_s = tokens_out / window_s
    # every cell computes all of them; BENCHMARK.json says which a cell
    # is judged by (the tails below the knee, tokens/s above it)
    end_to_end = {"setup_s": setup_s, "serve_tok_s": serve_tok_s,
                  "serve_mfu": 100.0 * needed / window_s
                  / (ctx["chips"] * ctx["peak"]["bf16_flops"]),
                  "ttft_p95_ms": percentile(ttft, 95),
                  "tpot_p95_ms": percentile(tpot, 95)}
    late = [1e3 * r["late"] for r in mine]
    print(f"# serve: {attempted} requests due in a window of "
          f"{window_s:.3f} s ({attempted / window_s:.2f}/s), "
          f"{len(finished_in_window)} finished in it, {n_failed} failed; "
          f"{tokens_out} tokens out = {serve_tok_s:.1f} tokens/s over "
          f"{sum(1 for s in w['steps'] if s['live'])} steps, drain "
          f"{drain_s:.1f} s", flush=True)

    # ---- what the check needs, then the program is freed ----------------
    sample = _sample(ctx, eng, rows, finished_in_window, firsts)
    request_rows = [{
        "due": t_zero + r["due"], "late": r["late"],
        "prompt": len(r["prompt"]),
        "bucket": eng.bucket_for(len(r["prompt"])),
        "generated": len(r["req"].generated),
        "t_submit": r["req"].t_submit, "t_admitted": r["req"].t_admitted,
        "t_first": r["req"].t_first, "t_done": r["req"].t_done,
    } for r in mine if r["req"] is not None]
    n_finished = len(finished_in_window)
    del net, eng, firsts, rows, mine, failed, finished_in_window
    gc.collect()
    t_ref = time.perf_counter()
    check, checked = _check(ctx, sample, post_warmup_compiles,
                            in_window["programs"] - setup["programs"])
    reference_s = time.perf_counter() - t_ref
    for row in check.rows:        # also where a failed run's record looks
        print("# check " + json.dumps(row), file=sys.stderr, flush=True)

    return {
        "end_to_end": end_to_end,
        "attempted": attempted, "failed": n_failed,
        "check": check, "memory_peak_bytes": peak, "xplane": xplane,
        "info": {"setup_s": setup_s, "compile_setup": setup,
                 "window_s": window_s, "finished_in_window": n_finished,
                 "tokens_out": tokens_out, "end_to_end": end_to_end,
                 "live_slots": _summary([s["live"] for s in w["steps"]]),
                 "ttft_ms": _summary(ttft), "tpot_ms": _summary(tpot),
                 "submit_late_ms": _summary(late), "drain_s": drain_s,
                 "checked": checked, "reference_s": reference_s},
        "observations": {
            # ``window`` as the set-up readers take it: [0] is where
            # set-up ended
            "window": (t_zero, t_close), "serve_window": (t_open, t_close),
            "traced": (w["t_traced"], t_close),
            "memory_peak_bytes": peak, "compile_setup": setup,
            "steps": w["steps"], "requests": request_rows,
            "max_slots": cell["engine"]["max_slots"],
            "weight_itemsize": _itemsize(cell["weights"]),
            "cache_itemsize": _itemsize(cell["engine"]["cache_dtype"]),
            "serve_flops": flops,
            "_setup_timeline": _setup_pieces(
                ctx["t_process"], t_init, t_warm, t_warmed, t_zero),
        },
    }


def _itemsize(dtype):
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]


def _summary(values):
    if not values:
        return None
    return {"n": len(values), "p50": percentile(values, 50),
            "p95": percentile(values, 95), "max": max(values)}


def _serve_module(ctx, kind):
    import run as harness
    mod = harness.load_module(kind, ctx["cfg"]["family"] + ".serve")
    if mod is None:
        raise SystemExit(f"chipbench: no chipbench/{kind}/"
                         f"{ctx['cfg']['family']}.serve.py")
    return mod


def _setup_pieces(t_process, t_init, t_warm, t_warmed, t_zero):
    """``setup_s`` cut six ways with no rest, under the names the train
    driver's cut has (``setup_timeline.PIECES``), at this driver's
    analogous stamps: process start | the package's ``import`` span |
    weights and the zoo's constructors | the object that owns the
    compiled programs (``ServeEngine.__init__``: cache and state
    allocated) | its first calls (``warmup()``: every program traced,
    lowered, compiled or loaded) | the first served work (a request a
    bucket).  None for all where the program keeps no start-up record."""
    import setup_timeline
    from mxnet_tpu import trace
    startup = getattr(trace, "startup", None)
    imp = startup and next((s for s in startup() if s["name"] == "import"),
                           None)
    if not imp:
        return None
    stamps = [t_process, imp["start_s"], imp["end_s"], t_init, t_warm,
              t_warmed, t_zero]
    pieces = dict.fromkeys(setup_timeline.PIECES)
    for name, a, b in zip(setup_timeline.PIECES, stamps, stamps[1:]):
        if t_process <= a <= b <= t_zero:
            pieces[name] = b - a
    print("# setup " + json.dumps({"pieces": pieces}), flush=True)
    return pieces


def _sample(ctx, eng, records, finished_in_window, firsts):
    """What the check compares, taken while the engine still stands:
    ``check.requests`` of the requests the window finished, drawn by
    the seed, the longest among them; set-up's first requests; and the
    last request each slot held, with the rows the engine's programs
    wrote for it (``families/<family>.serve.py``), up to
    ``check.cache_rows`` rows in all."""
    chk = ctx["cell"]["check"]
    rng = onp.random.default_rng([11, int(ctx["seed"]) % (2 ** 32)])
    by_length = sorted(finished_in_window, key=lambda r: -len(r["prompt"])
                       - len(r["req"].generated))
    drawn = by_length[:1] + [by_length[1:][i] for i in
                             rng.permutation(len(by_length) - 1)]
    sample = [(list(r["prompt"]), list(r["req"].generated), None)
              for r in drawn[:chk["requests"]]]
    sample += [(list(q.prompt), list(q.generated), None) for q in firsts]
    holder = {}
    for r in sorted((r for r in records if r["slot"] is not None),
                    key=lambda r: r["req"].t_admitted):
        holder[r["slot"]] = r
    family = _serve_module(ctx, "families")
    reference = _serve_module(ctx, "reference")
    held = 0
    for slot in rng.permutation(sorted(holder)):
        q = holder[slot]["req"]
        n = len(q.prompt) + len(q.generated) - 1
        if n < 1 or held + n > chk["cache_rows"]:
            continue
        held += n
        sample.append((list(q.prompt), list(q.generated),
                       family.cache_rows(eng, int(slot),
                                         reference.padded(n, ctx["cfg"]))))
    return sample


def _check(ctx, sample, post_warmup_compiles, programs_in_window):
    """The sample against the reference.  Its weights are the
    configuration's as the cell serves them: the benchmark's generator
    makes them in the cell's type, and the reference computes on those
    values in float32."""
    import jax.numpy as jnp
    cell, cfg, family = ctx["cell"], ctx["cfg"], ctx["family"]
    lim = cell["check"]["limits"]
    check = Check()
    if not any(rows is not None for _, _, rows in sample):
        check.exactly("slots_with_rows_to_compare", 0, "> 0")
        return check, {"requests": len(sample)}
    params = {k: v.astype(jnp.float32) for k, v in family.make_weights(
        cfg, ctx["seed"], cell["weights"]).items()}
    gaps, cache = _serve_module(ctx, "reference").compare(
        params, sample, cfg)
    del params
    for name, key in (("cache_projected_err_worst_layer", "fitted"),
                      ("cache_plain_err_worst_layer", "plain")):
        worst = max(range(len(cache[key])), key=cache[key].__getitem__)
        check.at_most(name, cache[key][worst],
                      f"layer {worst}, {cache['rows']} rows",
                      limit=lim[name.replace("_worst_layer", "")])
    k = max(range(len(gaps)), key=lambda i: gaps[i].max())
    check.at_most("served_logit_gap_widest", float(gaps[k].max()),
                  f"request {k} of the sample, token {int(gaps[k].argmax())}",
                  limit=lim["logit_gap"])
    check.exactly("post_warmup_compiles", int(post_warmup_compiles), 0)
    check.exactly("programs_compiled_in_window", int(programs_in_window), 0)
    gaps = onp.concatenate(gaps)
    return check, {
        "requests": len(sample), "tokens": int(gaps.size),
        "cache_rows": cache["rows"],
        "cache_projected_err": [round(x, 6) for x in cache["fitted"]],
        "cache_plain_err": [round(x, 6) for x in cache["plain"]],
        "logit_gap_mean": float(gaps.mean()),
        "not_reference_choice": float((gaps > 0).mean())}
