"""What the serve readers (``layer_metrics/*.serve.py``)
share: the engine's programs found in the device trace, the driver's own
record of each ``eng.step()``, and the needed work of the traced stretch.

The engine names nothing on the device timeline (no ``jax.named_scope``;
PERF.md section 7), so its work is told apart by program: every executed
program is an event of the line ``XLA Modules`` named by the jitted
function, ``jit__decode_fn(<id>)`` for the one decode step and
``jit__prefill_fn(<id>)`` for each bucket's prefill.  The host side of
the same stretch is ``obs["steps"]`` (one row an ``eng.step()`` that
found live slots: host times, live slots, the context rows they hold,
queue depth) and ``obs["requests"]`` (one row a request due in the
window), both on ``time.perf_counter()``; the traced stretch of the
window is ``obs["traced"]``.
"""
from __future__ import annotations

import bisect
import importlib.util
import math
import os

DECODE, PREFILL = "_decode_fn", "_prefill_fn"


def percentile(values, q):
    """The q-th percentile by the nearest rank at or above (a p95 over
    20 values is the 19th: a value that was measured); None of none."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[min(len(vals), max(1, math.ceil(q / 100.0 * len(vals)))) - 1]


def sibling(path):
    """The ``read`` of ``layer_metrics/<quantity>.serve.py`` for the
    reader file ``<quantity>.<suffix>.py`` at ``path``: one quantity
    read one way, under the name of the end-to-end metric it moves in a
    cell (``.serve`` moves ``serve_tok_s`` on the flooded cell, ``.tpot``
    and ``.ttft`` the two tails of the steady one)."""
    quantity = os.path.basename(path).rsplit(".", 2)[0]
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metrics_" + quantity.replace(".", "_") + "_serve",
        os.path.join(os.path.dirname(path), quantity + ".serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def untraced_requests(obs):
    """The window's requests whose first token reached the caller before
    the profiler started: starting and stopping it stalls the one
    thread that submits and steps (seconds, for a 30 MB trace), and a
    tail over requests in flight then reads the profiler."""
    start = obs["traced"][0]
    return [r for r in obs["requests"] if r["t_first"] is not None
            and (start is None or r["t_first"] < start)]


def live_bytes(obs):
    """What the window's traffic keeps in use on the chip, mean over the
    steps that found live slots: the weights and the cache rows the
    live slots hold (``flops/<family>.serve.py``).  The rest of the
    peak is the cache's reservation (``max_slots x max_seq`` rows) and a
    program's temporaries."""
    lo, hi = obs["serve_window"]
    rows = [s["contexts"] for s in obs["steps"]
            if s["live"] and lo <= s["t0"] <= hi]
    if not rows:
        return None
    cfg, fl = obs["ctx"]["cfg"], obs["serve_flops"]
    return fl.weight_bytes(cfg, obs["weight_itemsize"]) + sum(rows) \
        / len(rows) * fl.cache_row_bytes(cfg, obs["cache_itemsize"])


def modules(obs, kind):
    """Device 0's executions of the decode step (``DECODE``) or of any
    prefill bucket (``PREFILL``) inside the traced window, oldest
    first."""
    d0 = obs["device_trace"]["devices"][0]
    return sorted((m for m in d0["modules"] if kind in m["name"]),
                  key=lambda m: m["start"])


def module_ms(obs, kind):
    """Device times of those executions, in ms."""
    return [(m["end"] - m["start"]) / 1e6 for m in modules(obs, kind)]


def ops_inside(obs, kind):
    """Device 0's operations that ran inside those executions, and how
    many executions there were."""
    mods = modules(obs, kind)
    starts = [m["start"] for m in mods]
    ops = []
    for o in obs["device_trace"]["devices"][0]["ops"]:
        i = bisect.bisect_right(starts, o["start"]) - 1
        if i >= 0 and o["end"] <= mods[i]["end"]:
            ops.append(o)
    return ops, len(mods)


def traced_steps(obs):
    """The driver's rows of the ``eng.step()`` calls inside the traced
    stretch that found live slots (each dispatched one decode step)."""
    lo, hi = obs["traced"]
    return [s for s in obs["steps"]
            if s["live"] and s["t0"] >= lo and s["t1"] <= hi]


def traced_prefills(obs):
    """The requests admitted (prefill dispatched) inside the traced
    stretch."""
    lo, hi = obs["traced"]
    return [r for r in obs["requests"]
            if r["t_admitted"] is not None and lo <= r["t_admitted"] <= hi]


def decode_roofline(obs):
    """The decode step against its roofline: the bytes one step must
    move (``flops/<family>.serve.py``: the weights once, every live
    slot's context rows read, one row a live slot written; mean over
    the traced stretch's steps) over the chip's HBM bandwidth, against
    the mean device time of the decode program.  A decode step is bound
    by bytes; where the operations' bound is the larger it is taken."""
    steps, times = traced_steps(obs), module_ms(obs, DECODE)
    if not steps or not times:
        return None
    ctx, fl = obs["ctx"], obs["serve_flops"]
    by = sum(fl.decode_step_bytes(
        ctx["cfg"], [s["contexts"] / s["live"]] * s["live"],
        obs["weight_itemsize"], obs["cache_itemsize"])
        for s in steps) / len(steps)
    ops = sum(s["live"] * fl.decode_flops(ctx["cfg"],
                                          s["contexts"] / s["live"])
              for s in steps) / len(steps)
    least = max(by / ctx["peak"]["hbm_bytes_per_s"],
                ops / ctx["peak"]["bf16_flops"])
    return 100.0 * least / (sum(times) / len(times) / 1e3)


def stretch_mfu(obs):
    """The traced stretch's needed operations — every live slot's
    decoded token at the context it had, every prefill dispatched at
    its prompt's own length — over the stretch's seconds and the chip's
    peak: the whole step's share, beside the kernels' rooflines."""
    steps = traced_steps(obs)
    if not steps:
        return None
    ctx, fl = obs["ctx"], obs["serve_flops"]
    need = sum(s["live"] * fl.decode_flops(ctx["cfg"],
                                           s["contexts"] / s["live"])
               for s in steps)
    need += sum(fl.prefill_flops(ctx["cfg"], r["prompt"])
                for r in traced_prefills(obs))
    lo, hi = obs["traced"]
    return 100.0 * need / (hi - lo) / (ctx["chips"]
                                       * ctx["peak"]["bf16_flops"])
