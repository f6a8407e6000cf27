"""Kernel-level autotuning: searched Pallas block/grid shapes.

The step-level search (search.py) picks ``{batch_size, steps_per_call,
...}``; this module tunes the layer below — the tile shapes every
Pallas kernel hard-coded until now (``block_q``/``block_k`` for flash
attention forward and backward, ``block_m``/``block_n`` for the
int8/fp8 matmuls, the ln_residual row tile).  TVM-style
(arXiv 1802.04799): an analytic VMEM-footprint model prunes the block
grid, a cost model — learned (learned.py) when it beats the closed
form on recorded trials, analytic otherwise — ranks the survivors, and
only the predicted-top ``autotune.kernel_trial_fraction`` is measured
with short hermetic trials (same ``trial_compile_scope`` / OOM-survival
discipline as the step search).

Winners persist in the same ``winners.json`` (schema 2, persist.py)
keyed ``kernel|shape_bucket|device_kind`` and load into a
process-global tuned-shape table.  Kernel call sites route through
:func:`resolve_blocks` — a tuned run changes no call signatures, and an
untuned run falls back to a per-``device_kind`` static default table
(one module-dict read on the fast path; gated under the <2% budget by
benchmark/telemetry_overhead.py).

Closing the loop online: :class:`Retuner` arms on ``insight.drift``
events (``autotune.retune_on_drift`` knob), re-searches in a background
thread, and hot-swaps the winner at the next checkpoint boundary via
``ShardedTrainStep.rebuild`` — an ``autotune.retune`` trace span and
the ``autotune.retunes_total`` counter mark every swap.
"""
from __future__ import annotations

import itertools
import math
import threading
import time

from .. import config as _config
from .. import fault as _fault
from .. import goodput as _goodput
from .. import runtime as _runtime
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..base import MXNetError
from .cost import (VMEM_BYTES, VMEM_FRACTION, kernel_cost,
                   kernel_tile_bytes)
from .learned import LearnedCostModel, rank_gate
from .persist import (append_trials, kernel_key, load_all, load_trials,
                      save_winner, winners_path)
from .search import TrialOOM, _is_oom, trial_compile_scope
from .space import as_axis

__all__ = ["KERNELS", "resolve_blocks", "shape_bucket", "static_blocks",
           "kernel_candidates", "search_kernels", "load_tuned",
           "kernel_config_summary", "KernelSearchResult", "Retuner",
           "last_kernel_summary", "reset"]

#: the tunable kernels and their block-shape axes (flash attention's
#: forward and backward passes tile independently — the bwd kernels
#: carry twice the accumulator footprint, so their optimum is smaller)
KERNELS = ("flash_attention", "flash_attention_bwd", "quantized_matmul",
           "fp8_matmul", "ln_residual")

_SPACE = {
    "flash_attention": {"block_q": (256, 512, 1024, 2048),
                        "block_k": (128, 256, 512, 1024)},
    "flash_attention_bwd": {"block_q": (256, 512, 1024),
                            "block_k": (128, 256, 512)},
    "quantized_matmul": {"block_m": (64, 128, 256, 512),
                         "block_n": (128, 256, 512)},
    "fp8_matmul": {"block_m": (64, 128, 256, 512),
                   "block_n": (128, 256, 512)},
    "ln_residual": {"block_rows": (64, 128, 256, 512, 1024)},
}

#: per-device_kind static defaults — the no-winner fallback.  The "cpu"
#: row is the interpret-mode path and keeps the historical one-size
#: constants bit-for-bit (CPU CI behavior is unchanged); the TPU rows
#: size tiles to each generation's VMEM/MXU balance: v4 favors smaller
#: q tiles (HBM BW per FLOP is tighter), v6 takes the largest tiles its
#: VMEM fits.
_STATIC_DEFAULTS = {
    "v4": {"flash_attention": {"block_q": 512, "block_k": 512},
           "flash_attention_bwd": {"block_q": 512, "block_k": 512},
           "quantized_matmul": {"block_m": 256, "block_n": 256},
           "fp8_matmul": {"block_m": 256, "block_n": 256},
           "ln_residual": {"block_rows": 256}},
    "v5e": {"flash_attention": {"block_q": 512, "block_k": 512},
            "flash_attention_bwd": {"block_q": 512, "block_k": 512},
            "quantized_matmul": {"block_m": 256, "block_n": 512},
            "fp8_matmul": {"block_m": 256, "block_n": 512},
            "ln_residual": {"block_rows": 512}},
    "v6": {"flash_attention": {"block_q": 2048, "block_k": 1024},
           "flash_attention_bwd": {"block_q": 1024, "block_k": 512},
           "quantized_matmul": {"block_m": 512, "block_n": 512},
           "fp8_matmul": {"block_m": 512, "block_n": 512},
           "ln_residual": {"block_rows": 512}},
    "cpu": {"flash_attention": {"block_q": 1024, "block_k": 512},
            "flash_attention_bwd": {"block_q": 1024, "block_k": 512},
            "quantized_matmul": {"block_m": 256, "block_n": 256},
            "fp8_matmul": {"block_m": 256, "block_n": 256},
            "ln_residual": {"block_rows": 256}},
}

#: process-global tuned-shape table: (kernel, bucket) -> blocks dict.
#: Mutated in place (never rebound) so resolve_blocks' fast path is one
#: truthiness test on a module global.
_TUNED = {}
#: resolved static defaults for THIS process' device family, filled
#: lazily on first resolve (jax backend init is too heavy for import)
_STATIC = {}

#: summary of the most recent kernel search in this process — merged
#: into the "autotune" plane of TrainingTelemetry run reports
_LAST_KERNELS = None


def _device_family(device_kind=None):
    """Map a device kind onto a static-default row (v4 / v5e / v6 /
    cpu).  v5p ("TPU v5", "TPU v5p") sizes like v6 (same-generation
    VMEM); v2/v3 take the v4 row (closest conservative tiling).  A kind
    no row was sized for raises: blocks tuned for another chip's VMEM
    are a guess, and a guess must not look like a default."""
    if device_kind is None:
        if not _runtime.on_tpu():
            return "cpu"
        import jax
        device_kind = jax.devices()[0].device_kind
    k = str(device_kind).lower()
    if k == "cpu":
        return "cpu"
    if "v6" in k or "v5p" in k or k == "tpu v5":
        return "v6"
    if "v5" in k:
        return "v5e"
    if "v4" in k or "v3" in k or "v2" in k:
        return "v4"
    raise MXNetError(
        f"no static kernel blocks for device kind {device_kind!r}; add a "
        "row to autotune.kernels._STATIC_DEFAULTS")


def static_blocks(kernel, device_kind=None):
    """The per-device_kind static default blocks for ``kernel`` (the
    untuned fallback)."""
    if kernel not in _SPACE:
        raise MXNetError(f"unknown kernel {kernel!r}; one of {KERNELS}")
    return dict(_STATIC_DEFAULTS[_device_family(device_kind)][kernel])


def _init_static():
    fam = _STATIC_DEFAULTS[_device_family()]
    for kern, blocks in fam.items():
        _STATIC[kern] = dict(blocks)
    return _STATIC


def _p2(n):
    return 1 << max(0, int(n) - 1).bit_length()


def shape_bucket(kernel, shape):
    """Bucket a problem shape: every searched dim rounds up to a power
    of two, so one measured winner covers the whole bucket (tile choice
    is insensitive to small shape deltas; a 2x shape change re-tunes)."""
    if kernel in ("flash_attention", "flash_attention_bwd"):
        sq, sk, d = shape
        return (_p2(sq), _p2(sk), int(d))
    if kernel in ("quantized_matmul", "fp8_matmul"):
        m, n, k = shape
        return (_p2(m), _p2(n), _p2(k))
    if kernel == "ln_residual":
        rows, dim = shape
        return (_p2(rows), int(dim))
    raise MXNetError(f"unknown kernel {kernel!r}; one of {KERNELS}")


def resolve_blocks(kernel, shape=None):
    """Blocks for one kernel call: the tuned winner for the shape's
    bucket when one is loaded, else the per-device static default.

    This is the routing every kernel call site takes at TRACE time (the
    resolved values are static python ints baked into the jitted
    executable) — the untuned fast path is one module-dict truthiness
    test plus one dict read, gated <2% by the CI overhead budget.
    """
    if _TUNED and shape is not None:
        rec = _TUNED.get((kernel, shape_bucket(kernel, shape)))
        if rec is not None:
            return rec
    blocks = _STATIC.get(kernel)
    if blocks is not None:
        return blocks
    return _init_static()[kernel]


def _clamped(kernel, bucket, blocks):
    """The effective blocks after the kernel's own shape clamps — used
    to dedup candidates that compile identically on a small bucket."""
    b = dict(blocks)
    if kernel in ("flash_attention", "flash_attention_bwd"):
        sq, sk, _d = bucket
        return (min(b["block_q"], sq), min(b["block_k"], sk))
    if kernel in ("quantized_matmul", "fp8_matmul"):
        m, n, _k = bucket
        return (min(b["block_m"], -(-m // 32) * 32),
                min(b["block_n"], -(-n // 128) * 128))
    rows, _dim = bucket
    br = min(b["block_rows"], max(8, rows))
    return ((br + 7) // 8 * 8,)


def kernel_candidates(kernel, bucket=None, axes=None):
    """Enumerate the block grid for one kernel, deterministic order.
    With a ``bucket``, candidates whose clamped effective tiles coincide
    are deduped (first wins) — on small problems most of the grid
    collapses.  ``axes`` overrides any axis, e.g. ``{"block_q": (128,
    256)}``."""
    if kernel not in _SPACE:
        raise MXNetError(f"unknown kernel {kernel!r}; one of {KERNELS}")
    space = dict(_SPACE[kernel])
    for name, vals in (axes or {}).items():
        if name not in space:
            raise MXNetError(f"{kernel} has no block axis {name!r}")
        space[name] = as_axis(vals)
    names = sorted(space)
    out, seen = [], set()
    for vals in itertools.product(*(space[n] for n in names)):
        blocks = dict(zip(names, (int(v) for v in vals)))
        if bucket is not None:
            eff = _clamped(kernel, bucket, blocks)
            if eff in seen:
                continue
            seen.add(eff)
        out.append(blocks)
    return out


def reset():
    """Drop every loaded/tuned winner and the last kernel summary (test
    isolation; the static defaults are device facts and survive)."""
    global _LAST_KERNELS
    _TUNED.clear()
    _LAST_KERNELS = None


def last_kernel_summary():
    """Summary of the most recent kernel search in this process (None
    when none ran) — merged into run reports via search.last_summary."""
    return _LAST_KERNELS


def load_tuned(path=None, device_kind=None):
    """Load persisted kernel winners for this device kind into the
    process-global table; returns the number of entries loaded."""
    if device_kind is None:
        import jax
        devs = jax.devices()
        device_kind = (getattr(devs[0], "device_kind", "cpu") if devs
                       else "cpu")
    n = 0
    for key, rec in load_all(path).items():
        if not isinstance(rec, dict) or rec.get("kind") != "kernel":
            continue
        if rec.get("device_kind") != device_kind:
            continue
        kern = rec.get("kernel")
        bucket = rec.get("bucket")
        blocks = rec.get("blocks")
        if kern in _SPACE and isinstance(blocks, dict) and bucket:
            _TUNED[(kern, tuple(int(d) for d in bucket))] = {
                k: int(v) for k, v in blocks.items()}
            n += 1
    return n


def kernel_config_summary():
    """The resolved block shapes per kernel (static defaults overlaid
    with any loaded tuned winners) plus the tuned-bucket count — what
    bench.py stamps on train/decode rows as ``kernel_config``."""
    out = {}
    try:
        for kern in KERNELS:
            out[kern] = dict(resolve_blocks(kern))
    except Exception:
        return {}
    for (kern, _bucket), blocks in sorted(_TUNED.items()):
        out[kern] = dict(blocks)
    out["tuned_buckets"] = len(_TUNED)
    return out


# ---------------------------------------------------------------------------
# measured trials
# ---------------------------------------------------------------------------

#: default representative problem shapes per kernel (CPU CI keeps them
#: tiny — interpret-mode trials are Python-speed; a TPU run tunes real
#: production geometry)
def default_shapes(device_kind=None):
    if _device_family(device_kind) == "cpu":
        return {"flash_attention": [(128, 128, 64)],
                "flash_attention_bwd": [(128, 128, 64)],
                "quantized_matmul": [(128, 128, 128)],
                "fp8_matmul": [(128, 128, 128)],
                "ln_residual": [(256, 128)]}
    return {"flash_attention": [(2048, 2048, 128)],
            "flash_attention_bwd": [(2048, 2048, 128)],
            "quantized_matmul": [(1024, 1024, 4096)],
            "fp8_matmul": [(1024, 1024, 4096)],
            "ln_residual": [(4096, 1024)]}


class _Owner:
    """Compile-count owner for trial_compile_scope (the kernel tuner
    has no Block to charge trial compiles to)."""


_OWNER = _Owner()


def _make_trial_fn(kernel, bucket, interpret):
    """Build inputs once for a bucket and return ``fn(blocks) ->
    seconds-per-call`` timing the REAL kernel (jit + block_until_ready),
    hermetic: fresh arrays, no model state touched."""
    import numpy as onp
    import jax
    import jax.numpy as jnp

    rs = onp.random.RandomState(0)
    if kernel in ("flash_attention", "flash_attention_bwd"):
        from ..ops.pallas.flash_attention import flash_attention
        sq, sk, d = bucket
        # timed on what the chip runs: bf16 operands, and heads enough
        # that the grid's steady state outweighs its first steps
        q = jnp.asarray(rs.randn(4, 8, sq, d), jnp.bfloat16)
        k = jnp.asarray(rs.randn(4, 8, sk, d), jnp.bfloat16)
        v = jnp.asarray(rs.randn(4, 8, sk, d), jnp.bfloat16)

        def build(blocks):
            if kernel == "flash_attention":
                def f(q_, k_, v_):
                    return flash_attention(q_, k_, v_, causal=True,
                                           interpret=interpret, **blocks)
            else:
                def f(q_, k_, v_):
                    # all three gradients: one asked for alone lets XLA
                    # drop the other backward kernel from the trial
                    def loss(qq, kk, vv):
                        return flash_attention(
                            qq, kk, vv, causal=True, interpret=interpret,
                            bwd_block_q=blocks["block_q"],
                            bwd_block_k=blocks["block_k"]
                        ).astype(jnp.float32).sum()
                    return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)
            return jax.jit(f), (q, k, v)
    elif kernel in ("quantized_matmul", "fp8_matmul"):
        m, n, kk = bucket
        x = jnp.asarray(rs.randn(m, kk), jnp.float32)
        ws = jnp.asarray(onp.abs(rs.randn(n)) / 127.0 + 1e-4, jnp.float32)
        xs = jnp.float32(0.05)
        if kernel == "quantized_matmul":
            from ..ops.pallas.quant_matmul import quantized_matmul as mm
            w = jnp.asarray(rs.randint(-127, 128, (n, kk)), jnp.int8)
        else:
            from ..ops.pallas.quant_matmul import (FP8_FORMATS,
                                                   fp8_matmul as mm)
            w = jnp.asarray(rs.randn(n, kk), FP8_FORMATS["e4m3"][0])

        def build(blocks):
            def f(x_, w_, ws_, xs_):
                return mm(x_, w_, ws_, xs_, interpret=interpret, **blocks)
            return jax.jit(f), (x, w, ws, xs)
    elif kernel == "ln_residual":
        from ..ops.pallas.ln_residual import ln_residual_dropout
        rows, dim = bucket
        x = jnp.asarray(rs.randn(rows, dim), jnp.float32)
        h = jnp.asarray(rs.randn(rows, dim), jnp.float32)
        g = jnp.ones((dim,), jnp.float32)
        b = jnp.zeros((dim,), jnp.float32)

        def build(blocks):
            def f(x_, h_, g_, b_):
                return ln_residual_dropout(x_, h_, g_, b_,
                                           interpret=interpret, **blocks)
            return jax.jit(f), (x, h, g, b)
    else:
        raise MXNetError(f"unknown kernel {kernel!r}")

    def run(blocks, trial_seconds, warmup, max_calls=50):
        fn, args = build(blocks)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))    # compile
        _telemetry.note_compile(_OWNER, f"autotune.kernel:{kernel}",
                                time.perf_counter() - t0)
        for _ in range(max(0, warmup - 1)):
            fn(*args)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        pilot = max(time.perf_counter() - t0, 1e-7)
        calls = min(max_calls, max(1, math.ceil(trial_seconds / pilot)))
        t0 = time.perf_counter()
        out = None
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / calls

    return run


class KernelSearchResult:
    """Outcome of one :func:`search_kernels` call: per-(kernel, bucket)
    winners, the raw trials, and what the ranking model was."""

    def __init__(self, device_kind):
        self.device_kind = device_kind
        self.searches = []       # per-bucket dicts
        self.trials = []         # raw trial records
        self.tuned = {}          # (kernel, bucket) -> blocks
        self.cache_hits = 0
        self.ranked_by = "analytic"
        self.learned_corr = None
        self.analytic_corr = None
        self.wall_s = 0.0

    @property
    def n_trials(self):
        return len(self.trials)

    def summary(self):
        out = {"device_kind": self.device_kind,
               "searches": self.searches,
               "trials": len(self.trials),
               "cache_hits": self.cache_hits,
               "ranked_by": self.ranked_by,
               "wall_s": round(self.wall_s, 3),
               "kernel_trials": self.trials}
        if self.learned_corr is not None:
            out["learned_rank_corr"] = round(self.learned_corr, 4)
            out["analytic_rank_corr"] = round(self.analytic_corr, 4)
        return out


def search_kernels(kernels=None, shapes=None, measure=None, force=False,
                   persist=True, publish=True, trial_seconds=None,
                   warmup=None, fraction=None, use_learned=True,
                   interpret=None, telemetry_jsonl=None):
    """Search tuned block shapes for ``kernels`` over ``shapes``.

    ``shapes`` maps kernel -> problem-shape list (defaults to one
    representative shape per kernel); each distinct shape bucket gets
    its own search.  ``measure(kernel, bucket, blocks) -> seconds``
    injects a deterministic backend (tests/chaos); the real path times
    jitted kernel calls hermetically under ``trial_compile_scope``.
    Winners persist to winners.json (schema 2) and — with ``publish`` —
    load into the process-global table immediately; the drift Retuner
    passes ``publish=False`` and applies at a checkpoint boundary.
    """
    global _LAST_KERNELS
    t_start = time.perf_counter()
    import jax
    device_kind = jax.devices()[0].device_kind
    if interpret is None:
        interpret = _runtime.pallas_interpret()
    if fraction is None:
        fraction = float(_config.get("autotune.kernel_trial_fraction"))
    if trial_seconds is None:
        trial_seconds = float(_config.get("autotune.kernel_trial_seconds"))
    if warmup is None:
        warmup = int(_config.get("autotune.trial_warmup"))
    kernels = tuple(kernels) if kernels else KERNELS
    for kern in kernels:
        if kern not in _SPACE:
            raise MXNetError(f"unknown kernel {kern!r}; one of {KERNELS}")
    if shapes is None:
        shapes = default_shapes(device_kind)
    path = winners_path()
    result = KernelSearchResult(device_kind)

    # the learned model trains on every recorded trial this host can
    # see: the winners-file ring plus (optionally) a fleet-aggregated
    # TrainingTelemetry JSONL
    records = list(load_trials(path)) if persist else []
    if telemetry_jsonl:
        from .learned import load_telemetry_records
        records.extend(load_telemetry_records(telemetry_jsonl))
    model = LearnedCostModel()
    use_model = False
    if use_learned and records:
        model.fit(records)
        use_model, lc, ac = rank_gate(model, records)
        result.learned_corr, result.analytic_corr = lc, ac
        _telemetry.set_gauge("autotune.learned_rank_corr", lc)
    result.ranked_by = "learned" if use_model else "analytic"

    vmem_budget = int(VMEM_BYTES * VMEM_FRACTION)
    root = _trace.begin("autotune.kernel_search", category="autotune",
                        kernels=",".join(kernels)) if _trace._active else None

    with trial_compile_scope(_OWNER):
        for kern in kernels:
            for shape in shapes.get(kern, ()):
                bucket = shape_bucket(kern, shape)
                key = kernel_key(kern, bucket, device_kind)
                if persist and not force:
                    rec = load_all(path).get(key)
                    if rec is not None and isinstance(
                            rec.get("blocks"), dict):
                        blocks = {k: int(v)
                                  for k, v in rec["blocks"].items()}
                        if publish:
                            _TUNED[(kern, bucket)] = blocks
                        result.tuned[(kern, bucket)] = blocks
                        result.cache_hits += 1
                        result.searches.append(
                            {"key": key, "reused": True, "blocks": blocks})
                        _telemetry.inc("autotune.kernel_cache_hits_total")
                        continue

                cands = kernel_candidates(kern, bucket)
                _telemetry.inc("autotune.candidates_total", len(cands))
                kept, n_vmem = [], 0
                for blocks in cands:
                    if kernel_tile_bytes(kern, bucket,
                                         blocks) > vmem_budget:
                        n_vmem += 1
                        _telemetry.inc("autotune.pruned_total",
                                       reason="vmem")
                    else:
                        kept.append(blocks)
                if not kept:          # degenerate budget: keep the default
                    kept = [static_blocks(kern, device_kind)]
                if use_model:
                    kept.sort(key=lambda b: model.predict(kern, bucket, b))
                else:
                    kept.sort(key=lambda b: kernel_cost(kern, bucket, b))
                n_measure = max(1, int(fraction * len(kept)))
                default = static_blocks(kern, device_kind)
                eff_default = _clamped(kern, bucket, default)
                chosen = kept[:n_measure]
                if not any(_clamped(kern, bucket, b) == eff_default
                           for b in chosen):
                    # the static default always gets a measured baseline;
                    # it replaces the worst-ranked pick so the fraction
                    # cap holds
                    chosen[-1] = default
                for blocks in kept[len(chosen):]:
                    _telemetry.inc("autotune.pruned_total",
                                   reason="ranked_out")

                trial_fn = None
                trials_here = []
                for blocks in chosen:
                    sp = _trace.begin(
                        "autotune.trial", category="autotune",
                        parent=(root.context if root else None),
                        kernel=kern, **blocks) if _trace._active else None
                    t0 = time.perf_counter()
                    rec = {"kernel": kern, "bucket": list(bucket),
                           "blocks": dict(blocks),
                           "device_kind": device_kind, "status": "ok",
                           "created": time.time()}
                    try:
                        if _fault._active and _fault.fire(
                                "autotune.trial_oom"):
                            raise TrialOOM(
                                f"injected OOM for {kern}{blocks}")
                        if measure is not None:
                            sec = float(measure(kern, bucket, blocks))
                        else:
                            if trial_fn is None:
                                trial_fn = _make_trial_fn(kern, bucket,
                                                          interpret)
                            sec = trial_fn(blocks, trial_seconds, warmup)
                        rec["seconds"] = sec
                    except Exception as e:
                        rec["status"] = ("oom" if _is_oom(e) else "error")
                        rec["error"] = f"{type(e).__name__}: {e}"[:300]
                        if rec["status"] == "oom":
                            _telemetry.inc("autotune.trials_oom_total")
                            _fault.record("autotune.trial_oom")
                    rec["wall_s"] = round(time.perf_counter() - t0, 4)
                    if sp is not None:
                        sp.end(status=rec["status"],
                               seconds=rec.get("seconds", 0.0))
                    _telemetry.inc("autotune.kernel_trials_total")
                    trials_here.append(rec)
                result.trials.extend(trials_here)

                ok = [t for t in trials_here if t["status"] == "ok"]
                if not ok:
                    result.searches.append(
                        {"key": key, "reused": False, "blocks": None,
                         "trials": len(trials_here)})
                    continue
                best = min(ok, key=lambda t: t["seconds"])
                dflt = next((t for t in ok
                             if _clamped(kern, bucket, t["blocks"])
                             == eff_default), None)
                speedup = (dflt["seconds"] / best["seconds"]
                           if dflt and best["seconds"] > 0 else None)
                blocks = dict(best["blocks"])
                result.tuned[(kern, bucket)] = blocks
                if publish:
                    _TUNED[(kern, bucket)] = blocks
                result.searches.append(
                    {"key": key, "reused": False, "blocks": blocks,
                     "trials": len(trials_here),
                     "seconds": round(best["seconds"], 6),
                     "speedup_vs_default": (round(speedup, 4)
                                            if speedup else None)})
                if speedup:
                    _telemetry.set_gauge("autotune.best_speedup", speedup)
                if persist:
                    save_winner(key, {"kind": "kernel", "kernel": kern,
                                      "bucket": list(bucket),
                                      "blocks": blocks,
                                      "seconds": best["seconds"],
                                      "speedup_vs_default": speedup,
                                      "device_kind": device_kind,
                                      "created": time.time()}, path)
    if root is not None:
        root.end(trials=len(result.trials))
    if persist and result.trials:
        append_trials(result.trials, path)
    result.wall_s = time.perf_counter() - t_start
    _telemetry.observe("autotune.search_seconds", result.wall_s)
    _LAST_KERNELS = result.summary()
    return result


# ---------------------------------------------------------------------------
# drift-triggered online re-tuning
# ---------------------------------------------------------------------------

class Retuner:
    """Online re-tune state machine: ARMED -> (insight.drift) ->
    SEARCHING (background thread) -> STAGED -> (checkpoint boundary)
    -> swap via ``ShardedTrainStep.rebuild`` -> ARMED.

    The drift hook only fires a search when ``autotune.retune_on_drift``
    is on and no search is already in flight; the winner is never
    applied mid-step — :meth:`checkpoint` publishes the staged table
    and re-jits the step at the caller's checkpoint boundary, so the
    loss trajectory continues uninterrupted on the same weights and
    ``_n_step``.
    """

    def __init__(self, kernels=None, shapes=None, measure=None,
                 trial_seconds=None, fraction=None):
        self._kw = dict(kernels=kernels, shapes=shapes, measure=measure,
                        trial_seconds=trial_seconds, fraction=fraction,
                        force=True, publish=False)
        self._lock = threading.Lock()
        self._thread = None
        self._staged = None
        self._armed = False
        self.searches = 0
        self.applied = 0

    def arm(self):
        """Register on the insight drift plane; idempotent."""
        if not self._armed:
            from .. import insight as _insight
            _insight.on_drift(self._on_drift)
            self._armed = True
        return self

    def disarm(self):
        if self._armed:
            from .. import insight as _insight
            _insight.remove_drift_hook(self._on_drift)
            self._armed = False
        return self

    def _on_drift(self, source, event):
        if not _config.get("autotune.retune_on_drift"):
            return
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return                      # one re-search at a time
            if self._staged is not None:
                return                      # a winner already awaits swap
            self.searches += 1
            self._thread = threading.Thread(
                target=self._search, name="mx-autotune-retune",
                daemon=True)
            self._thread.start()

    def _search(self):
        # the background re-search competes with training for host
        # cycles: its lifetime is retune badput in the goodput ledger
        tok = _goodput.begin("retune") if _goodput._active else None
        try:
            self._staged = search_kernels(**self._kw)
        except Exception as e:   # a failed re-search must not kill training
            _telemetry.note_event("autotune.retune_failed",
                                  f"{type(e).__name__}: {e}"[:200])
        finally:
            _goodput.end(tok)

    def join(self, timeout=None):
        t = self._thread
        if t is not None:
            t.join(timeout)
        return self

    @property
    def pending(self):
        """True when a finished background search awaits the next
        checkpoint boundary."""
        return self._staged is not None

    def checkpoint(self, step=None):
        """Checkpoint-boundary hook: when a re-search result is staged,
        publish its winners into the process-global table and rebuild
        ``step`` (same mesh, weights synced) so the next jit picks the
        new blocks up.  Returns the (possibly rebuilt) step — callers
        use it as ``step = retuner.checkpoint(step)`` right where they
        save a checkpoint.  No-op (and zero-cost) while nothing is
        staged."""
        res = self._staged
        if res is None:
            return step
        self._staged = None
        sp = _trace.begin("autotune.retune", category="autotune",
                          buckets=len(res.tuned)) if _trace._active else None
        tok = _goodput.begin("retune") if _goodput._active else None
        try:
            _TUNED.update(res.tuned)
            if step is not None and \
                    getattr(step, "mesh_config", None) is not None:
                step = step.rebuild(step.mesh_config)
        finally:
            _goodput.end(tok)
        self.applied += 1
        _telemetry.inc("autotune.retunes_total")
        if sp is not None:
            sp.end(applied=True)
        return step
