"""Measured config search over the compiled training step.

The loop (TVM-style measure-and-prune, arxiv 1802.04799):

1. Enumerate the ``SearchSpace`` grid.
2. ``CostModel.plan`` rejects >=50% of it analytically (dominance + HBM
   budget) — nothing pruned here is ever compiled.
3. Each surviving candidate gets a short **hermetic** measured trial of
   the real ``ShardedTrainStep``: params re-read from the block (never
   written back), the optimizer deep-cloned, trial compiles accounted
   through the recompile detector under a trial-scoped limit, and device
   OOM recorded as a trial outcome instead of killing the search.
4. The measured items/s winner persists to ``winners.json`` keyed by
   ``(model fingerprint, device_kind, dp size)`` — the next run with the
   same key reloads it and runs **zero** trials.

``measure=`` injects a deterministic measurement backend (tests); the
HBM budget defaults to ``"auto"``: read from the same PJRT
``memory_stats`` that feed the ``memory.*`` gauges, scaled by
``autotune.hbm_fraction`` (None on backends without memory stats — the
dominance rules still prune, and real OOMs are caught per trial).
"""
from __future__ import annotations

import contextlib
import copy
import math
import time

import numpy as onp

from .. import config as _config
from .. import fault as _fault
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..base import MXNetError
from .cost import CostModel, ModelStats
from .persist import (load_winner, model_fingerprint, save_winner,
                      winner_key, winners_path)
from .space import Candidate, SearchSpace

__all__ = ["TrialOOM", "TrialParity", "TrialResult", "SearchResult",
           "search", "tune_estimator", "trial_compile_scope",
           "last_summary"]

#: summary of the most recent search in this process — surfaced as the
#: "autotune" plane of TrainingTelemetry run reports
_LAST = None


class TrialOOM(MXNetError):
    """A measured trial exhausted device memory (real RESOURCE_EXHAUSTED,
    or injected via the ``autotune.trial_oom`` fault point)."""


class TrialParity(MXNetError):
    """A reduced-precision candidate failed its loss-parity probe against
    the fp32 reference (relative loss delta beyond
    ``autotune.fp8_parity_tol``).  The candidate is disqualified — fp8
    ships only on shape buckets where trials PROVE parity — but the
    search continues (status "parity" in the trial record)."""


def _is_oom(exc):
    if isinstance(exc, TrialOOM):
        return True
    msg = f"{type(exc).__name__}: {exc}".lower()
    return ("resource_exhausted" in msg or "resource exhausted" in msg
            or "out of memory" in msg or "oom" in msg.split())


@contextlib.contextmanager
def trial_compile_scope(owner, limit=None):
    """Route trial compiles through the recompile detector without letting
    them poison the caller's budget: the per-block compile count and the
    warn-once latch (telemetry.note_compile state) are saved and restored,
    and ``telemetry.recompile_limit`` is raised to the trial allowance for
    the duration — warmup compiles across N candidate configs are
    expected, so they must not trip ``RecompileWarning`` during or after
    the search."""
    if limit is None:
        limit = _config.get("autotune.recompile_limit")
    d = owner.__dict__
    saved = (d.get("_telemetry_compiles", 0),
             d.get("_telemetry_recompile_warned", False))
    saved_limit = _config.get("telemetry.recompile_limit")
    _config.set("telemetry.recompile_limit", int(limit))
    try:
        yield
    finally:
        _config.set("telemetry.recompile_limit", saved_limit)
        d["_telemetry_compiles"] = saved[0]
        d["_telemetry_recompile_warned"] = saved[1]


def _clone_optimizer(opt):
    """Hermetic per-trial optimizer: same hyperparameters/schedule, fresh
    bookkeeping — trials advance the clone's ``num_update``, never the
    caller's."""
    clone = copy.copy(opt)
    clone.param_dict = {}
    clone.idx2name = dict(opt.idx2name)
    clone.lr_mult = dict(opt.lr_mult)
    clone.wd_mult = dict(opt.wd_mult)
    clone._index_update_count = {}
    clone._master_weights = {}
    return clone


class TrialResult:
    """Outcome of one measured (or cached) candidate."""

    def __init__(self, candidate, items_per_s=None, status="ok",
                 seconds=0.0, error=None):
        self.candidate = candidate
        self.items_per_s = items_per_s
        self.status = status          # ok | oom | error | parity | cached
        self.seconds = seconds
        self.error = error

    def summary(self):
        out = {"config": self.candidate.config(), "status": self.status,
               "seconds": round(self.seconds, 4)}
        if self.items_per_s is not None:
            out["items_per_s"] = round(self.items_per_s, 3)
        if self.error:
            out["error"] = self.error
        return out


class SearchResult:
    """What a search produced: the winner, the measured trials, the
    pruned grid, and where the winner persisted."""

    def __init__(self, key, path, n_candidates, trials, pruned, best,
                 default, reused=False, wall_s=0.0, hbm_budget=None):
        self.key = key
        self.path = path
        self.n_candidates = n_candidates
        self.trials = trials
        self.pruned = pruned
        self.best = best
        self.default = default
        self.reused = reused
        self.wall_s = wall_s
        self.hbm_budget = hbm_budget

    @property
    def config(self):
        return self.best.candidate.config() if self.best else None

    @property
    def speedup(self):
        if (self.best and self.default
                and self.best.items_per_s and self.default.items_per_s):
            return self.best.items_per_s / self.default.items_per_s
        return None

    @property
    def pruned_fraction(self):
        if not self.n_candidates:
            return 0.0
        return len(self.pruned) / self.n_candidates

    def summary(self):
        reasons = {}
        for _c, reason in self.pruned:
            reasons[reason] = reasons.get(reason, 0) + 1
        oom = sum(1 for t in self.trials if t.status == "oom")
        out = {"key": self.key, "path": self.path, "reused": self.reused,
               "candidates": self.n_candidates,
               "trials": len(self.trials), "trials_oom": oom,
               "pruned": len(self.pruned), "pruned_by_reason": reasons,
               "pruned_fraction": round(self.pruned_fraction, 4),
               "wall_s": round(self.wall_s, 3),
               "hbm_budget": self.hbm_budget,
               "best": self.best.summary() if self.best else None,
               "default": self.default.summary() if self.default else None}
        if self.speedup is not None:
            out["speedup_vs_default"] = round(self.speedup, 4)
        return out


def last_summary():
    """Summary dict of the most recent search in this process (None when
    no search ran) — merged into TrainingTelemetry run reports.  A
    kernel-level search (kernels.py) contributes a ``"kernels"`` plane
    and the raw ``"kernel_trials"`` records the learned cost model
    harvests back out of fleet-aggregated report files."""
    from . import kernels as _kernels
    ks = _kernels.last_kernel_summary()
    if ks is None:
        return _LAST
    out = dict(_LAST or {})
    out["kernels"] = {k: v for k, v in ks.items() if k != "kernel_trials"}
    out["kernel_trials"] = ks.get("kernel_trials", [])
    return out


def _hbm_budget(devices=None):
    """Per-device HBM budget from the runtime: min ``bytes_limit`` across
    devices (refreshing the ``memory.*`` gauges on the way when telemetry
    is enabled) times ``autotune.hbm_fraction``.  None when the backend
    reports no memory stats (CPU)."""
    if devices is None:
        import jax
        devices = jax.local_devices()
    _telemetry.record_memory(devices)
    limits = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_limit"):
            limits.append(int(stats["bytes_limit"]))
    if not limits:
        return None
    return int(min(limits) * _config.get("autotune.hbm_fraction"))


def _stacked_batch(sample_batch, candidate):
    """Shape the sample batch for a candidate: resize the batch axis to
    ``batch_size * steps_per_call`` samples (cyclic tiling) and fold in
    the leading grad_accum/steps axes exactly as ShardedTrainStep
    expects them."""
    c = candidate
    total = c.batch_size * c.steps_per_call
    micro = c.batch_size // c.grad_accum
    out = []
    for a in sample_batch:
        a = onp.asarray(getattr(a, "_data", a))
        flat = onp.resize(a, (total,) + a.shape[1:])
        lead = ()
        if c.steps_per_call > 1:
            lead += (c.steps_per_call,)
        if c.grad_accum > 1:
            lead += (c.grad_accum,)
        out.append(flat.reshape(lead + (micro if c.grad_accum > 1
                                        else c.batch_size,) + a.shape[1:]))
    return tuple(out)


def _sync(loss):
    return float(onp.asarray(getattr(loss, "_data", loss)))


def _parity_probe(c, fp8_step, block, loss_fn, optimizer, mesh,
                  batch_specs, batch, n_labels, param_specs, dp_axis,
                  steps=2):
    """Run the fp8 candidate and an identically-configured fp32 reference
    a few steps on the SAME batch and compare losses; raises TrialParity
    beyond ``autotune.fp8_parity_tol``.  Doubles as extra fp8 warmup —
    the throughput measurement that follows is unaffected by the probe
    having advanced the trial's (hermetic) weights."""
    from ..parallel.train import ShardedTrainStep
    import jax.numpy as jnp
    tol = float(_config.get("autotune.fp8_parity_tol"))
    ref = ShardedTrainStep(
        block, loss_fn, _clone_optimizer(optimizer), mesh, batch_specs,
        n_labels=n_labels, param_specs=param_specs,
        steps_per_call=c.steps_per_call, zero=c.zero,
        grad_accum=c.grad_accum, remat=c.remat, dp_axis=dp_axis)
    ref.trainable = {n: jnp.copy(v) for n, v in ref.trainable.items()}
    ref.aux = {n: jnp.copy(v) for n, v in ref.aux.items()}
    ref._insight_label = fp8_step._insight_label + ":parity_ref"
    for _ in range(max(1, steps)):
        l8 = fp8_step(*batch)
        lref = ref(*batch)
    l8, lref = _sync(l8), _sync(lref)
    denom = max(abs(lref), 1e-8)
    rel = abs(l8 - lref) / denom
    if not math.isfinite(l8) or rel > tol:
        raise TrialParity(
            f"fp8 parity probe failed for {c!r}: fp8 loss {l8:.6g} vs "
            f"fp32 {lref:.6g} (rel delta {rel:.3g} > tol {tol})")


def _measure_candidate(candidate, block, loss_fn, optimizer, mesh,
                       batch_specs, sample_batch, n_labels, param_specs,
                       dp_axis, trial_seconds, warmup, max_calls=200):
    """One hermetic measured trial -> items/s.  Raises TrialOOM on device
    memory exhaustion (or when the ``autotune.trial_oom`` fault point
    fires — the chaos path CI uses to prove OOM survival)."""
    from ..parallel.mesh import MeshConfig
    from ..parallel.train import ShardedTrainStep
    if _fault._active and _fault.fire("autotune.trial_oom"):
        raise TrialOOM(f"injected OOM for {candidate!r}")
    c = candidate
    batch = _stacked_batch(sample_batch, c)
    if c.mesh is not None:
        # mesh-axis candidate: the trial runs on ITS layout, not the
        # caller's — batch/param specs re-derive from the MeshConfig
        # (megatron tp specs auto-apply inside ShardedTrainStep)
        mesh = MeshConfig(**c.mesh)
        batch_specs = mesh.batch_specs(*[a.ndim for a in sample_batch])
        param_specs = None
        dp_axis = "dp"
    # the precision axis maps onto the training step: "fp8" builds a real
    # fp8 step (delayed scaling state and all), every other value runs
    # the fp32 training path (bf16/int8* are inference-search formats)
    precision = getattr(c, "precision", "fp32")
    step_precision = "fp8" if precision == "fp8" else "fp32"
    step = ShardedTrainStep(
        block, loss_fn, _clone_optimizer(optimizer), mesh, batch_specs,
        n_labels=n_labels, param_specs=param_specs,
        steps_per_call=c.steps_per_call, zero=c.zero,
        grad_accum=c.grad_accum, remat=c.remat, dp_axis=dp_axis,
        precision=step_precision)
    # Hermeticity: the constructor's device_put can ALIAS the block's own
    # param buffers (a same-layout put is a no-op), and the step donates
    # its inputs — without a copy, the first trial call would delete the
    # caller's parameter arrays.  Give the trial its own buffers.
    import jax.numpy as jnp
    step.trainable = {n: jnp.copy(v) for n, v in step.trainable.items()}
    step.aux = {n: jnp.copy(v) for n, v in step.aux.items()}
    # mx.insight attribution label: each measured trial registers its
    # own cost-analysis entry instead of masquerading as the train step
    step._insight_label = (f"autotune.trial[bs{c.batch_size}"
                           f"x{c.steps_per_call},ga{c.grad_accum},"
                           f"zero{c.zero},{step_precision}]")
    if step_precision == "fp8":
        # loss-parity gate BEFORE timing: fp8 may only win a bucket where
        # its loss curve tracks the fp32 reference within
        # autotune.fp8_parity_tol — a fast format with broken numerics
        # must not be selected (raises TrialParity -> status "parity")
        _parity_probe(c, step, block, loss_fn, optimizer, mesh,
                      batch_specs, batch, n_labels, param_specs, dp_axis)
    # first call = trace + compile; account it through the detector so
    # the trial-scoped limit governs it like any hybridized compile
    t0 = time.perf_counter()
    _sync(step(*batch))
    _telemetry.note_compile(block, f"autotune:{type(block).__name__}",
                            time.perf_counter() - t0)
    for _ in range(max(0, warmup - 1)):
        step(*batch)
    t0 = time.perf_counter()
    _sync(step(*batch))
    pilot = max(time.perf_counter() - t0, 1e-6)
    calls = min(max_calls, max(1, math.ceil(trial_seconds / pilot)))
    t0 = time.perf_counter()
    for _ in range(calls):
        loss = step(*batch)
    _sync(loss)  # single host fetch syncs the whole chain
    sec = (time.perf_counter() - t0) / calls
    return c.batch_size * c.steps_per_call / sec


def search(block, loss_fn, optimizer, mesh, batch_specs, sample_batch,
           n_labels=1, space=None, hbm_budget="auto", devices=None,
           measure=None, force=False, persist=True, dp_axis="dp",
           param_specs=None, stats=None, trial_seconds=None, warmup=None,
           flops_per_item=None, act_bytes_per_item=None, max_trials=None):
    """Run the config search; returns a ``SearchResult``.

    block/loss_fn/optimizer/mesh/batch_specs/n_labels/param_specs mirror
    ``ShardedTrainStep`` — every trial builds a real step from them.
    ``sample_batch`` is one representative batch (inputs then labels,
    host arrays); candidates re-shape it to their own geometry.

    The search is hermetic: the block's parameters and the caller's
    optimizer are read, never written.
    """
    from ..optimizer import optimizer as opt_mod
    global _LAST
    t_start = time.perf_counter()
    if isinstance(optimizer, str):
        optimizer = opt_mod.create(optimizer)
    sample_batch = tuple(onp.asarray(getattr(b, "_data", b))
                         for b in sample_batch)
    if not sample_batch:
        raise MXNetError("autotune.search needs a non-empty sample_batch")
    dp = int(mesh.shape.get(dp_axis, 1))
    if space is None:
        space = SearchSpace.default(int(sample_batch[0].shape[0]))
    default = space.default_candidate()

    import jax
    device_kind = getattr(jax.devices()[0], "device_kind", "cpu")
    fp = model_fingerprint(block, loss_fn, optimizer)
    # the mesh shape keys the winner — a layout tuned on dp2xtp2 never
    # loads on dp4 (mesh-axis searches store the winning layout in the
    # record's config["mesh"])
    key = winner_key(fp, device_kind, dp, mesh=dict(mesh.shape))
    path = winners_path()

    candidates = space.candidates()
    n_candidates = len(candidates)

    if persist and not force:
        rec = load_winner(key, path)
        if rec is not None:
            _telemetry.inc("autotune.cache_hits_total")
            best = TrialResult(Candidate.from_config(rec["config"]),
                               items_per_s=rec.get("items_per_s"),
                               status="cached")
            dflt = TrialResult(default,
                               items_per_s=rec.get("default_items_per_s"),
                               status="cached")
            result = SearchResult(key, path, n_candidates, [], [], best,
                                  dflt, reused=True,
                                  wall_s=time.perf_counter() - t_start)
            _LAST = result.summary()
            return result

    if hbm_budget == "auto":
        hbm_budget = _hbm_budget(devices)
    if stats is None:
        stats = ModelStats.probe(block, optimizer, sample_batch, dp,
                                 flops_per_item=flops_per_item,
                                 act_bytes_per_item=act_bytes_per_item)
    zero_ok = bool(getattr(type(optimizer), "_zero_partitionable", False))
    model = CostModel(stats, hbm_budget=hbm_budget, zero_ok=zero_ok,
                      max_trials=max_trials)
    keep, pruned = model.plan(candidates, default)

    _telemetry.inc("autotune.candidates_total", n_candidates)
    for _c, reason in pruned:
        _telemetry.inc("autotune.pruned_total", reason=reason)

    if trial_seconds is None:
        trial_seconds = _config.get("autotune.trial_seconds")
    if warmup is None:
        warmup = _config.get("autotune.trial_warmup")

    trials = []
    root = _trace.begin("autotune.search", category="autotune",
                        candidates=n_candidates, kept=len(keep),
                        pruned=len(pruned)) if _trace._active else None
    with trial_compile_scope(block):
        for c in keep:
            t0 = time.perf_counter()
            # trial span carries the candidate config as attrs, so a
            # trace export reads as (config -> measured wall time) pairs
            sp = _trace.begin("autotune.trial", category="autotune",
                             parent=(root.context if root else None),
                             **c.config()) if _trace._active else None
            try:
                if measure is not None:
                    if _fault._active and _fault.fire("autotune.trial_oom"):
                        raise TrialOOM(f"injected OOM for {c!r}")
                    ips = measure(c)
                else:
                    ips = _measure_candidate(
                        c, block, loss_fn, optimizer, mesh, batch_specs,
                        sample_batch, n_labels, param_specs, dp_axis,
                        trial_seconds, warmup)
                trials.append(TrialResult(
                    c, float(ips), "ok", time.perf_counter() - t0))
            except Exception as e:  # a dead candidate must not kill the search
                status = ("oom" if _is_oom(e)
                          else "parity" if isinstance(e, TrialParity)
                          else "error")
                trials.append(TrialResult(
                    c, None, status, time.perf_counter() - t0,
                    error=f"{type(e).__name__}: {e}"[:300]))
                if status == "oom":
                    _telemetry.inc("autotune.trials_oom_total")
                    _fault.record("autotune.trial_oom")
                elif status == "parity":
                    _telemetry.inc("autotune.trials_parity_total")
            if sp is not None:
                last = trials[-1]
                sp.end(status=last.status,
                       items_per_s=(last.items_per_s or 0.0))
            _telemetry.inc("autotune.trials_total")
    if root is not None:
        root.end(trials=len(trials))

    ok = [t for t in trials if t.status == "ok"]
    best = max(ok, key=lambda t: t.items_per_s) if ok else None
    dflt = next((t for t in trials if t.candidate == default), None)
    wall_s = time.perf_counter() - t_start
    result = SearchResult(key, path, n_candidates, trials, pruned, best,
                          dflt, wall_s=wall_s, hbm_budget=hbm_budget)
    _telemetry.observe("autotune.search_seconds", wall_s)
    if result.speedup is not None:
        _telemetry.set_gauge("autotune.best_speedup", result.speedup)
    if persist and best is not None:
        rec = {"config": best.candidate.config(),
               "items_per_s": best.items_per_s,
               "default_items_per_s":
                   dflt.items_per_s if dflt else None,
               "speedup_vs_default": result.speedup,
               "device_kind": device_kind, "dp": dp,
               "fingerprint": fp, "created": time.time()}
        save_winner(key, rec, path)
    _LAST = result.summary()
    return result


def _host_batch(batches, batch=None):
    """One batch as host numpy: ``batch`` itself, or ONE borrowed from the
    loader ``batches`` and released via ``pipeline.take``."""
    if batch is None:
        from .. import pipeline as _pipeline
        batch = next(iter(_pipeline.take(batches, 1)), None)
        if batch is None:
            raise MXNetError("autotune: batches yielded no batch")
    return tuple(onp.asarray(getattr(b, "_data", b)) for b in batch)


def tune_step(step, batches=None, sample_batch=None, space=None, **kw):
    """Search the step-config grid around a ``ShardedTrainStep``'s model,
    loss, optimizer and mesh (:func:`search`) and return
    ``(tuned_step, result)``.

    ``batches`` lends ONE sample batch (shaped like the step's per-update
    batch, no lead axes) and is released via ``pipeline.take``; pass
    ``sample_batch=`` to skip the loader.  Current weights sync to the
    block first so trials — and the returned tuned step — start from the
    step's training state.  The tuned step reuses the caller's optimizer
    (schedule position included); trials only ever run on hermetic
    clones.  Keyword args flow to :func:`search` (hbm_budget=, force=,
    ...).
    """
    from ..parallel.mesh import MeshConfig
    from ..parallel.train import ShardedTrainStep
    if sample_batch is None and batches is None:
        raise MXNetError(
            "autotune needs `batches` (a loader to borrow one batch from) "
            "or an explicit `sample_batch`")
    sample = _host_batch(batches, sample_batch)
    step.sync_to_block()
    result = search(
        step.block, step.loss_fn, step.fopt.opt, step.mesh,
        step.batch_specs, sample, n_labels=step.n_labels,
        param_specs=step.param_specs, dp_axis=step.dp_axis,
        space=space, **kw)
    cfg = result.config
    if cfg is None:  # every trial failed: keep the caller's config
        return step, result
    mesh = step.mesh_config or step.mesh
    batch_specs, param_specs, dp_axis = (
        step.batch_specs, step.param_specs, step.dp_axis)
    if cfg.get("mesh"):
        # a mesh-axis search won on a different layout: rebuild the
        # step around the winning MeshConfig (specs re-derive)
        mesh = MeshConfig(**cfg["mesh"])
        batch_specs = mesh.batch_specs(
            *[len(s) if s is not None else 2 for s in step.batch_specs])
        param_specs = None
        dp_axis = "dp"
    precision = cfg.get("precision", "fp32")
    tuned = ShardedTrainStep(
        step.block, step.loss_fn, step.fopt.opt, mesh,
        batch_specs, n_labels=step.n_labels,
        param_specs=param_specs,
        steps_per_call=cfg["steps_per_call"], zero=cfg["zero"],
        grad_accum=cfg["grad_accum"], remat=cfg["remat"],
        dp_axis=dp_axis,
        precision=precision if precision in ("fp32", "fp8")
        else step.precision,
        grad_compress=step._compress)
    tuned._n_step = step._n_step
    return tuned, result


def tune_estimator(estimator, train_data, space=None, apply=True, **kw):
    """`estimator.fit(autotune=True)` backend: search around the
    estimator's net/loss/optimizer using one batch drawn from the loader
    (batch size stays the loader's — the loader owns it), then apply what
    an eager fit can use: the winning remat policy (re-hybridize) and
    prefetch depth (``pipeline.prefetch_depth`` knob).  The full result
    lands on ``estimator.autotune_result`` so a ShardedTrainStep caller
    can lift the rest (zero/grad_accum/steps_per_call)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import make_mesh

    arrs = _host_batch(train_data)
    b0 = int(arrs[0].shape[0])
    ndev = len(jax.devices())
    dp = ndev if b0 % ndev == 0 else 1
    mesh = make_mesh({"dp": dp})
    specs = tuple(P("dp") for _ in arrs)

    net, loss = estimator.net, estimator.loss

    def loss_fn(out, *labels):
        import jax.numpy as jnp
        from ..numpy.multiarray import _wrap
        val = loss(_wrap(out), *[_wrap(x) for x in labels])
        return jnp.mean(getattr(val, "_data", val))

    if space is None:
        space = SearchSpace(batch_size=b0)
    result = search(net, loss_fn, estimator.trainer.optimizer, mesh, specs,
                    arrs, n_labels=len(arrs) - 1, space=space, **kw)
    cfg = result.config
    if apply and cfg:
        if cfg.get("prefetch_depth") is not None:
            _config.set("pipeline.prefetch_depth", cfg["prefetch_depth"])
        if cfg.get("remat") and hasattr(net, "hybridize"):
            try:
                net.hybridize(remat=cfg["remat"])
            except Exception:
                pass  # non-hybridizable net: the knob has no eager analog
    estimator.autotune_result = result
    return result
