"""Analytic cost model: prune the candidate grid before anything compiles.

Two estimates per candidate, both cheap closed forms over quantities
probed once from the model (no tracing, no compilation):

- **HBM bytes per device** — params + gradients (1/dp at zero>=2) +
  optimizer state (1/dp at zero>=1, slot count probed from the real
  ``create_state``) + live activations scaled by the remat policy's
  keep-fraction + staged input batches times the prefetch depth.
  Candidates whose estimate exceeds the budget are rejected with reason
  ``"hbm"`` — the budget itself comes from the ``memory.*`` gauges
  (PJRT ``memory_stats``), see search.py.
- **Relative compute cost per item** — logical batch FLOPs times the
  remat policy's recompute factor, plus the ZeRO collective and
  grad-accum loop penalties, plus per-launch dispatch overhead amortized
  over ``batch * steps_per_call`` items.

Pruning is dominance, not prediction: within a group of candidates that
differ only in the *memory* knobs (zero level, grad_accum, remat), every
knob strictly costs compute — so whenever the cheapest-compute member
fits the budget, the rest of the group is ``"dominated"`` and never
measured.  With 3 zero levels x 2 grad_accum x 3 remat policies per
group this alone rejects 17/18 of the grid, which is how the tuner hits
the >=50%-pruned-without-compiling target even when no budget is known
(CPU CI, where ``memory_stats`` is empty).
"""
from __future__ import annotations

import numpy as onp

from .. import config as _config
from ..base import MXNetError
from .space import Candidate

__all__ = ["ModelStats", "CostModel", "REMAT_MEM_FRACTION",
           "REMAT_FLOPS_FACTOR", "PRECISION_COMPUTE_FACTOR",
           "VMEM_BYTES", "VMEM_FRACTION", "kernel_tile_bytes",
           "kernel_cost"]

#: fraction of peak live activation bytes kept under each remat policy
#: (full remat keeps only layer inputs; 'dots' keeps matmul outputs)
REMAT_MEM_FRACTION = {False: 1.0, "dots": 0.45, True: 0.18}
#: recompute multiplier on fwd+bwd FLOPs (full remat replays the forward:
#: 4 passes instead of 3 -> 4/3)
REMAT_FLOPS_FACTOR = {False: 1.0, "dots": 1.15, True: 4.0 / 3.0}

#: compute penalties for the memory knobs (relative, used only to order
#: candidates inside a dominance group — never to predict wall time)
_ZERO_PENALTY = 0.05        # all-gather/reduce-scatter per update
_ACCUM_PENALTY = 0.02       # scan-carry overhead per extra microbatch

#: relative time-per-flop by precision: MXU peak ratios (bf16 2x fp32,
#: int8/fp8 2x bf16 on generations that rate them — bench.py
#: PEAK_INT8_FACTOR carries the per-chip truth; this table only orders
#: candidates). Weight-only modes move bytes, not flops: the matmuls
#: still run in the activation dtype, so they rank as bf16-ish.
PRECISION_COMPUTE_FACTOR = {
    "fp32": 1.0, "bf16": 0.5, "int8": 0.25, "fp8": 0.25,
    "int8_weights": 0.5, "int4_weights": 0.5,
}


def _state_slots(optimizer, dtype):
    """Probe how many bytes of optimizer state one parameter element
    costs by asking the real ``create_state`` for a tiny weight."""
    from ..numpy.multiarray import _wrap
    import jax
    import jax.numpy as jnp
    try:
        s = optimizer.create_state(
            "autotune_probe", _wrap(jnp.zeros((8,), dtype)))
        leaves = [l for l in jax.tree_util.tree_leaves(s) if l is not None]
        return sum(jnp.dtype(getattr(l, "dtype", jnp.float32)).itemsize
                   for l in leaves)
    except Exception:
        return 8  # adam-class fallback: two fp32 slots


class ModelStats:
    """Per-model quantities the cost model runs on.  ``probe`` derives
    them from the live block/optimizer; tests construct directly."""

    def __init__(self, param_count, param_bytes, state_bytes, dp,
                 flops_per_item=None, act_bytes_per_item=None,
                 sample_item_bytes=0):
        self.param_count = int(param_count)
        self.param_bytes = int(param_bytes)
        self.state_bytes = int(state_bytes)
        self.dp = max(1, int(dp))
        # 6ND rule: fwd + 2x bwd over every weight, per sample
        self.flops_per_item = (float(flops_per_item) if flops_per_item
                               else 6.0 * self.param_count)
        if act_bytes_per_item is None:
            # crude proxy when the caller has no profile: activations per
            # sample scale with the input sample plus a slice of the
            # weights touched per layer.  Only relative accuracy matters —
            # real OOMs are still caught per-trial by the search loop.
            act_bytes_per_item = 8 * sample_item_bytes + param_bytes // 64
        self.act_bytes_per_item = int(act_bytes_per_item)
        self.sample_item_bytes = int(sample_item_bytes)

    @classmethod
    def probe(cls, block, optimizer, sample_batch, dp,
              flops_per_item=None, act_bytes_per_item=None):
        from .. import functional
        trainable, _aux = functional.split_params(block)
        param_count = sum(int(onp.prod(v.shape) or 1)
                          for v in trainable.values())
        param_bytes = sum(
            int(onp.prod(v.shape) or 1) * onp.dtype(v.dtype).itemsize
            for v in trainable.values())
        first = next(iter(trainable.values()), None)
        dtype = getattr(first, "dtype", onp.float32)
        state_bytes = param_count * _state_slots(optimizer, dtype)
        sample_item_bytes = 0
        for a in sample_batch:
            a = onp.asarray(getattr(a, "_data", a))
            n = int(onp.prod(a.shape[1:]) or 1)  # per-sample, batch axis off
            sample_item_bytes += n * a.dtype.itemsize
        return cls(param_count, param_bytes, state_bytes, dp,
                   flops_per_item=flops_per_item,
                   act_bytes_per_item=act_bytes_per_item,
                   sample_item_bytes=sample_item_bytes)


class CostModel:
    """Prunes a candidate grid down to the points worth a measured trial."""

    def __init__(self, stats, hbm_budget=None, zero_ok=True,
                 launch_overhead_items=None, max_trials=None):
        self.stats = stats
        self.hbm_budget = hbm_budget
        self.zero_ok = zero_ok
        self.launch_overhead_items = (
            _config.get("autotune.launch_overhead_items")
            if launch_overhead_items is None else launch_overhead_items)
        self.max_trials = (_config.get("autotune.max_trials")
                           if max_trials is None else max_trials)

    # -- per-candidate estimates ------------------------------------------
    def hbm_bytes(self, c):
        """Estimated peak HBM bytes per device for candidate ``c``."""
        st = self.stats
        dp = st.dp
        params = st.param_bytes
        grads = st.param_bytes // (dp if c.zero >= 2 else 1)
        state = st.state_bytes // (dp if c.zero >= 1 else 1)
        micro = max(1, c.batch_size // max(1, c.grad_accum))
        acts = int(st.act_bytes_per_item * micro / dp
                   * REMAT_MEM_FRACTION.get(c.remat, 1.0))
        staged = (st.sample_item_bytes * c.batch_size * c.steps_per_call
                  // dp)
        inputs = staged * (1 + max(0, c.prefetch_depth or 0))
        return params + grads + state + acts + inputs

    def compute_cost(self, c):
        """Relative time per item — orders candidates inside a dominance
        group; the memory knobs only ever add cost."""
        st = self.stats
        f = st.flops_per_item * REMAT_FLOPS_FACTOR.get(c.remat, 1.0)
        f *= PRECISION_COMPUTE_FACTOR.get(
            getattr(c, "precision", "fp32"), 1.0)
        if c.zero and st.dp > 1:
            f *= 1.0 + _ZERO_PENALTY
        f *= 1.0 + _ACCUM_PENALTY * (c.grad_accum - 1)
        overhead = (self.launch_overhead_items * st.flops_per_item
                    / max(1, c.batch_size * c.steps_per_call))
        return f + overhead

    def fits(self, c):
        return self.hbm_budget is None or self.hbm_bytes(c) <= self.hbm_budget

    def invalid_reason(self, c):
        st = self.stats
        if c.batch_size < 1 or c.steps_per_call < 1 or c.grad_accum < 1:
            return "invalid"
        if c.batch_size % c.grad_accum:
            return "invalid"            # microbatch must be whole
        if (c.batch_size // c.grad_accum) % st.dp:
            return "invalid"            # microbatch must shard over dp
        if c.zero and st.dp == 1:
            return "dominated"          # nothing to shard, pure overhead
        if c.zero and not self.zero_ok:
            return "invalid"            # optimizer not ZeRO-partitionable
        return None

    # -- grid -> trial plan -----------------------------------------------
    def plan(self, candidates, default=None):
        """Split the grid into (keep, pruned).

        ``keep`` is the measured-trial list (predicted-best first);
        ``pruned`` is ``[(candidate, reason)]`` with reasons ``invalid``,
        ``dominated``, ``hbm`` or ``ranked_out``.  ``default`` (when in
        the grid) is always kept so the best-vs-default speedup has a
        measured baseline.
        """
        keep, pruned = [], []
        groups = {}
        for c in candidates:
            reason = self.invalid_reason(c)
            if reason is not None and c != default:
                pruned.append((c, reason))
                continue
            # precision is in the group key: a cheaper format is not a
            # dominance win over a slower one (different numerics), so
            # formats are only ever compared by measured trials
            groups.setdefault(
                (c.batch_size, c.steps_per_call, c.prefetch_depth,
                 getattr(c, "precision", "fp32")),
                []).append(c)
        for members in groups.values():
            fitting = [c for c in members if self.fits(c)]
            best = min(fitting, key=self.compute_cost) if fitting else None
            for c in members:
                if c is best or c == default:
                    keep.append(c)
                elif not self.fits(c):
                    pruned.append((c, "hbm"))
                else:
                    pruned.append((c, "dominated"))
        keep.sort(key=self.compute_cost)
        limit = self.max_trials
        if limit and len(keep) > limit:
            ranked, extra = keep[:limit], keep[limit:]
            if default is not None and default in extra:
                # the default always gets a measured baseline: it replaces
                # the worst-predicted ranked member so the cap holds
                extra.remove(default)
                if ranked:
                    extra.append(ranked.pop())
                ranked.append(default)
            pruned.extend((c, "ranked_out") for c in extra)
            keep = ranked
        return keep, pruned


# ---------------------------------------------------------------------------
# kernel-level analytics (kernels.py): VMEM footprint + relative tile cost
# ---------------------------------------------------------------------------

#: per-core VMEM capacity the tile footprint must fit (TPU v4/v5/v6 all
#: carry ~16 MB; the interpreter has no real limit but honoring it keeps
#: CPU-CI pruning representative)
VMEM_BYTES = 16 * 2 ** 20
#: fraction of VMEM the tuner budgets for one kernel's resident tiles
#: (the rest is Mosaic's: double-buffered DMA staging, scratch, spills)
VMEM_FRACTION = 0.5


def kernel_tile_bytes(kernel, bucket, blocks):
    """Estimated VMEM bytes resident for one grid step of ``kernel`` at
    ``blocks`` on a ``bucket``-shaped problem — the kernel tuner's
    pre-compile OOM guard (prune reason ``"vmem"``)."""
    b = dict(blocks)
    if kernel in ("flash_attention", "flash_attention_bwd"):
        sq, sk, d = bucket
        d = max(128, int(d))  # head_dim zero-pads to the lane width
        bq = min(int(b["block_q"]), int(sq))
        bk = min(int(b["block_k"]), int(sk))
        if kernel == "flash_attention":
            # q and o blocks, the (d, bq) accumulator, K and V whole (the
            # forward loops over k-blocks inside one grid step), the
            # (bk, bq) scores and their exponentials
            tiles = 3 * bq * d + 2 * int(sk) * d + 2 * bq * bk
        else:
            # the larger of the two kernels on each term: q, do and the
            # dq block with its fp32 scratch; k, v and the dk, dv blocks
            # with theirs; sT, pT, dpT, dsT; the lse and delta rows
            tiles = 4 * bq * d + 6 * bk * d + 4 * bq * bk + 2 * bq
        return 4 * tiles
    if kernel in ("quantized_matmul", "fp8_matmul"):
        m, n, k = bucket
        bm = min(int(b["block_m"]), int(m))
        bn = min(int(b["block_n"]), int(n))
        kp = max(128, int(k))
        # one (bm, K) activation tile (fp32 in + int8/fp8 quantized copy),
        # one (bn, K) low-bit weight tile, the fp32 (bm, bn) output tile
        return 5 * bm * kp + bn * kp + 4 * bm * bn
    if kernel == "ln_residual":
        rows, dim = bucket
        br = min(int(b["block_rows"]), max(8, int(rows)))
        # x/h/mask/out tiles plus fp32 row stats
        return 4 * (4 * br * dim + 2 * br)
    raise MXNetError(f"kernel_tile_bytes: unknown kernel {kernel!r}")


def _flash_cost(kernel, bucket, b, launch):
    """The flash kernels as they tile a causal call (what the trials
    run): a tile above the diagonal is skipped — a backward grid step
    that skips still pays its launch, the forward's k-loop never visits
    it — and a tile the diagonal crosses costs a whole tile (its mask
    did not show on the chip).  A tile's work is one pass of
    ``bq x bk x d`` per matrix product (2 forward; 4 in dK/dV + 3 in dQ
    backward); a forward k-loop trip costs what a grid step does.  The
    weights are the v5e's block sweep at (1024, 1024, 64) (PERF.md,
    PR 26): ~0.27 us a step against ~0.6 us a pass of a 512 x 512 x 128
    tile; rank correlation with that sweep 0.85 forward, 0.98 backward.
    No other chip was at hand: every device family ranks by the v5e's
    ratio of a step to a pass, unmeasured there, until its own sweep
    says otherwise (the search times what this ranking lets through).
    The bytes a step streams ride under its compute: with dense
    statistics and bf16 gradients the kernels are bound by their tiles,
    not by HBM."""
    from ..ops.pallas.flash_attention import tile_counts
    sq, sk, d = (int(x) for x in bucket)
    bq, bk = min(int(b["block_q"]), sq), min(int(b["block_k"]), sk)
    c = tile_counts(sq, sk, bq, bk, True)
    work = c["computed"] + c["masked"]
    per_pass = bq * bk * max(128, d) / 2 ** 25
    if kernel == "flash_attention":
        return (-(-sq // bq) + work) * launch + 2 * per_pass * work
    return 2 * (work + c["skipped"]) * launch + 7 * per_pass * work


def kernel_cost(kernel, bucket, blocks):
    """Relative analytic cost of ``blocks`` on a ``bucket``-shaped
    problem: per-tile work plus a fixed launch overhead per grid step,
    plus an MXU/VPU under-utilization penalty for tiles below the native
    (8/32 x 128) shape.  Only the ORDER matters — this is the ranking
    the learned model (learned.py) must beat on Spearman correlation to
    replace it."""
    b = dict(blocks)
    launch = 1.0   # relative dispatch cost per grid step

    def _grid_and_util(sizes, tiles, aligns):
        steps, util = 1.0, 1.0
        for size, tile, align in zip(sizes, tiles, aligns):
            size = max(1, int(size))
            tile = max(1, min(int(tile), size))
            steps *= -(-size // tile)          # ceil-div grid steps
            util *= min(1.0, tile / align)     # sub-native-tile penalty
        return steps, util

    if kernel in ("flash_attention", "flash_attention_bwd"):
        return _flash_cost(kernel, bucket, b, launch)
    if kernel in ("quantized_matmul", "fp8_matmul"):
        m, n, k = bucket
        steps, util = _grid_and_util((m, n), (b["block_m"], b["block_n"]),
                                     (256, 256))
        work = (min(b["block_m"], m) * min(b["block_n"], n)
                * max(128, k)) / 2 ** 20
        passes = 1.0
    elif kernel == "ln_residual":
        rows, dim = bucket
        steps, util = _grid_and_util((rows,), (b["block_rows"],), (256,))
        work = (min(b["block_rows"], rows) * dim) / 2 ** 17
        passes = 1.0
    else:
        raise MXNetError(f"kernel_cost: unknown kernel {kernel!r}")
    return passes * steps * (launch + work / util)
