"""mx.autotune — measured config search for the compiled training step.

Reference parity: none (the reference tunes by hand-edited perf.md
tables).  On a compiler-backed stack the throughput of one model is a
function of a small discrete config — ``{batch_size, steps_per_call,
grad_accum, zero, remat, prefetch_depth}`` — and the honest way to pick
it is TVM-style (arxiv 1802.04799): an analytic cost model prunes the
grid, short measured trials of the *real* compiled step rank the
survivors, and the winner persists next to the XLA compile cache so the
next run starts tuned with zero trials.

Three surfaces::

    # training-step API
    tuned_step, result = mx.autotune.tune_step(step, loader)

    # estimator API
    est.fit(train_data, epochs=2, autotune=True)

    # CLI
    JAX_PLATFORMS=cpu python tools/autotune.py --model mlp --assert

A second tier tunes the layer BELOW the step: kernels.py searches the
Pallas block/grid shapes every TPU kernel hard-codes (flash attention
q/k tiles, the int8/fp8 matmul m/n tiles, the ln_residual row tile),
ranked by a learned cost model (learned.py, fed by persisted trials and
fleet telemetry run reports) when it out-ranks the closed form, and
re-tuned online when mx.insight flags step-time drift::

    # kernel-level API (winners share winners.json, schema 2)
    mx.autotune.search_kernels()
    mx.autotune.resolve_blocks("flash_attention", (sq, sk, d))

    # CLI
    JAX_PLATFORMS=cpu python tools/autotune.py --kernels --assert

See docs/PERFORMANCE.md ("Autotuning the compiled step").
"""
from __future__ import annotations

from .cost import (CostModel, ModelStats, REMAT_FLOPS_FACTOR,
                   REMAT_MEM_FRACTION, VMEM_BYTES, kernel_cost,
                   kernel_tile_bytes)
from .kernels import (KERNELS, KernelSearchResult, Retuner,
                      kernel_candidates, kernel_config_summary, load_tuned,
                      resolve_blocks, search_kernels, shape_bucket,
                      static_blocks)
from .learned import (LearnedCostModel, load_telemetry_records, rank_gate,
                      spearman)
from .persist import (cache_dir, kernel_key, load_trials, load_winner,
                      model_fingerprint, save_winner, winner_key,
                      winners_path)
from .search import (SearchResult, TrialOOM, TrialParity, TrialResult,
                     last_summary, search, trial_compile_scope,
                     tune_estimator, tune_step)
from .space import Candidate, SearchSpace

__all__ = [
    "Candidate", "SearchSpace", "CostModel", "ModelStats",
    "REMAT_MEM_FRACTION", "REMAT_FLOPS_FACTOR",
    "SearchResult", "TrialResult", "TrialOOM", "TrialParity",
    "search", "tune_estimator", "tune_step", "trial_compile_scope",
    "last_summary",
    "cache_dir", "winners_path", "model_fingerprint", "winner_key",
    "load_winner", "save_winner",
    "KERNELS", "KernelSearchResult", "Retuner", "kernel_candidates",
    "kernel_config_summary", "load_tuned", "resolve_blocks",
    "search_kernels", "shape_bucket", "static_blocks",
    "kernel_key", "load_trials", "kernel_cost", "kernel_tile_bytes",
    "VMEM_BYTES", "LearnedCostModel", "rank_gate", "spearman",
    "load_telemetry_records",
]
