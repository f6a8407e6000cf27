#!/usr/bin/env python
"""ZeRO optimizer-state memory benchmark (CI `zero` stage).

Contract from docs/PERFORMANCE.md: on a >=4-way dp mesh, ``zero=1`` must
cut the PER-DEVICE optimizer-state footprint by at least ``--reduction``
(default 40%) versus the replicated baseline, while staying numerically
invisible (the loss oracle below; the exhaustive parity suite is
tests/test_zero.py).  Adam holds two fp32 slots per parameter, so an
ideal 4-way partition saves 75% — the 40% bar leaves room for padding
and non-partitionable (tp/ep-sharded) leftovers.

Bytes are measured from the arrays themselves: every optimizer-state
leaf's ``addressable_shards`` filtered to one device, so the number is
what the placement actually costs, not an estimate.  The ``memory.*``
telemetry plane (PJRT allocator live/peak) is reported alongside when
the backend provides it; the CPU backend used in CI has no allocator
stats, so that section prints n/a there and lights up on real TPUs.

This is a CPU gate: the ``setdefault("JAX_PLATFORMS", "cpu")`` below puts
it on the virtual CPU mesh unless the caller names another platform; it
counts bytes, it measures no device.

Usage: python benchmark/zero_memory.py [--reduction 0.4] [--dp 4]
           [--steps 2] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

IN_UNITS = 1024
UNITS = 2048
BATCH = 16


def _make_step(zero, dp, tp=1):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import MeshConfig, make_mesh
    from mxnet_tpu.parallel.train import ShardedTrainStep

    mx.random.seed(7)
    net = nn.Dense(UNITS, in_units=IN_UNITS)
    net.initialize()

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    if tp > 1:
        # ZeRO x TP: the weight is column-parallel over tp; zero=1 then
        # partitions the state's replicated in_units dim over dp
        cfg = MeshConfig(dp=dp, tp=tp)
        return ShardedTrainStep(
            net, loss_fn, mx.optimizer.create("adam", learning_rate=0.01),
            cfg, batch_specs=(P("dp"), P("dp")), n_labels=1, zero=zero,
            param_specs={"weight": P("tp", None), "bias": P("tp")})
    return ShardedTrainStep(
        net, loss_fn, mx.optimizer.create("adam", learning_rate=0.01),
        make_mesh({"dp": dp}), batch_specs=(P("dp"), P("dp")),
        n_labels=1, zero=zero)


def _state_bytes_on(step, device):
    """Optimizer-state bytes actually resident on ``device``."""
    import jax
    total = 0
    for s in step.states.values():
        for leaf in jax.tree_util.tree_leaves(s):
            for shard in leaf.addressable_shards:
                if shard.device == device:
                    total += shard.data.nbytes
    return total


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduction", type=float, default=0.40,
                    help="minimum per-device state-bytes cut (fraction)")
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--tp", type=int, default=2,
                    help="tp size for the ZeRO x TP section (skipped when "
                         "dp*tp exceeds the device count)")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    import numpy as onp
    import jax
    from mxnet_tpu import telemetry

    if len(jax.devices()) < args.dp:
        print(f"SKIP: needs {args.dp} devices, have {len(jax.devices())}")
        return 0

    rs = onp.random.RandomState(0)
    x = rs.randn(BATCH, IN_UNITS).astype("float32")
    y = rs.randint(0, UNITS, (BATCH,)).astype("int32")

    telemetry.enable()
    telemetry.reset()
    dev0 = jax.devices()[0]
    results = {}
    for zero in (0, 1):
        step = _make_step(zero, args.dp)
        losses = [float(step(x, y).asnumpy()) for _ in range(args.steps)]
        results[zero] = {
            "state_bytes_per_device": _state_bytes_on(step, dev0),
            "losses": losses,
        }

    # ZeRO x TP: same gate on a dp x tp mesh (needs dp*tp devices) — the
    # tensor-sharded weight's state partitions its replicated sub-axis
    tp = args.tp if len(jax.devices()) >= args.dp * args.tp else 1
    results_tp = {}
    if tp > 1:
        for zero in (0, 1):
            step = _make_step(zero, args.dp, tp=tp)
            losses = [float(step(x, y).asnumpy())
                      for _ in range(args.steps)]
            results_tp[zero] = {
                "state_bytes_per_device": _state_bytes_on(step, dev0),
                "losses": losses,
            }
    mem = telemetry.record_memory()
    telemetry.disable()

    repl = results[0]["state_bytes_per_device"]
    shard = results[1]["state_bytes_per_device"]
    reduction = 1.0 - shard / repl
    # the optimization must be numerically invisible, not just smaller
    onp.testing.assert_allclose(results[1]["losses"], results[0]["losses"],
                                rtol=1e-5, atol=1e-6)

    report = {
        "dp": args.dp,
        "replicated_state_bytes_per_device": repl,
        "zero1_state_bytes_per_device": shard,
        "reduction": reduction,
        "required_reduction": args.reduction,
        "memory_stats": mem or None,
    }
    if results_tp:
        repl_tp = results_tp[0]["state_bytes_per_device"]
        shard_tp = results_tp[1]["state_bytes_per_device"]
        reduction_tp = 1.0 - shard_tp / repl_tp
        onp.testing.assert_allclose(results_tp[1]["losses"],
                                    results_tp[0]["losses"],
                                    rtol=1e-5, atol=1e-6)
        report["zero_tp"] = {
            "dp": args.dp, "tp": tp,
            "replicated_state_bytes_per_device": repl_tp,
            "zero1_state_bytes_per_device": shard_tp,
            "reduction": reduction_tp,
        }
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(f"dp={args.dp}  optimizer-state bytes/device: "
              f"replicated={repl:,}  zero=1 {shard:,}  "
              f"(-{reduction:.1%}, bar {args.reduction:.0%})")
        if results_tp:
            print(f"dp={args.dp} tp={tp} (ZeRO x TP)  state bytes/device: "
                  f"zero=0 {repl_tp:,}  zero=1 {shard_tp:,}  "
                  f"(-{reduction_tp:.1%}, bar {args.reduction:.0%})")
        print("memory.* (PJRT): "
              + (json.dumps(mem) if mem else "n/a on this backend"))

    if reduction < args.reduction:
        print(f"FAIL: reduction {reduction:.1%} < required "
              f"{args.reduction:.0%}")
        return 1
    if results_tp and reduction_tp < args.reduction:
        print(f"FAIL: ZeRO x TP reduction {reduction_tp:.1%} < required "
              f"{args.reduction:.0%}")
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
