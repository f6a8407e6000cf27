#!/usr/bin/env python
"""fp8 training + compressed-collective benchmark gate (CI `fp8` stage).

Contract from ISSUE 20 / docs/PRECISION.md, on a >=4-way dp mesh:

1. Loss-curve parity: a GPT-class step trained with ``precision="fp8"``
   (e4m3 fwd / e5m2 bwd, delayed scaling) plus int8 error-feedback
   gradient compression must track the fp32 reference loss curve within
   ``--parity-tol`` relative after ``--steps`` identical batches.
2. Zero post-warmup recompiles: the overlapped fp8+compressed step must
   stay ONE executable after its first call (delayed scaling keeps every
   scale a traced scalar — nothing retriggers tracing).
3. Checkpoint round-trip: amax histories + EF residuals survive
   save_states/load_states bitwise (the dp-resize elastic test lives in
   tests/test_fp8.py; this gate covers the same-layout path end-to-end).
4. MFU floor (``--mfu``, default 0.45): asserted only on accelerators —
   the CPU emulation backend has no meaningful MXU peak, so CI prints
   the measured value and skips the floor there.

This is a CPU gate: the ``setdefault("JAX_PLATFORMS", "cpu")`` below puts
it on the virtual CPU mesh unless the caller names another platform, so
its timings are never device speed and item 4 has never executed
(ROADMAP S9).  What the compressed reduce puts on the wire is held by the
lowered program (tests/test_fp8.py), not by a counter here.

Usage: python benchmark/fp8_train.py [--dp 4] [--steps 6]
           [--parity-tol 0.05] [--mfu 0.45] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB = 1000
UNITS = 64
LAYERS = 2
HEADS = 4
SEQ = 32
BATCH = 8


def _make_step(precision, compress, dp):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
    from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

    mx.random.seed(7)
    net = GPTForCausalLM(vocab_size=VOCAB, units=UNITS,
                         hidden_size=UNITS * 4, num_layers=LAYERS,
                         num_heads=HEADS, max_length=SEQ,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((2, SEQ), dtype="int32"))

    def loss_fn(logits, labels):
        from mxnet_tpu.ops.xent import sparse_softmax_xent
        return jnp.mean(sparse_softmax_xent(logits, labels))

    cfg = MeshConfig(dp=dp)
    step = ShardedTrainStep(
        net, loss_fn, mx.optimizer.create("adam", learning_rate=1e-3),
        cfg, batch_specs=cfg.batch_specs(2, 2), n_labels=1,
        precision=precision, grad_compress=compress)
    n_params = sum(int(v.size) for v in step.trainable.values())
    return step, n_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--parity-tol", type=float, default=0.05,
                    help="max relative loss delta vs the fp32 reference")
    ap.add_argument("--mfu", type=float, default=0.45,
                    help="MFU floor (asserted on accelerators only)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    import numpy as onp
    import jax
    from mxnet_tpu import telemetry

    if len(jax.devices()) < args.dp:
        print(f"SKIP: needs {args.dp} devices, have {len(jax.devices())}")
        return 0
    on_cpu = jax.devices()[0].platform == "cpu"

    rs = onp.random.RandomState(0)
    x = rs.randint(0, VOCAB, (BATCH, SEQ)).astype("int32")
    y = rs.randint(0, VOCAB, (BATCH, SEQ)).astype("int32")

    step8, n_params = _make_step("fp8", "int8", args.dp)
    stepref, _ = _make_step("fp32", "none", args.dp)

    # -- 1. loss-curve parity over identical batches --------------------
    l8 = lref = None
    for _ in range(args.steps):
        l8 = step8(x, y)
        lref = stepref(x, y)
    l8, lref = float(l8.asnumpy()), float(lref.asnumpy())
    parity = abs(l8 - lref) / max(abs(lref), 1e-8)

    # -- 2+4. recompiles + throughput on the fp8 step -------------------
    telemetry.enable()
    telemetry.reset()
    compiles_before = telemetry.counters(
        prefix="compile.", aggregate=True)
    k = max(3, args.steps)
    t0 = time.perf_counter()
    for _ in range(k):
        loss = step8(x, y)
    float(loss.asnumpy())
    sec = (time.perf_counter() - t0) / k
    compiles_after = telemetry.counters(prefix="compile.", aggregate=True)
    telemetry.disable()

    recompiles = sum(compiles_after.values()) - sum(compiles_before.values())

    # -- 3. checkpoint round-trip (same layout) ---------------------------
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "fp8.safetensors")
        step8.save_states(path)
        before = {
            f"fp8/{s}/{kk}": onp.asarray(v)
            for s, h in step8.extra["fp8"].items() for kk, v in h.items()}
        before.update({f"efresid/{n}": onp.asarray(v).sum(axis=0)
                       for n, v in step8.extra["resid"].items()})
        step8.load_states(path)
        after = {
            f"fp8/{s}/{kk}": onp.asarray(v)
            for s, h in step8.extra["fp8"].items() for kk, v in h.items()}
        after.update({f"efresid/{n}": onp.asarray(v).sum(axis=0)
                      for n, v in step8.extra["resid"].items()})
        ckpt_ok = all(onp.array_equal(before[kk], after[kk]) for kk in before)

    flops = 6.0 * n_params * BATCH * SEQ
    peak = None
    mfu = None
    if not on_cpu:
        from mxnet_tpu import insight
        peak = insight.peaks()[0]      # unknown device kind: raises
        mfu = flops / sec / peak

    report = {
        "dp": args.dp,
        "loss_fp8": round(l8, 6),
        "loss_ref": round(lref, 6),
        "parity_delta": round(parity, 6),
        "parity_tol": args.parity_tol,
        "post_warmup_recompiles": int(recompiles),
        "checkpoint_roundtrip_bitwise": bool(ckpt_ok),
        "sec_per_step": round(sec, 6),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_floor": args.mfu if not on_cpu else None,
    }
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(f"dp={args.dp}  fp8 loss {l8:.5f} vs fp32 {lref:.5f} "
              f"(delta {parity:.2%}, tol {args.parity_tol:.0%})")
        print(f"post-warmup recompiles: {int(recompiles)}  "
              f"checkpoint bitwise: {ckpt_ok}")
        print("mfu: " + (f"{mfu:.3f} (floor {args.mfu})"
                         if mfu is not None else "n/a on this backend"))

    fail = []
    if parity > args.parity_tol:
        fail.append(f"parity delta {parity:.2%} > tol "
                    f"{args.parity_tol:.0%}")
    if recompiles > 0:
        fail.append(f"{int(recompiles)} post-warmup recompiles")
    if not ckpt_ok:
        fail.append("fp8/EF checkpoint round-trip not bitwise")
    if mfu is not None and mfu < args.mfu:
        fail.append(f"MFU {mfu:.3f} < floor {args.mfu}")
    if fail:
        for f in fail:
            print(f"FAIL: {f}")
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
