"""Low-bit inference gates (CI `quantize` stage; the PR 8 acceptance
benchmark — docs/PERFORMANCE.md "Low-bit inference").

CPU CI gates (always run):

- **fused-kernel parity**: the Pallas fused quantize->int8-dot->dequant
  kernel (interpret mode off-TPU, ``quantize.fused_matmul=on``) against
  the XLA fallback chain (``off``) — bitwise without a bias (symmetric
  int8 quantizes identically and accumulates in exact int32; zero
  padding is exact), <=1e-5 with a bias (the kernel may FMA-contract the
  epilogue mul+add).
- **int4 weight bytes**: packed group-wise int4 over a GPT's eligible
  weights must come in at <=0.15x the fp32 footprint (nibbles + scales).
- **zero recompiles**: engines with ``int8_weights`` and
  ``int4_weights,int8_kv`` must report ZERO post-warmup compiles across
  a mixed-bucket workload — low-bit storage must not change the traced
  step signature (the PR 2 detector is the oracle).

Hardware gates (TPU attached; skipped with a notice on CPU):

- int8 resnet50 inference beats bf16 (items/s — the fused path's reason
  to exist: the unfused chain pays an HBM round-trip for the int8
  activations between its ops).
- gpt2-class decode with ``int4_weights`` >= --min-decode-speedup
  (default 1.3x) tokens/s over fp32 with greedy parity on the workload.

Prints ONE JSON line (the bench.py contract).

Usage: JAX_PLATFORMS=cpu python benchmark/quantized_inference.py --assert
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _route(mode):
    from mxnet_tpu import config
    return config.set("quantize.fused_matmul", mode)


def gate_fused_parity():
    """Pallas-vs-fallback over aligned and deliberately ragged shapes."""
    import mxnet_tpu as mx
    from mxnet_tpu import npx

    results = []
    for m, k, n, bias in [(32, 64, 16, False), (5, 33, 7, False),
                          (130, 257, 129, False), (32, 64, 16, True)]:
        rs = onp.random.RandomState(m)
        x = rs.randn(m, k).astype("float32")
        w = (rs.randn(n, k) * 0.5).astype("float32")
        w_scale = onp.abs(w).max(axis=1) / 127.0
        qw = onp.clip(onp.round(w / w_scale[:, None]), -127, 127
                      ).astype("int8")
        b = rs.randn(n).astype("float32") if bias else None
        args = (mx.np.array(x), mx.np.array(qw),
                float(onp.abs(x).max()) / 127.0, mx.np.array(w_scale))
        kw = {"bias": mx.np.array(b)} if bias else {}
        prev = _route("on")
        try:
            got = npx.quantized_dense_fused(*args, **kw).asnumpy()
        finally:
            _route(prev)
        prev = _route("off")
        try:
            ref = npx.quantized_dense_fused(*args, **kw).asnumpy()
        finally:
            _route(prev)
        if bias:  # FMA contraction inside the kernel: one ulp
            ok = bool(onp.abs(got - ref).max() <= 1e-5)
        else:
            ok = bool((got == ref).all())
        results.append({"shape": [m, k, n], "bias": bias, "ok": ok,
                        "max_abs_diff": float(onp.abs(got - ref).max())})
    return {"cases": results, "ok": all(r["ok"] for r in results)}


def _tiny_gpt(seed):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM

    mx.random.seed(seed)
    net = GPTForCausalLM(vocab_size=512, units=64, hidden_size=256,
                         num_layers=2, num_heads=4, max_length=128,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((1, 2), dtype="int32"))
    return net


def gate_int4_bytes(max_ratio):
    import mxnet_tpu as mx

    eng = mx.serve.load(_tiny_gpt(0), max_slots=4, quantize="int4_weights")
    st = eng.stats()
    ratio = st["weight_bytes"] / st["weight_bytes_fp"]
    return {"weight_bytes_ratio": round(ratio, 4),
            "quantized_params": st["quantized_params"],
            "passthrough_params": st["passthrough_params"],
            "ok": bool(ratio <= max_ratio)}


def gate_zero_recompiles():
    import mxnet_tpu as mx

    rng = onp.random.RandomState(1)
    out = {}
    for spec in ("int8_weights", "int4_weights,int8_kv"):
        eng = mx.serve.load(_tiny_gpt(1), max_slots=4, quantize=spec,
                            warmup=True)
        for _ in range(8):  # mixed lengths across the bucket grid
            eng.submit(rng.randint(1, 512, size=rng.randint(2, 24)).tolist(),
                       max_new_tokens=8)
        eng.run()
        out[spec] = eng.stats()["post_warmup_compiles"]
    return {"post_warmup_compiles": out,
            "ok": all(v == 0 for v in out.values())}


def _decode_tokens_per_s(net, quantize, work, seed=0):
    import time

    import mxnet_tpu as mx

    eng = mx.serve.load(net, max_slots=8, quantize=quantize, seed=seed,
                        warmup=True)
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
    eng.run()
    wall = time.perf_counter() - t0
    st = eng.stats()
    return st["tokens_out"] / wall, [r.output_ids for r in reqs], st


def gate_hardware(min_decode_speedup):
    """TPU-only: the wins the fused path + weight-only storage promise."""
    import bench
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM

    peak = mx.insight.peaks()[0]
    r_bf16 = bench.bench_resnet50_infer("bf16", False, peak)
    r_int8 = bench.bench_resnet50_infer("int8", False, peak)
    infer_speedup = r_int8["items_per_s"] / r_bf16["items_per_s"]

    mx.random.seed(3)
    net = GPTForCausalLM(vocab_size=50257, units=768, hidden_size=3072,
                         num_layers=12, num_heads=12, max_length=512,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((1, 2), dtype="int32"))
    rng = onp.random.RandomState(3)
    work = [(rng.randint(1, 50257, size=rng.randint(4, 64)).tolist(), 48)
            for _ in range(24)]
    tps_fp, out_fp, _ = _decode_tokens_per_s(net, None, work)
    tps_i4, out_i4, st4 = _decode_tokens_per_s(net, "int4_weights", work)
    matched = sum(a == b for a, b in zip(out_fp, out_i4))
    decode_speedup = tps_i4 / tps_fp
    return {
        "resnet50_int8_vs_bf16": round(infer_speedup, 3),
        "gpt2_decode_int4_vs_fp32": round(decode_speedup, 3),
        "decode_outputs_matched": f"{matched}/{len(work)}",
        "int4_weight_bytes_ratio": round(
            st4["weight_bytes"] / st4["weight_bytes_fp"], 4),
        "ok": bool(infer_speedup > 1.0
                   and decode_speedup >= min_decode_speedup
                   and matched == len(work)),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--max-int4-ratio", type=float, default=0.15)
    p.add_argument("--min-decode-speedup", type=float, default=1.3)
    p.add_argument("--assert", dest="check", action="store_true",
                   help="exit nonzero unless every gate holds")
    args = p.parse_args(argv)

    import jax
    on_tpu = jax.devices()[0].platform == "tpu"

    report = {
        "metric": "quantized_inference_gates",
        "platform": jax.devices()[0].platform,
        "fused_parity": gate_fused_parity(),
        "int4_bytes": gate_int4_bytes(args.max_int4_ratio),
        "zero_recompiles": gate_zero_recompiles(),
    }
    if on_tpu:
        report["hardware"] = gate_hardware(args.min_decode_speedup)
    else:
        report["hardware"] = "skipped (no TPU attached)"
    gates = [v for v in report.values() if isinstance(v, dict) and "ok" in v]
    report["ok"] = all(g["ok"] for g in gates)
    print(json.dumps(report))
    if args.check and not report["ok"]:
        failed = [k for k, v in report.items()
                  if isinstance(v, dict) and v.get("ok") is False]
        print(f"FAIL: gates {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
