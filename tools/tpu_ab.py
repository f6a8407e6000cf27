"""A/B the Pallas fusion levers on real TPU, using bench.py's own rows.

Round-4 verdict items #1/#2: the fused conv3x3+BN+ReLU backward and the
fused dropout+residual+LayerNorm kernels were built as the named levers
for the ResNet/BERT MFU targets but never measured on hardware. This
runs each affected bench row twice — fusion forced on, then off — and
prints a compact JSON comparison.

Usage: python tools/tpu_ab.py [resnet|bert|all]
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def run_ab(flag, fn, kwargs, peak):
    from mxnet_tpu import config
    prior = config.get(flag)
    out = {}
    try:
        for mode in ("on", "off"):
            config.set(flag, mode)
            row = fn(on_cpu=False, peak=peak, **kwargs)
            out[mode] = {k: row[k] for k in
                         ("name", "items_per_s", "ms_per_step", "mfu")
                         if k in row}
    finally:
        config.set(flag, prior)
    if "on" in out and "off" in out:
        out["speedup_on_vs_off"] = round(
            out["on"]["items_per_s"] / out["off"]["items_per_s"], 4)
    return out


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    import jax
    dev = jax.devices()[0]
    assert dev.platform == "tpu", f"need TPU, got {dev.platform}"
    from mxnet_tpu import insight
    peak = insight.peaks(dev.device_kind)[0]
    res = {"device": getattr(dev, "device_kind", "?")}
    if which in ("bert", "all"):
        # both workloads: dropout off (XLA's fusion wins) and on (the
        # kernel's case) — the auto gate in transformer.py cites these.
        res["bert_bs32_fused_ln"] = run_ab(
            "fused_ln_residual", bench.bench_bert_train,
            dict(precision="bf16", bs=32), peak)
        res["bert_bs32_dropout0.1_fused_ln"] = run_ab(
            "fused_ln_residual", bench.bench_bert_train,
            dict(precision="bf16", bs=32, dropout=0.1), peak)
    if which in ("resnet", "all"):
        res["resnet50_bs32_fused_conv_bn"] = run_ab(
            "fused_conv_bn", bench.bench_resnet50_train,
            dict(precision="bf16"), peak)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
