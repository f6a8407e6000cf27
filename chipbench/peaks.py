"""The benchmark's own table of chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect).  The program has a table of its
own (``mx.insight.PEAKS``); this copy is the yardstick and later PRs
cannot change it.  A device that is not in the table is an error, never
a default.
"""
from __future__ import annotations

PEAKS = {
    # device_kind as jax.devices()[0].device_kind reports a v5e
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"chipbench: device kind {device_kind!r} is not in "
            f"chipbench/peaks.py ({sorted(PEAKS)}); add it with its source "
            "before measuring on it") from None
