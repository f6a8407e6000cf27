"""Fused low-bit matmul Pallas kernels (TPU).

Reference parity: the role of src/operator/quantization/'s cuDNN int8
kernels (quantized_fully_connected.cc, quantized_conv.cc) — the hand-
written path the reference keeps because compiler fusion alone does not
reach the int8 peak. The same holds here: in the composed
quantize_v2 → dot_general(int32) → dequantize chain XLA materializes the
int8 activations and the fp32 epilogue in HBM between ops, which spends
the bandwidth the int8 matmul saved. This kernel streams one
(block_m, K) activation tile through VMEM ONCE: quantize in registers,
int8×int8 dot on the MXU with int32 accumulation, dequant + bias +
activation in the epilogue, write the finished fp tile.

Scheme (matches ops/quantization.py): symmetric int8, zero-point 0,
per-tensor activation scale (calibrated threshold), per-output-channel
weight scales. The epilogue computes ``acc * (x_scale * w_scale) + bias``
in fp32 — bitwise the same expression as the XLA fallback, which the
parity tests in tests/test_quantization.py hold as an oracle.

The fp8 variant keeps the same structure with e4m3/e5m2 operands and
fp32 MXU accumulation; it is gated on device capability
(:func:`fp8_capable`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import runtime as _runtime

__all__ = ["quantized_matmul", "fp8_matmul", "fp8_capable", "FP8_FORMATS"]

_INT8_MAX = 127.0
_VMEM_DEFAULT = 16 << 20    # Mosaic's scoped VMEM limit a kernel

#: fp8 storage formats: name -> (dtype, absmax of the format)
FP8_FORMATS = {
    "e4m3": (jnp.float8_e4m3fn, 448.0),
    "e5m2": (jnp.float8_e5m2, 57344.0),
}

_ACTS = {
    None: lambda z: z,
    "relu": lambda z: jnp.maximum(z, 0.0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "gelu": jax.nn.gelu,
}


def fp8_capable(device=None):
    """Whether the Pallas fp8 kernel is used on ``device``: every TPU
    from v5 on.  On the v5e ("TPU v5 lite") libtpu 0.0.34's Mosaic
    accepts the e4m3 and e5m2 ``dot_general`` and the result is exact
    against an fp32 reference (tools/tpu_kernel_check.py, chip run of
    PR 21) — but the v5e MXU has no fp8 mode (its peak row lists none;
    v6e is the first that does), so there the dot is an upcast and
    whether it beats bf16 is not measured (ROADMAP S9).  v4 and earlier
    stay gated off."""
    if device is None:
        if not _runtime.on_tpu():
            return False
        device = jax.devices()[0]
    elif device.platform != "tpu":
        return False
    kind = getattr(device, "device_kind", "").lower()
    return not any(old in kind for old in ("v2", "v3", "v4"))


def _round_up(n, m):
    return (n + m - 1) // m * m


def _pad2(a, rows, cols):
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    if pr == 0 and pc == 0:
        return a
    return jnp.pad(a, ((0, pr), (0, pc)))


def _int8_kernel(xs_ref, x_ref, w_ref, ws_ref, b_ref, o_ref, *, act):
    """One (block_m, block_n) output tile: quantize the activation tile
    in registers, int8×int8 dot (int32 MXU accumulation), fp32 dequant
    epilogue with bias + activation."""
    x_scale = xs_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    xq = jnp.clip(jnp.round(x / x_scale), -_INT8_MAX, _INT8_MAX
                  ).astype(jnp.int8)
    acc = lax.dot_general(xq, w_ref[...], (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * (x_scale * ws_ref[...])
    out = out + b_ref[...]
    o_ref[...] = _ACTS[act](out).astype(o_ref.dtype)


def quantized_matmul(x, w_q, w_scale, x_scale, bias=None, act=None,
                     block_m=None, block_n=None, interpret=False):
    """``dequant(quantize(x) @ w_q.T) + bias`` fused in one VMEM pass.

    x: (M, K) float; w_q: (N, K) int8 (per-output-channel quantized);
    w_scale: (N,) fp32; x_scale: scalar fp32 (calibrated threshold / 127).
    bias: (N,) fp32 or None; act: one of None/'relu'/'sigmoid'/'tanh'/
    'gelu', applied in the epilogue. Returns (M, N) fp32.

    K rides whole through VMEM per tile (one (block_n, K) int8 weight
    tile is K bytes * block_n — 256x4096 = 1 MB, comfortably resident);
    M/N are tiled and zero-padded to Mosaic-aligned blocks. Zero padding
    is exact: padded activations quantize to 0 and contribute nothing to
    the int32 dot.
    """
    if act not in _ACTS:
        raise ValueError(f"unsupported fused activation {act!r}; "
                         f"one of {sorted(k for k in _ACTS if k)}")
    m, k = x.shape
    n = w_q.shape[0]
    if block_m is None or block_n is None:
        from ...autotune.kernels import resolve_blocks
        tb = resolve_blocks("quantized_matmul", (m, n, k))
        block_m = tb["block_m"] if block_m is None else block_m
        block_n = tb["block_n"] if block_n is None else block_n
    # int8 tiles are (32, 128); the fp32 output tile needs lane 128
    bm = min(block_m, _round_up(m, 32))
    bn = min(block_n, _round_up(n, 128))
    grid_m, grid_n = pl.cdiv(m, bm), pl.cdiv(n, bn)
    mp, np_, kp = grid_m * bm, grid_n * bn, _round_up(k, 128)
    xp = _pad2(x, mp, kp)
    wp = _pad2(w_q, np_, kp)
    wsp = _pad2(w_scale.astype(jnp.float32)[None, :], 1, np_)
    b = (jnp.zeros((n,), jnp.float32) if bias is None
         else bias.astype(jnp.float32))
    bp = _pad2(b[None, :], 1, np_)
    xs = jnp.asarray(x_scale, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_int8_kernel, act=act),
        grid=(grid_m, grid_n),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, kp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="mx_int8_matmul",
    )(xs, xp, wp, wsp, bp)
    return out[:m, :n]


def _fp8_kernel(xs_ref, x_ref, w_ref, ws_ref, b_ref, o_ref, *, act, fmt):
    dtype, _ = FP8_FORMATS[fmt]
    x_scale = xs_ref[0, 0]
    xq = (x_ref[...].astype(jnp.float32) / x_scale).astype(dtype)
    acc = lax.dot_general(xq, w_ref[...], (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    out = acc * (x_scale * ws_ref[...]) + b_ref[...]
    o_ref[...] = _ACTS[act](out).astype(o_ref.dtype)


def fp8_matmul(x, w_q, w_scale, x_scale, bias=None, act=None, fmt="e4m3",
               block_m=None, block_n=None, interpret=False):
    """fp8×fp8 variant of :func:`quantized_matmul`.

    w_q: (N, K) in the chosen fp8 format (per-output-channel scaled so
    each row uses the format's full range); accumulation is fp32 on the
    MXU. Same tiling/padding story as the int8 kernel.
    """
    if fmt not in FP8_FORMATS:
        raise ValueError(f"unknown fp8 format {fmt!r}; "
                         f"one of {sorted(FP8_FORMATS)}")
    if act not in _ACTS:
        raise ValueError(f"unsupported fused activation {act!r}")
    m, k = x.shape
    n = w_q.shape[0]
    if block_m is None or block_n is None:
        from ...autotune.kernels import resolve_blocks
        tb = resolve_blocks("fp8_matmul", (m, n, k))
        block_m = tb["block_m"] if block_m is None else block_m
        block_n = tb["block_n"] if block_n is None else block_n
    bm = min(block_m, _round_up(m, 32))
    bn = min(block_n, _round_up(n, 128))
    grid_m, grid_n = pl.cdiv(m, bm), pl.cdiv(n, bn)
    mp, np_, kp = grid_m * bm, grid_n * bn, _round_up(k, 128)
    xp = _pad2(x, mp, kp)
    wp = _pad2(w_q, np_, kp)
    wsp = _pad2(w_scale.astype(jnp.float32)[None, :], 1, np_)
    b = (jnp.zeros((n,), jnp.float32) if bias is None
         else bias.astype(jnp.float32))
    bp = _pad2(b[None, :], 1, np_)
    xs = jnp.asarray(x_scale, jnp.float32).reshape(1, 1)
    # K rides whole: past the default scoped limit (16 MiB: fp32
    # activations at K 5120 and more) the call asks for what its tiles
    # hold, twice for the pipeline, and their fp32 / fp8 copies in the
    # body; shorter calls are compiled as they always were
    tiles = kp * (bm * xp.dtype.itemsize + bn) + 4 * bm * bn
    extra = {} if 2 * tiles <= _VMEM_DEFAULT - (2 << 20) else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=2 * tiles + 5 * bm * kp + (16 << 20))}
    out = pl.pallas_call(
        functools.partial(_fp8_kernel, act=act, fmt=fmt),
        grid=(grid_m, grid_n),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, kp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="mx_fp8_matmul",
        **extra,
    )(xs, xp, wp, wsp, bp)
    return out[:m, :n]
