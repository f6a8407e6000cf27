"""INT8 quantization ops.

Reference parity: src/operator/quantization/ (quantize_v2-inl.h,
dequantize-inl.h, quantized_fully_connected.cc, quantized_conv.cc, ~7.1k
LoC of CPU/GPU kernels).  TPU-native design: int8 tensors feed
``lax.dot_general`` / ``lax.conv_general_dilated`` with
``preferred_element_type=int32`` — XLA lowers these to the MXU's native
int8 matmul path — and the scale/zero-point arithmetic is plain jnp that
XLA fuses around the matmul.  The reference's `requantize` op and its
quantize/dequantize-elimination graph passes are subsumed by XLA fusion:
we always dequantize to fp32 after accumulation and let the compiler fuse
adjacent quantize(dequantize(x)) chains.

Quantization scheme: symmetric int8 (zero-point 0), per-tensor for
activations (calibrated range), per-output-channel for weights — the
scheme the reference uses for its int8 conv/FC path with
``MXNET_QUANTIZATION_*`` defaults.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import config as _config
from .. import runtime as _runtime
from ..numpy.multiarray import _invoke

__all__ = ["quantize_v2", "dequantize", "quantized_fully_connected",
           "quantized_conv", "quantized_dense_fused", "quantized_conv_fused",
           "fp8_dense_fused"]

_INT8_MAX = 127.0

#: fused-epilogue activations jnp can express inside one traced op (the
#: Pallas kernel supports the same set — see ops/pallas/quant_matmul.py)
FUSED_ACTS = (None, "relu", "sigmoid", "tanh", "gelu")


def _apply_act(out, act):
    import jax
    if act is None:
        return out
    if act == "relu":
        return jnp.maximum(out, 0.0)
    if act == "sigmoid":
        return jax.nn.sigmoid(out)
    if act == "tanh":
        return jnp.tanh(out)
    if act == "gelu":
        return jax.nn.gelu(out)
    raise ValueError(f"activation {act!r} cannot be fused; "
                     f"supported: {FUSED_ACTS}")


def _route_fused():
    """(use_pallas, interpret) per the ``quantize.fused_matmul`` knob:
    'auto' = Pallas on TPU only, 'on' = Pallas everywhere (interpret
    off-TPU — the CI parity oracle), 'off' = the XLA dot_general chain."""
    mode = str(_config.get("quantize.fused_matmul")).lower()
    if mode == "off":
        return False, False
    if mode == "on":
        return True, _runtime.pallas_interpret()
    return _runtime.on_tpu(), False


def _scale_from_range(min_range, max_range):
    return jnp.maximum(jnp.abs(min_range), jnp.abs(max_range)) / _INT8_MAX


def quantize_v2(data, min_calib_range=None, max_calib_range=None,
                out_type="int8"):
    """float32 -> (int8, min_range, max_range).

    Reference: src/operator/quantization/quantize_v2-inl.h — when calib
    ranges are given they are used directly; otherwise the runtime min/max
    of `data` is used.  Symmetric: zero maps to zero.
    """
    if out_type != "int8":
        raise NotImplementedError("TPU path quantizes to int8 only")

    def fn(x):
        if min_calib_range is None or max_calib_range is None:
            mx_ = jnp.max(jnp.abs(x))
            mn, mx = -mx_, mx_
        else:
            mn = jnp.asarray(min_calib_range, jnp.float32)
            mx = jnp.asarray(max_calib_range, jnp.float32)
        scale = _scale_from_range(mn, mx)
        q = jnp.clip(jnp.round(x / scale), -_INT8_MAX, _INT8_MAX)
        return q.astype(jnp.int8), mn, mx

    return _invoke(fn, (data,), name="quantize_v2")


def dequantize(data, min_range, max_range, out_type="float32"):
    """int8 -> float32 (reference: dequantize-inl.h)."""
    def fn(q, mn, mx):
        return q.astype(jnp.float32) * _scale_from_range(mn, mx)
    return _invoke(fn, (data, min_range, max_range), name="dequantize")


def quantized_fully_connected(data, weight, x_scale, w_scale, bias=None,
                              flatten=True):
    """int8 x int8 -> fp32 dense layer.

    Reference: src/operator/quantization/quantized_fully_connected.cc.
    TPU-native signature: instead of the reference's 9-input
    (min/max per operand) form, scales are passed directly —
    ``x_scale`` scalar, ``w_scale`` per-output-channel (units,) — and the
    output is dequantized fp32 (accumulation in int32 on the MXU).
    """
    def fn(x, w, xs, ws, *rest):
        b = rest[0] if rest else None
        h = x.reshape(x.shape[0], -1) if flatten else x
        acc = lax.dot_general(h, w, (((h.ndim - 1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * (jnp.asarray(xs, jnp.float32) * ws)
        if b is not None:
            out = out + b
        return out

    args = (data, weight, x_scale, w_scale)
    if bias is not None:
        args += (bias,)
    return _invoke(fn, args, name="quantized_fully_connected")


def quantized_conv(data, weight, x_scale, w_scale, bias=None, kernel=None,
                   stride=None, dilate=None, pad=None, num_filter=1,
                   num_group=1, layout="NCHW"):
    """int8 x int8 -> fp32 convolution.

    Reference: src/operator/quantization/quantized_conv.cc (cuDNN int8
    path, NHWC-only there; here any layout the fp conv supports).
    Accumulates int32 on the MXU, dequantizes with per-channel w_scale.
    """
    nd = data.ndim - 2
    spatial = "DHW"[3 - nd:]
    lhs_spec = layout
    rhs_spec = "OI" + spatial
    out_spec = layout
    strides = tuple(stride or (1,) * nd)
    dilation = tuple(dilate or (1,) * nd)
    padding = tuple((p, p) for p in (pad or (0,) * nd))
    c_axis = layout.index("C")

    def fn(x, w, xs, ws, *rest):
        b = rest[0] if rest else None
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        (lhs_spec, rhs_spec, out_spec))
        acc = lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=num_group,
            preferred_element_type=jnp.int32)
        shape = [1] * acc.ndim
        shape[c_axis] = -1
        sc = jnp.asarray(xs, jnp.float32) * jnp.reshape(ws, shape)
        out = acc.astype(jnp.float32) * sc
        if b is not None:
            out = out + jnp.reshape(b, shape)
        return out

    args = (data, weight, x_scale, w_scale)
    if bias is not None:
        args += (bias,)
    return _invoke(fn, args, name="quantized_conv")


def quantized_dense_fused(data, weight, x_scale, w_scale, bias=None,
                          act=None, flatten=True):
    """Fused quantize -> int8 x int8 dot -> dequant+bias+act dense layer.

    One traced op end to end: the separate quantize_v2 /
    quantized_fully_connected pair costs an HBM round-trip for the int8
    activations between the two ops.  Routing per ``quantize.fused_matmul``: the Pallas kernel
    (ops/pallas/quant_matmul.py) on TPU / when forced 'on' (interpret
    mode off-TPU), else the same ``lax.dot_general(preferred=int32)``
    expression as :func:`quantized_fully_connected` inside one jit so XLA
    fuses the chain.  ``weight`` is pre-quantized int8 (units, in_units),
    ``w_scale`` per-output-channel, ``x_scale`` the calibrated
    threshold / 127.
    """
    if act not in FUSED_ACTS:
        raise ValueError(f"activation {act!r} cannot be fused; "
                         f"supported: {FUSED_ACTS}")
    use_pallas, interpret = _route_fused()

    def fn(x, w, xs, ws, *rest):
        b = rest[0] if rest else None
        h = x.reshape(x.shape[0], -1) if flatten else x
        lead = h.shape[:-1]
        h2 = h.reshape(-1, h.shape[-1])
        if use_pallas:
            from .pallas.quant_matmul import quantized_matmul
            out = quantized_matmul(h2, w, ws, xs, bias=b, act=act,
                                   interpret=interpret)
        else:
            xs32 = jnp.asarray(xs, jnp.float32)
            xq = jnp.clip(jnp.round(h2 / xs32), -_INT8_MAX, _INT8_MAX
                          ).astype(jnp.int8)
            acc = lax.dot_general(xq, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * (xs32 * ws)
            if b is not None:
                out = out + b
            out = _apply_act(out, act)
        return out.reshape(lead + (w.shape[0],))

    args = (data, weight, x_scale, w_scale)
    if bias is not None:
        args += (bias,)
    return _invoke(fn, args, name="quantized_dense_fused")


def fp8_dense_fused(data, weight, x_scale, w_scale, bias=None, act=None,
                    flatten=True, fmt=None):
    """fp8-activation variant of :func:`quantized_dense_fused`.

    ``weight`` is pre-cast to the fp8 format (per-output-channel scaled),
    accumulation is fp32.  Gated on device capability by the caller via
    :func:`mxnet_tpu.ops.pallas.quant_matmul.fp8_capable`; the fallback
    (fp8 operands into ``lax.dot_general`` with fp32 preferred type)
    runs anywhere XLA supports the dtype, including CPU.
    """
    if act not in FUSED_ACTS:
        raise ValueError(f"activation {act!r} cannot be fused; "
                         f"supported: {FUSED_ACTS}")
    fmt = fmt or _config.get("quantize.fp8_format")
    use_pallas, interpret = _route_fused()

    def fn(x, w, xs, ws, *rest):
        from .pallas.quant_matmul import FP8_FORMATS, fp8_matmul
        if fmt not in FP8_FORMATS:
            raise ValueError(f"unknown fp8 format {fmt!r}")
        b = rest[0] if rest else None
        h = x.reshape(x.shape[0], -1) if flatten else x
        lead = h.shape[:-1]
        h2 = h.reshape(-1, h.shape[-1])
        if use_pallas:
            out = fp8_matmul(h2, w, ws, xs, bias=b, act=act, fmt=fmt,
                             interpret=interpret)
        else:
            xs32 = jnp.asarray(xs, jnp.float32)
            xq = (h2.astype(jnp.float32) / xs32).astype(FP8_FORMATS[fmt][0])
            acc = lax.dot_general(xq, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            out = acc * (xs32 * ws)
            if b is not None:
                out = out + b
            out = _apply_act(out, act)
        return out.reshape(lead + (w.shape[0],))

    args = (data, weight, x_scale, w_scale)
    if bias is not None:
        args += (bias,)
    return _invoke(fn, args, name="fp8_dense_fused")


def quantized_conv_fused(data, weight, x_scale, w_scale, bias=None,
                         act=None, kernel=None, stride=None, dilate=None,
                         pad=None, num_filter=1, num_group=1, layout="NCHW"):
    """Fused quantize -> int8 conv -> dequant+bias+act convolution.

    Same contract as :func:`quantized_conv` but the activation quantize
    and the epilogue live inside ONE traced op, so XLA keeps the int8
    activations in registers/VMEM instead of round-tripping them through
    HBM between quantize_v2 and the conv (there is no Pallas conv kernel;
    on TPU XLA's own int8 ``conv_general_dilated`` hits the MXU).
    """
    if act not in FUSED_ACTS:
        raise ValueError(f"activation {act!r} cannot be fused; "
                         f"supported: {FUSED_ACTS}")
    nd = data.ndim - 2
    spatial = "DHW"[3 - nd:]
    lhs_spec = layout
    rhs_spec = "OI" + spatial
    out_spec = layout
    strides = tuple(stride or (1,) * nd)
    dilation = tuple(dilate or (1,) * nd)
    padding = tuple((p, p) for p in (pad or (0,) * nd))
    c_axis = layout.index("C")

    def fn(x, w, xs, ws, *rest):
        b = rest[0] if rest else None
        xs32 = jnp.asarray(xs, jnp.float32)
        xq = jnp.clip(jnp.round(x / xs32), -_INT8_MAX, _INT8_MAX
                      ).astype(jnp.int8)
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        (lhs_spec, rhs_spec, out_spec))
        acc = lax.conv_general_dilated(
            xq, w, window_strides=strides, padding=padding,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=num_group,
            preferred_element_type=jnp.int32)
        shape = [1] * acc.ndim
        shape[c_axis] = -1
        sc = xs32 * jnp.reshape(ws, shape)
        out = acc.astype(jnp.float32) * sc
        if b is not None:
            out = out + jnp.reshape(b, shape)
        return _apply_act(out, act)

    args = (data, weight, x_scale, w_scale)
    if bias is not None:
        args += (bias,)
    return _invoke(fn, args, name="quantized_conv_fused")
