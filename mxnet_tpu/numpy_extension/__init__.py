"""mx.npx — NumPy-extension (neural-network) operators.

Reference parity: python/mxnet/numpy_extension/ over the C++ op library
src/operator/nn/* (convolution, batch_norm, layer_norm, softmax, pooling,
dropout, fully_connected, rnn-inl.h fused RNN), src/operator/contrib/
transformer.cc:675-828 (interleaved multi-head-attention matmuls) and
src/operator/npx_control_flow.cc (foreach/while_loop/cond subgraph ops).

TPU-native design: every op is a jnp/lax composition dispatched through
``_invoke`` (async + autograd-recorded); XLA fuses the elementwise tails into
the MXU matmuls/convs. Convolution/pooling lower to
``lax.conv_general_dilated`` / ``lax.reduce_window`` — the XLA ops the TPU
compiler tiles onto the MXU directly (replacing the cuDNN paths). The fused
RNN op is a ``lax.scan`` (compiler-friendly loop), and the control-flow ops
are ``lax.cond`` / ``lax.while_loop`` / ``lax.scan`` so they stay jittable.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, np_dtype
from ..numpy.multiarray import ndarray, _invoke, _wrap, _wrap_out

# ---------------------------------------------------------------------------
# numpy-mode toggles (reference: npx.set_np / util.py scopes). The new
# framework is numpy-semantics-only, so these are compatibility facades.
# ---------------------------------------------------------------------------

_np_state = threading.local()


def set_np(shape=True, array=True, dtype=False):
    _np_state.active = True


def reset_np():
    _np_state.active = False


def is_np_array():
    return True


def is_np_shape():
    return True


def is_np_default_dtype():
    return getattr(_np_state, "np_dtype", False)


def use_np(func):
    return func


def use_np_array(func):
    return func


def use_np_shape(func):
    return func


def waitall():
    from .. import engine
    engine.wait_all()


def cpu(i=0):
    from ..context import cpu as _cpu
    return _cpu(i)


def gpu(i=0):
    from ..context import gpu as _gpu
    return _gpu(i)


def num_gpus():
    from ..context import num_gpus as _n
    return _n()


# ---------------------------------------------------------------------------
# activations / softmax
# ---------------------------------------------------------------------------

_ACTS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "log_sigmoid": jax.nn.log_sigmoid,
    "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
}


def activation(data, act_type="relu", **kwargs):
    """Reference: src/operator/nn/activation.cc."""
    if act_type not in _ACTS:
        raise MXNetError(f"unknown act_type {act_type!r}")
    return _invoke(_ACTS[act_type], (data,), name=f"activation:{act_type}")


def relu(data):
    return _invoke(jax.nn.relu, (data,))


def sigmoid(data):
    return _invoke(jax.nn.sigmoid, (data,))


def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, **kwargs):
    """Reference: src/operator/leaky_relu.cc (leaky/prelu/elu/selu/gelu/rrelu)."""
    nm = f"leaky_relu:{act_type}"  # attr-suffixed for AMP conditional lists
    if act_type == "leaky":
        return _invoke(lambda x: jax.nn.leaky_relu(x, slope), (data,), name=nm)
    if act_type == "prelu":
        return _invoke(lambda x, g: jnp.where(x >= 0, x, g * x),
                       (data, gamma), name=nm)
    if act_type == "elu":
        return _invoke(lambda x: jax.nn.elu(x, slope), (data,), name=nm)
    if act_type == "selu":
        return _invoke(jax.nn.selu, (data,), name=nm)
    if act_type == "gelu":
        return _invoke(lambda x: jax.nn.gelu(x, approximate=False), (data,),
                       name=nm)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return _invoke(lambda x: jax.nn.leaky_relu(x, mid), (data,), name=nm)
    raise MXNetError(f"unknown leaky_relu act_type {act_type!r}")


def _length_mask(h, ln, axis):
    """Positions-beyond-length mask (reference: softmax-inl.h:132 — length
    has the data's shape minus the softmax axis; a 1-D length broadcasts
    over the middle dims)."""
    ax = axis % h.ndim
    pos = jnp.arange(h.shape[ax])
    shape = [1] * h.ndim
    shape[ax] = h.shape[ax]
    if ln.ndim == h.ndim - 1:
        ln_b = jnp.expand_dims(ln, ax)
    else:
        ln_b = ln.reshape((ln.shape[0],) + (1,) * (h.ndim - 1))
    return pos.reshape(shape) < ln_b


def softmax(data, length=None, axis=-1, temperature=None, use_length=False,
            dtype=None):
    """Reference: src/operator/nn/softmax.cc (with optional length masking;
    masked positions write 0.0, softmax-inl.h:142)."""
    def fn(x, ln=None):
        h = x / temperature if temperature else x
        if ln is not None:
            mask = _length_mask(h, ln, axis)
            out = jax.nn.softmax(jnp.where(mask, h, -jnp.inf), axis)
            return jnp.where(mask, out, 0.0).astype(np_dtype(dtype) or x.dtype)
        return jax.nn.softmax(h, axis).astype(np_dtype(dtype) or x.dtype)
    if length is not None or use_length:
        return _invoke(fn, (data, length), name="softmax")
    return _invoke(fn, (data,), name="softmax")


def log_softmax(data, axis=-1, temperature=None, dtype=None, use_length=False,
                length=None):
    """Reference: src/operator/nn/softmax.cc log variant; masked positions
    write 0.0 like the softmax kernel (same OType(0.0f) store)."""
    def fn(x, ln=None):
        h = x / temperature if temperature else x
        if ln is not None:
            mask = _length_mask(h, ln, axis)
            out = jax.nn.log_softmax(jnp.where(mask, h, -jnp.inf), axis)
            return jnp.where(mask, out, 0.0).astype(np_dtype(dtype) or x.dtype)
        return jax.nn.log_softmax(h, axis).astype(np_dtype(dtype) or x.dtype)
    if length is not None or use_length:
        return _invoke(fn, (data, length), name="log_softmax")
    return _invoke(fn, (data,), name="log_softmax")


def masked_softmax(data, mask, axis=-1, temperature=1.0):
    def fn(x, m):
        h = x / temperature if temperature else x
        h = jnp.where(m, h, -jnp.inf)
        return jnp.where(m, jax.nn.softmax(h, axis), 0.0)
    return _invoke(fn, (data, mask), name="masked_softmax")


def masked_log_softmax(data, mask, axis=-1, temperature=1.0):
    def fn(x, m):
        h = x / temperature if temperature else x
        h = jnp.where(m, h, -jnp.inf)
        return jnp.where(m, jax.nn.log_softmax(h, axis), -jnp.inf)
    return _invoke(fn, (data, mask), name="masked_log_softmax")


def softmin(data, axis=-1, temperature=None, dtype=None):
    return softmax(-data if not isinstance(data, ndarray) else data * -1,
                   axis=axis, temperature=temperature, dtype=dtype)


# ---------------------------------------------------------------------------
# dense / conv / pooling / norm  (the MXU path)
# ---------------------------------------------------------------------------

def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    """Reference: src/operator/nn/fully_connected.cc. weight is (units, in)."""
    def fn(x_, w, b=None):
        h = x_.reshape(x_.shape[0], -1) if flatten else x_
        out = jnp.matmul(h, w.T)
        if b is not None:
            out = out + b
        return out
    if bias is None or no_bias:
        return _invoke(fn, (x, weight), name="fully_connected")
    return _invoke(fn, (x, weight, bias), name="fully_connected")


def convolution(data=None, weight=None, bias=None, kernel=None, stride=None,
                dilate=None, pad=None, num_filter=1, num_group=1,
                workspace=1024, no_bias=False, cudnn_tune=None,
                cudnn_off=False, layout=None):
    """Reference: src/operator/nn/convolution.cc (cuDNN path rnn-inl style).

    Lowers to lax.conv_general_dilated — XLA maps this straight onto the MXU.
    Layouts supported: NCW / NCHW / NCDHW (MXNet defaults) and NWC/NHWC/NDHWC.
    """
    nd = data.ndim - 2
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nd]
    channel_last = layout[-1] == "C"
    spatial = "DHW"[3 - nd:]
    lhs_spec = layout
    rhs_spec = "OI" + spatial
    out_spec = layout
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    padding = [(p, p) for p in pad]

    def fn(x, w, b=None):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        (lhs_spec, rhs_spec, out_spec))
        out = lax.conv_general_dilated(
            x, w, window_strides=stride, padding=padding,
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=num_group)
        if b is not None:
            shape = [1] * out.ndim
            shape[out_spec.index("C")] = b.shape[0]
            out = out + b.reshape(shape)
        return out

    if bias is None or no_bias:
        return _invoke(fn, (data, weight), name="convolution")
    return _invoke(fn, (data, weight, bias), name="convolution")


def deconvolution(data=None, weight=None, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, target_shape=None,
                  num_filter=1, num_group=1, workspace=512, no_bias=True,
                  cudnn_tune=None, cudnn_off=False, layout=None):
    """Reference: src/operator/nn/deconvolution.cc (transposed conv)."""
    nd = data.ndim - 2
    layout = layout or {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nd]
    spatial = "DHW"[3 - nd:]
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd

    def fn(x, w, b=None):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        (layout, "IO" + spatial, layout))
        k = [(w.shape[2 + i] - 1) * dilate[i] + 1 for i in range(nd)]
        padding = [(k[i] - 1 - pad[i], k[i] - 1 - pad[i]) for i in range(nd)]
        # transposed conv = fractionally-strided conv with spatially-flipped
        # kernel read as (I, O, spatial)
        w_flipped = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
        if num_group > 1:
            # weight is (C_in, C_out/g, *k); lax wants I=C_in/g with O=C_out
            # blocked by group: regroup (g, C_in/g, C_out/g) -> (C_in/g, g*C_out/g)
            cin, cog = w.shape[0], w.shape[1]
            ksp = w.shape[2:]
            w_flipped = (w_flipped
                         .reshape((num_group, cin // num_group, cog) + ksp)
                         .transpose((1, 0, 2) + tuple(range(3, 3 + nd)))
                         .reshape((cin // num_group, num_group * cog) + ksp))
        out = lax.conv_general_dilated(
            x, w_flipped, window_strides=(1,) * nd, padding=padding,
            lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=num_group)
        if b is not None:
            shape = [1] * out.ndim
            shape[layout.index("C")] = b.shape[0]
            out = out + b.reshape(shape)
        return out

    if bias is None or no_bias:
        return _invoke(fn, (data, weight), name="deconvolution")
    return _invoke(fn, (data, weight, bias), name="deconvolution")


def deformable_convolution(data=None, offset=None, weight=None, bias=None,
                           kernel=None, stride=None, dilate=None, pad=None,
                           num_filter=1, num_group=1, num_deformable_group=1,
                           workspace=1024, no_bias=False, layout=None,
                           **kwargs):
    """DCN v1 (reference: src/operator/contrib/deformable_convolution.cc).

    Bilinear grid-sampling gathers + one MXU einsum; see ops/deformable.py.
    NCHW only (the reference CUDA kernel is also NCHW-only).
    """
    from ..ops.deformable import deformable_conv2d
    if layout not in (None, "NCHW"):
        raise MXNetError("deformable_convolution supports NCHW only")
    kernel = tuple(kernel)
    kw = dict(kernel=kernel, stride=tuple(stride) if stride else (1, 1),
              pad=tuple(pad) if pad else (0, 0),
              dilate=tuple(dilate) if dilate else (1, 1),
              num_group=num_group,
              num_deformable_group=num_deformable_group)
    if bias is None or no_bias:
        return _invoke(lambda x, o, w: deformable_conv2d(x, o, w, **kw),
                       (data, offset, weight), name="deformable_convolution")
    return _invoke(lambda x, o, w, b: deformable_conv2d(x, o, w, b, **kw),
                   (data, offset, weight, bias),
                   name="deformable_convolution")


def modulated_deformable_convolution(data=None, offset=None, mask=None,
                                     weight=None, bias=None, kernel=None,
                                     stride=None, dilate=None, pad=None,
                                     num_filter=1, num_group=1,
                                     num_deformable_group=1, workspace=1024,
                                     no_bias=False, layout=None, **kwargs):
    """DCN v2 (reference: src/operator/contrib/modulated_deformable_convolution.cc).
    The mask input multiplies each sampled value (caller applies sigmoid*2,
    matching the reference Gluon block)."""
    from ..ops.deformable import deformable_conv2d
    if layout not in (None, "NCHW"):
        raise MXNetError("modulated_deformable_convolution supports NCHW only")
    kw = dict(kernel=tuple(kernel),
              stride=tuple(stride) if stride else (1, 1),
              pad=tuple(pad) if pad else (0, 0),
              dilate=tuple(dilate) if dilate else (1, 1),
              num_group=num_group,
              num_deformable_group=num_deformable_group)
    if bias is None or no_bias:
        return _invoke(
            lambda x, o, m, w: deformable_conv2d(x, o, w, mask=m, **kw),
            (data, offset, mask, weight),
            name="modulated_deformable_convolution")
    return _invoke(
        lambda x, o, m, w, b: deformable_conv2d(x, o, w, b, mask=m, **kw),
        (data, offset, mask, weight, bias),
        name="modulated_deformable_convolution")


def pooling(data, kernel=1, stride=None, pad=None, pool_type="max",
            pooling_convention="valid", global_pool=False, p_value=2,
            count_include_pad=True, layout="NCHW", cudnn_off=False):
    """Reference: src/operator/nn/pooling.cc. lax.reduce_window lowering."""
    nd = data.ndim - 2
    if isinstance(kernel, int):
        kernel = (kernel,) * nd
    kernel = tuple(kernel)
    stride = tuple(stride) if stride else kernel
    pad = tuple(pad) if pad else (0,) * nd
    ch_axis = layout.index("C")
    sp_axes = [i for i in range(data.ndim) if i not in (0, ch_axis)]

    def fn(x):
        if global_pool:
            if pool_type == "max":
                return jnp.max(x, axis=tuple(sp_axes), keepdims=True)
            if pool_type == "avg":
                return jnp.mean(x, axis=tuple(sp_axes), keepdims=True)
            if pool_type == "sum":
                return jnp.sum(x, axis=tuple(sp_axes), keepdims=True)
            return jnp.power(jnp.sum(jnp.power(jnp.abs(x), p_value),
                                     axis=tuple(sp_axes), keepdims=True),
                             1.0 / p_value)
        dims, strides, padding = [1] * x.ndim, [1] * x.ndim, [(0, 0)] * x.ndim
        for i, ax in enumerate(sp_axes):
            dims[ax], strides[ax] = kernel[i], stride[i]
            padding[ax] = (pad[i], pad[i])
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, dims, strides, padding)
        s = lax.reduce_window(
            x if pool_type != "lp" else jnp.power(jnp.abs(x), p_value),
            0.0, lax.add, dims, strides, padding)
        if pool_type == "sum":
            return s
        if pool_type == "lp":
            return jnp.power(s, 1.0 / p_value)
        if count_include_pad:
            denom = 1
            for i in range(nd):
                denom *= kernel[i]
            return s / denom
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, padding)
        return s / cnt

    return _invoke(fn, (data,), name=f"pooling:{pool_type}")


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False,
               min_calib_range=None, max_calib_range=None):
    """Reference: src/operator/nn/batch_norm.cc.

    Training mode (autograd.is_training and not use_global_stats) uses batch
    statistics and updates the running-stat arrays *in place* (version bump on
    the same wrappers — the Gluon layer passes its aux Parameters here, which
    is how the reference's mutable aux states behave).
    """
    from .. import autograd as _ag
    training = _ag.is_training() and not use_global_stats

    def fn(x_, g, b):
        red = tuple(i for i in range(x_.ndim) if i != axis)
        shape = [1] * x_.ndim
        shape[axis] = x_.shape[axis]
        if training:
            # Single-pass statistics (E[x^2] - E[x]^2, fp32 accumulation):
            # both reductions share one read of x, which matters because the
            # training step is HBM-bandwidth-bound on TPU (profiled: the
            # two-pass mean/var formulation costs ~8% of a ResNet-50 step).
            xf = x_.astype(jnp.float32)
            mean = jnp.mean(xf, axis=red)
            # clamp: E[x^2]-E[x]^2 can go slightly negative by cancellation
            var = jnp.maximum(jnp.mean(xf * xf, axis=red) - mean * mean, 0.0)
        else:
            mean = running_mean._data
            var = running_var._data
        g_ = jnp.ones_like(g) if fix_gamma else g
        inv = lax.rsqrt((var + eps).astype(jnp.float32))
        # fold (mean, inv, gamma, beta) into a per-channel scale/shift so the
        # apply pass is one fused multiply-add in the compute dtype
        scale = (inv * g_).astype(x_.dtype).reshape(shape)
        shift = (b - mean * inv * g_).astype(x_.dtype).reshape(shape)
        out = x_ * scale + shift
        return (out, mean, var) if (training or output_mean_var) else out

    res = _invoke(fn, (x, gamma, beta), name="batch_norm")
    if training:
        out, mean, var = res
        m = momentum
        running_mean._rebind(
            (m * running_mean._data
             + (1 - m) * lax.stop_gradient(mean._data)).astype(running_mean.dtype))
        running_var._rebind(
            (m * running_var._data
             + (1 - m) * lax.stop_gradient(var._data)).astype(running_var.dtype))
        return (out, mean, var) if output_mean_var else out
    return res


def fused_conv_bn_relu(x, weight, gamma, beta, running_mean, running_var,
                       momentum=0.9, eps=1e-5, interpret=None):
    """Training-mode relu(bn(conv3x3_s1(x, w))) with the Pallas fused
    backward (ops/pallas_conv_bwd.py — dy recomputed in VMEM, dgrad+wgrad
    share one read of the saved tensors).

    NCHW in/out (transposed to the kernel's NHWC inside the traced fn so
    XLA folds the relayout into its own layout assignment); weight OIHW.
    Running stats update exactly like npx.batch_norm.
    """
    from ..ops.pallas_conv_bwd import fused_cbr_train
    if interpret is None:
        from .. import runtime as _runtime
        interpret = _runtime.pallas_interpret()

    def fn(x_, w, g, b):
        xh = jnp.transpose(x_, (0, 2, 3, 1))          # NCHW -> NHWC
        wh = jnp.transpose(w, (2, 3, 1, 0))           # OIHW -> HWIO
        a, mean, var = fused_cbr_train(xh, wh, g, b, eps, interpret)
        return jnp.transpose(a, (0, 3, 1, 2)), mean, var

    out, mean, var = _invoke(fn, (x, weight, gamma, beta),
                             name="fused_conv_bn_relu")
    m = momentum
    running_mean._rebind(
        (m * running_mean._data
         + (1 - m) * lax.stop_gradient(mean._data)).astype(running_mean.dtype))
    running_var._rebind(
        (m * running_var._data
         + (1 - m) * lax.stop_gradient(var._data)).astype(running_var.dtype))
    return out


def layer_norm(data, gamma=None, beta=None, axis=-1, eps=1e-5):
    """Reference: src/operator/nn/layer_norm.cc."""
    def fn(x, g, b):
        mean = jnp.mean(x, axis=axis, keepdims=True)
        var = jnp.var(x, axis=axis, keepdims=True)
        out = (x - mean) * lax.rsqrt(var + eps)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        return out * g.reshape(shape) + b.reshape(shape)
    return _invoke(fn, (data, gamma, beta), name="layer_norm")


def rms_norm(data, gamma, eps=1e-5):
    """Root-mean-square norm over the last axis with a learned scale
    (Zhang & Sennrich 2019; no mean, no bias), computed in float32 and
    returned in the type it arrived in (under AMP it is an fp32 op and
    arrives in float32)."""
    def fn(x, g):
        xf = x.astype(jnp.float32)
        out = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (out * g.astype(jnp.float32)).astype(x.dtype)
    return _invoke(fn, (data, gamma), name="rms_norm")


def rotary_embedding(data, heads, theta=10000.0):
    """Rotary position embedding (Su et al. 2021) on (batch, seq,
    heads*dim), positions 0..seq-1, the whole head rotated, rotate-half
    convention: ``x * cos + concat(-x2, x1) * sin`` with ``x1, x2`` the
    halves of a head and angle ``pos * theta**(-2i/dim)`` for the pair
    ``i``.  Angles and products in float32."""
    def fn(x):
        b, s, hd = x.shape
        d = hd // heads
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        xf = x.astype(jnp.float32).reshape(b, s, heads, d)
        x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
        return out.reshape(b, s, hd).astype(x.dtype)
    return _invoke(fn, (data,), name="rotary_embedding")


def group_norm(data, gamma=None, beta=None, num_groups=1, eps=1e-5):
    """Reference: src/operator/nn/group_norm.cc (N, C, ...) layout."""
    def fn(x, g, b):
        n, c = x.shape[0], x.shape[1]
        xg = x.reshape((n, num_groups, c // num_groups) + x.shape[2:])
        red = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=red, keepdims=True)
        var = jnp.var(xg, axis=red, keepdims=True)
        out = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
        shape = [1, c] + [1] * (x.ndim - 2)
        return out * g.reshape(shape) + b.reshape(shape)
    return _invoke(fn, (data, gamma, beta), name="group_norm")


def instance_norm(data, gamma=None, beta=None, eps=1e-3):
    """Reference: src/operator/instance_norm.cc."""
    def fn(x, g, b):
        red = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=red, keepdims=True)
        var = jnp.var(x, axis=red, keepdims=True)
        out = (x - mean) * lax.rsqrt(var + eps)
        shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
        return out * g.reshape(shape) + b.reshape(shape)
    return _invoke(fn, (data, gamma, beta), name="instance_norm")


def l2_normalization(data, eps=1e-10, mode="instance"):
    def fn(x):
        if mode == "channel":
            norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True) + eps)
        elif mode == "spatial":
            norm = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(2, x.ndim)),
                                    keepdims=True) + eps)
        else:
            norm = jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1)
                            + eps).reshape((-1,) + (1,) * (x.ndim - 1))
        return x / norm
    return _invoke(fn, (data,), name="l2_normalization")


def dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False):
    """Reference: src/operator/nn/dropout.cc. Keys from mx.random's global
    threefry stream; identity outside autograd.train_mode."""
    from .. import autograd as _ag
    from .. import random as _r
    if p == 0:
        return data
    if mode != "always" and not _ag.is_training():
        return data
    key = _r._next_key()

    def fn(x):
        shape = list(x.shape)
        for ax in (axes or ()):
            shape[ax] = 1
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return _invoke(fn, (data,), name="dropout")


# ---------------------------------------------------------------------------
# embedding / indexing ops
# ---------------------------------------------------------------------------

def embedding(data, weight, input_dim=None, output_dim=None, dtype="float32",
              sparse_grad=False):
    """Reference: src/operator/tensor/indexing_op.cc (Embedding).

    ``sparse_grad=True`` records a backward that yields a
    ``RowSparseNDArray`` cotangent for the weight (the reference's
    row_sparse gradient path feeding lazy_update optimizers and kvstore
    row_sparse push) — O(batch) rows instead of an O(vocab) dense scatter.
    """
    idx = data._data if isinstance(data, ndarray) else jnp.asarray(data)
    if not sparse_grad:
        from ..ops.lookup import take_rows
        return _invoke(lambda w: take_rows(w, idx.astype(jnp.int32)),
                       (weight,), name="embedding")

    from .. import autograd as _ag
    from ..ndarray.sparse import RowSparseNDArray, dedupe_coo
    from ..numpy.multiarray import _wrap
    w_nd = weight if isinstance(weight, ndarray) else _wrap(jnp.asarray(weight))
    idx32 = idx.astype(jnp.int32)
    out = _wrap(jnp.take(w_nd._data, idx32, axis=0))
    if _ag.is_recording() and w_nd._entry is not None:
        vocab = int(w_nd.shape[0])

        def vjp_sparse(cots):
            dy = cots[0] if isinstance(cots, (tuple, list)) else cots
            dim = dy.shape[-1]
            flat_idx = idx32.reshape(-1)
            flat_dy = dy.reshape(-1, dim)
            uidx, uvals = dedupe_coo(flat_idx, flat_dy, vocab)
            return (RowSparseNDArray(_wrap(uvals), _wrap(uidx),
                                     (vocab, dim)),)

        _ag._record_op(vjp_sparse, [w_nd], [out], "embedding_sparse")
    return out


def one_hot(data, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    idx = data._data if isinstance(data, ndarray) else jnp.asarray(data)
    return _wrap_out(jax.nn.one_hot(idx, depth, dtype=np_dtype(dtype))
                     * (on_value - off_value) + off_value)


def pick(data, index, axis=-1, mode="clip", keepdims=False):
    """Reference: src/operator/tensor/broadcast_reduce_op_index.cc (pick)."""
    def fn(x, idx=None):
        i = (idx if idx is not None else
             (index._data if isinstance(index, ndarray) else jnp.asarray(index)))
        i = i.astype(jnp.int32)
        if mode == "clip":
            i = jnp.clip(i, 0, x.shape[axis] - 1)
        else:
            i = i % x.shape[axis]
        picked = jnp.take_along_axis(x, jnp.expand_dims(i, axis), axis)
        return picked if keepdims else jnp.squeeze(picked, axis)
    return _invoke(fn, (data,), name="pick")


def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """Reference: src/operator/tensor/ordering_op.cc."""
    def fn(x):
        xm = jnp.moveaxis(x, axis, -1)
        vals, idx = lax.top_k(-xm if is_ascend else xm, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis).astype(np_dtype(dtype))
        if ret_typ == "value":
            return vals
        if ret_typ == "both":
            return (vals, idx)
        return idx
    return _invoke(fn, (data,), name="topk")


def gather_nd(data, indices):
    idx = indices._data if isinstance(indices, ndarray) else jnp.asarray(indices)
    idx = tuple(idx.astype(jnp.int32))
    return _invoke(lambda x: x[idx], (data,), name="gather_nd")


def scatter_nd(data, indices, shape):
    idx = indices._data if isinstance(indices, ndarray) else jnp.asarray(indices)
    idx = tuple(idx.astype(jnp.int32))
    return _invoke(lambda d: jnp.zeros(shape, d.dtype).at[idx].add(d),
                   (data,), name="scatter_nd")


def index_update(data, indices, value):
    idx = indices._data if isinstance(indices, ndarray) else jnp.asarray(indices)
    idx = tuple(idx.astype(jnp.int32))
    return _invoke(lambda d, v: d.at[idx].set(v), (data, value))


def index_add(data, indices, value):
    idx = indices._data if isinstance(indices, ndarray) else jnp.asarray(indices)
    idx = tuple(idx.astype(jnp.int32))
    return _invoke(lambda d, v: d.at[idx].add(v), (data, value))


def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    """Reference: src/operator/sequence_mask.cc. axis is the sequence axis
    (0: (seq, batch, ...), 1: (batch, seq, ...))."""
    if not use_sequence_length or sequence_length is None:
        return data

    def fn(x, ln):
        pos = jnp.arange(x.shape[axis])
        if axis == 0:
            mask = pos[:, None] < ln[None, :]
        else:
            mask = pos[None, :] < ln[:, None]
        mask = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
        return jnp.where(mask, x, value)
    return _invoke(fn, (data, sequence_length), name="sequence_mask")


def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    def fn(x, ln=None):
        if ln is None:
            return jnp.take(x, -1, axis)
        idx = (ln - 1).astype(jnp.int32)
        xm = jnp.moveaxis(x, axis, 0)  # (seq, batch, ...)
        return jnp.take_along_axis(
            xm, idx.reshape((1, -1) + (1,) * (xm.ndim - 2)), 0)[0]
    if use_sequence_length and sequence_length is not None:
        return _invoke(fn, (data, sequence_length), name="sequence_last")
    return _invoke(fn, (data,), name="sequence_last")


def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    def fn(x, ln=None):
        if ln is None:
            return jnp.flip(x, axis)
        seq = x.shape[0]
        pos = jnp.arange(seq)[:, None]
        rev = jnp.where(pos < ln[None, :], ln[None, :] - 1 - pos, pos)
        return jnp.take_along_axis(
            x, rev.reshape(rev.shape + (1,) * (x.ndim - 2)).astype(jnp.int32), 0)
    if use_sequence_length and sequence_length is not None:
        return _invoke(fn, (data, sequence_length), name="sequence_reverse")
    return _invoke(fn, (data,), name="sequence_reverse")


def reshape_like(lhs, rhs):
    return _invoke(lambda a: jnp.reshape(a, rhs.shape), (lhs,), name="reshape_like")


def arange_like(data, start=0.0, step=1.0, repeat=1, ctx=None, axis=None):
    n = data.size if axis is None else data.shape[axis]
    return _wrap(jnp.arange(start, start + step * n, step, jnp.float32))


def broadcast_like(lhs, rhs, lhs_axes=None, rhs_axes=None):
    return _invoke(lambda a: jnp.broadcast_to(a, rhs.shape), (lhs,),
                   name="broadcast_like")


def slice(data, begin, end, step=None):  # noqa: A001 - reference op name
    import builtins
    step = step or (None,) * len(begin)
    key = tuple(builtins.slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[key]


def slice_axis(data, axis, begin, end):
    import builtins
    key = [builtins.slice(None)] * data.ndim
    key[axis] = builtins.slice(begin, end)
    return data[tuple(key)]


def slice_like(data, shape_like, axes=None):
    import builtins
    key = [builtins.slice(None)] * data.ndim
    for ax in (axes if axes is not None else range(data.ndim)):
        key[ax] = builtins.slice(0, shape_like.shape[ax])
    return data[tuple(key)]


def where(condition, x, y):
    return _invoke(jnp.where, (condition, x, y), name="where")


def erf(data):
    return _invoke(jax.scipy.special.erf, (data,))


def erfinv(data):
    return _invoke(jax.scipy.special.erfinv, (data,))


def gamma(data):
    return _invoke(lambda x: jnp.exp(jax.scipy.special.gammaln(x)), (data,))


def gammaln(data):
    return _invoke(jax.scipy.special.gammaln, (data,))


def digamma(data):
    return _invoke(jax.scipy.special.digamma, (data,))


def clip_global_norm(arrays, max_norm, check_isfinite=True):
    from ..gluon.utils import clip_global_norm as _cgn
    return _cgn(arrays, max_norm, check_isfinite)


# ---------------------------------------------------------------------------
# fused RNN op (reference: src/operator/rnn-inl.h:601-699, cuDNN fused path)
# ---------------------------------------------------------------------------

def rnn(data=None, parameters=None, state=None, state_cell=None, mode="lstm",
        state_size=None, num_layers=1, bidirectional=False, p=0.0,
        state_outputs=True, projection_size=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None, lstm_state_clip_nan=False,
        use_sequence_length=False, sequence_length=None):
    """Fused multi-layer RNN as lax.scan over time.

    data: (seq, batch, input). parameters: flat vector packed cuDNN-style
    (layer-major: [Wx, Wh, bx, bh] per layer-direction). Returns output
    (seq, batch, num_dir*state_size) and final states when state_outputs.
    """
    ngates = {"rnn_relu": 1, "rnn_tanh": 1, "gru": 3, "lstm": 4}[mode]
    ndir = 2 if bidirectional else 1
    input_size = data.shape[-1]

    # cuDNN packing: all weights layer-major first, then all biases
    # (rnn-inl.h GetRnnParamSize). Compute static slice offsets up front.
    w_slices, b_slices = [], []
    off = 0
    for layer in range(num_layers):
        cur_in = input_size if layer == 0 else state_size * ndir
        for _ in range(ndir):
            wx_n = ngates * state_size * cur_in
            wh_n = ngates * state_size * state_size
            w_slices.append((off, wx_n, cur_in, off + wx_n, wh_n))
            off += wx_n + wh_n
    for _ in range(num_layers * ndir):
        b_slices.append((off, off + ngates * state_size))
        off += 2 * ngates * state_size

    def cell_step(h, c, x, wx, wh, bx, bh):
        if mode == "gru":
            wxr, wxz, wxn = jnp.split(wx, 3, 0)
            whr, whz, whn = jnp.split(wh, 3, 0)
            bxr, bxz, bxn = jnp.split(bx, 3)
            bhr, bhz, bhn = jnp.split(bh, 3)
            r = jax.nn.sigmoid(x @ wxr.T + bxr + h @ whr.T + bhr)
            z = jax.nn.sigmoid(x @ wxz.T + bxz + h @ whz.T + bhz)
            n = jnp.tanh(x @ wxn.T + bxn + r * (h @ whn.T + bhn))
            return (1 - z) * n + z * h, None
        g = x @ wx.T + h @ wh.T + bx + bh
        if mode == "rnn_relu":
            return jax.nn.relu(g), None
        if mode == "rnn_tanh":
            return jnp.tanh(g), None
        i, f, g_, o = jnp.split(g, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        c_new = f * c + i * jnp.tanh(g_)
        if lstm_state_clip_min is not None:
            c_new = jnp.clip(c_new, lstm_state_clip_min, lstm_state_clip_max)
        return o * jnp.tanh(c_new), c_new

    def fn(x, params, h0, c0=None):
        outputs = x
        h_fin, c_fin = [], []
        for layer in range(num_layers):
            layer_outs = []
            for d in range(ndir):
                li = layer * ndir + d
                woff, wx_n, cur_in, hoff, wh_n = w_slices[li]
                wx = params[woff:woff + wx_n].reshape(ngates * state_size, cur_in)
                wh = params[hoff:hoff + wh_n].reshape(ngates * state_size, state_size)
                bxo, bho = b_slices[li]
                bx = params[bxo:bxo + ngates * state_size]
                bh = params[bho:bho + ngates * state_size]
                h = h0[li]
                c = c0[li] if c0 is not None else None
                xs = outputs if d == 0 else jnp.flip(outputs, 0)

                def step(carry, xt, wx=wx, wh=wh, bx=bx, bh=bh):
                    h_, c_ = carry
                    h2, c2 = cell_step(h_, c_, xt, wx, wh, bx, bh)
                    return (h2, c2 if c2 is not None else h2), h2

                (hT, cT), ys = lax.scan(step, (h, c if c is not None else h), xs)
                if d == 1:
                    ys = jnp.flip(ys, 0)
                layer_outs.append(ys)
                h_fin.append(hT)
                if mode == "lstm":
                    c_fin.append(cT)
            outputs = (jnp.concatenate(layer_outs, -1)
                       if ndir == 2 else layer_outs[0])
        hT = jnp.stack(h_fin)
        if mode == "lstm":
            return outputs, hT, jnp.stack(c_fin)
        return outputs, hT

    args = ((data, parameters, state) if mode != "lstm"
            else (data, parameters, state, state_cell))
    res = _invoke(fn, args, name=f"rnn:{mode}")
    if state_outputs:
        return res
    return res[0]


# ---------------------------------------------------------------------------
# multi-head attention ops (reference: src/operator/contrib/transformer.cc:675-828)
# ---------------------------------------------------------------------------

def interleaved_matmul_selfatt_qk(queries_keys_values, heads):
    """scores = Q @ K^T from interleaved QKV (seq, batch, 3*heads*dim).

    Reference: _contrib_interleaved_matmul_selfatt_qk (transformer.cc:675).
    Output: (batch*heads, seq, seq), scaled by 1/sqrt(dim).
    """
    def fn(qkv):
        seq, batch, three_hd = qkv.shape
        dim = three_hd // (3 * heads)
        x = qkv.reshape(seq, batch, heads, 3, dim)
        q = x[..., 0, :].transpose(1, 2, 0, 3).reshape(batch * heads, seq, dim)
        k = x[..., 1, :].transpose(1, 2, 0, 3).reshape(batch * heads, seq, dim)
        return jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(dim).astype(qkv.dtype)
    return _invoke(fn, (queries_keys_values,), name="interleaved_matmul_selfatt_qk")


def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads):
    """out = att @ V, back to (seq, batch, heads*dim).

    Reference: _contrib_interleaved_matmul_selfatt_valatt (transformer.cc:715).
    """
    def fn(qkv, att):
        seq, batch, three_hd = qkv.shape
        dim = three_hd // (3 * heads)
        v = qkv.reshape(seq, batch, heads, 3, dim)[..., 2, :]
        v = v.transpose(1, 2, 0, 3).reshape(batch * heads, seq, dim)
        out = jnp.einsum("bqk,bkd->bqd", att, v)
        return out.reshape(batch, heads, seq, dim).transpose(2, 0, 1, 3) \
            .reshape(seq, batch, heads * dim)
    return _invoke(fn, (queries_keys_values, attention),
                   name="interleaved_matmul_selfatt_valatt")


def interleaved_matmul_encdec_qk(queries, keys_values, heads):
    """Reference: _contrib_interleaved_matmul_encdec_qk (transformer.cc:752)."""
    def fn(q, kv):
        qlen, batch, hd = q.shape
        dim = hd // heads
        klen = kv.shape[0]
        qh = q.reshape(qlen, batch, heads, dim).transpose(1, 2, 0, 3) \
            .reshape(batch * heads, qlen, dim)
        k = kv.reshape(klen, batch, heads, 2, dim)[..., 0, :] \
            .transpose(1, 2, 0, 3).reshape(batch * heads, klen, dim)
        return jnp.einsum("bqd,bkd->bqk", qh, k) / jnp.sqrt(dim).astype(q.dtype)
    return _invoke(fn, (queries, keys_values), name="interleaved_matmul_encdec_qk")


def interleaved_matmul_encdec_valatt(keys_values, attention, heads):
    """Reference: _contrib_interleaved_matmul_encdec_valatt (transformer.cc:795)."""
    def fn(kv, att):
        klen, batch, two_hd = kv.shape
        dim = two_hd // (2 * heads)
        v = kv.reshape(klen, batch, heads, 2, dim)[..., 1, :] \
            .transpose(1, 2, 0, 3).reshape(batch * heads, klen, dim)
        out = jnp.einsum("bqk,bkd->bqd", att, v)
        qlen = att.shape[1]
        return out.reshape(batch, heads, qlen, dim).transpose(2, 0, 1, 3) \
            .reshape(qlen, batch, heads * dim)
    return _invoke(fn, (keys_values, attention),
                   name="interleaved_matmul_encdec_valatt")


def multi_head_attention(query, key, value, heads, mask=None, dropout_p=0.0,
                         causal=False):
    """Batch-first fused attention: (batch, seq, heads*dim) -> same.

    TPU-native addition: routes to the Pallas flash-attention kernel when
    available (mxnet_tpu.ops.pallas.flash_attention), else an XLA dot_general
    composition.
    """
    from ..ops import attention as _att
    return _att.multi_head_attention(query, key, value, heads, mask=mask,
                                     dropout_p=dropout_p, causal=causal)


# ---------------------------------------------------------------------------
# control flow (reference: src/operator/npx_control_flow.cc:1149-1318)
# ---------------------------------------------------------------------------

def foreach(body, data, init_states):
    """npx.foreach: scan body over axis 0 of data (subgraph op analog).

    body(data_slice, states) -> (out, new_states). Under autograd.record
    the loop runs eagerly with per-op recording — gradients flow to data,
    states AND parameters the body closes over, exactly like the
    reference's contrib.foreach imperative path. Outside recording (and
    inside hybridize/jit traces) it lowers to ONE lax.scan.
    """
    from .. import autograd as _ag
    from ..numpy.multiarray import _wrap
    from .. import numpy as _np
    single_data = isinstance(data, ndarray)
    single_state = isinstance(init_states, ndarray)

    length = (data.shape[0] if single_data else data[0].shape[0])
    if _ag.is_recording() and length > 0:
        # eager recorded loop (reference: contrib/control_flow foreach);
        # length 0 falls through to the scan path, whose empty (0, ...)
        # outputs match the non-recorded behavior
        states = init_states
        outs = []
        for t in range(length):
            x_t = data[t] if single_data else [d[t] for d in data]
            out, states = body(x_t, states)
            outs.append(out)
        if isinstance(outs[0], ndarray):
            stacked = _np.stack(outs)
        else:
            stacked = [_np.stack([o[i] for o in outs])
                       for i in range(len(outs[0]))]
        return stacked, states

    def fn(xs_raw, carry0):
        def scan_body(carry, x_raw):
            st = (_wrap(carry) if single_state
                  else [_wrap(c) for c in carry])
            xin = _wrap(x_raw) if single_data else [_wrap(r) for r in x_raw]
            out, new_st = body(xin, st)
            out_raw = (out._data if isinstance(out, ndarray)
                       else [o._data for o in out])
            new_raw = (new_st._data if isinstance(new_st, ndarray)
                       else [s._data for s in new_st])
            return new_raw, out_raw

        final, outs = lax.scan(scan_body, carry0, xs_raw)
        return outs, final

    xs_arg = data if single_data else list(data)
    st_arg = init_states if single_state else list(init_states)
    outs_w, final_w = _invoke(fn, (xs_arg, st_arg), name="foreach")
    return outs_w, final_w


def while_loop(cond, func, loop_vars, max_iterations=None):
    """npx.while_loop analog; eager python loop (matches reference dynamic
    semantics; use lax.while_loop directly for jit paths)."""
    steps = 0
    outputs = []
    vars_ = list(loop_vars)
    while bool(cond(*vars_)) and (max_iterations is None or steps < max_iterations):
        out, vars_ = func(*vars_)
        outputs.append(out)
        vars_ = list(vars_) if isinstance(vars_, (list, tuple)) else [vars_]
        steps += 1
    from .. import numpy as _np
    stacked = (_np.stack(outputs) if outputs and isinstance(outputs[0], ndarray)
               else outputs)
    return stacked, vars_


def cond(pred, then_func, else_func, inputs=None):
    """npx.cond analog."""
    if inputs is None:
        inputs = []
    if bool(pred(*inputs) if callable(pred) else pred):
        return then_func(*inputs)
    return else_func(*inputs)


# ---------------------------------------------------------------------------
# save / load (reference: npx.save/load over src/serialization/cnpy.cc)
# ---------------------------------------------------------------------------

def save(file, arr_dict):
    """Save dict of arrays as .npz (reference: cnpy zip-of-npy)."""
    import numpy as onp
    if isinstance(arr_dict, ndarray):
        arr_dict = {"arr_0": arr_dict}
    if isinstance(arr_dict, (list, tuple)):
        arr_dict = {f"arr_{i}": a for i, a in enumerate(arr_dict)}
    onp.savez(file, **{k: v.asnumpy() if isinstance(v, ndarray) else onp.asarray(v)
                       for k, v in arr_dict.items()})


def load(file):
    import numpy as onp
    from ..numpy import array
    with onp.load(file, allow_pickle=False) as data:
        return {k: array(data[k]) for k in data.files}


def softmax_cross_entropy(data, label, sparse_label=True, axis=-1):
    """Reference: src/operator/loss_binary_op.cc softmax_cross_entropy —
    scalar sum of -log softmax(data)[label]. The sparse path routes
    through the fused logsumexp-minus-pick op (ops/xent.py), which never
    materializes an (N, V) float32 log-softmax."""
    from ..ops.xent import sparse_softmax_xent

    if sparse_label:
        # own dispatch name: amp lists "softmax_cross_entropy" as FP32,
        # which would cast and re-materialize the (N, V) array the fused
        # op avoids (it accumulates in f32 internally already)
        return _invoke(lambda x, l: sparse_softmax_xent(x, l, axis).sum(),
                       (data, label), name="sparse_softmax_xent")
    return _invoke(lambda x, l: -(l * jax.nn.log_softmax(x, axis)).sum(),
                   (data, label), name="softmax_cross_entropy")


def smooth_l1(data, scalar=1.0):
    def fn(x):
        s2 = scalar * scalar
        return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x,
                         jnp.abs(x) - 0.5 / s2)
    return _invoke(fn, (data,), name="smooth_l1")


def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False,
              forward_stype=None):
    """Reference: src/operator/tensor/dot.cc batch_dot — (b, m, k) x
    (b, k, n) batched matmul, the building block the reference's attention
    ops are made of; lowers to one MXU dot_general."""
    def fn(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)
    return _invoke(fn, (lhs, rhs), name="batch_dot")


def reshape(data, newshape, reverse=False, order="C"):
    """Reference: _npx_reshape (src/operator/numpy/np_matrix_op.cc) with
    MXNet's special codes: -1 infer, -2 copy remaining dims, -3 merge two
    consecutive dims, -4 split a dim (followed by the two factors), 0 keep."""
    def fn(x):
        shape = list(newshape) if isinstance(newshape, (list, tuple)) \
            else [newshape]
        src = list(x.shape)
        out, si, i = [], 0, 0
        while i < len(shape):
            s = shape[i]
            if s == 0:
                out.append(src[si]); si += 1
            elif s == -1:
                out.append(-1); si += 1
            elif s == -2:
                out.extend(src[si:]); si = len(src)
            elif s == -3:
                out.append(src[si] * src[si + 1]); si += 2
            elif s == -4:
                f1, f2 = shape[i + 1], shape[i + 2]
                d = src[si]
                if f1 == -1:
                    f1 = d // f2
                if f2 == -1:
                    f2 = d // f1
                out.extend([f1, f2]); si += 1; i += 2
            else:
                out.append(s); si += 1
            i += 1
        return jnp.reshape(x, tuple(out))
    return _invoke(fn, (data,), name="npx_reshape")


def constraint_check(data, msg="Constraint violated!"):
    """Reference: _npx_constraint_check (src/operator/numpy/
    np_constraint_check.cc): all(data) must hold; used by
    gluon.probability distributions. Functional form: returns True and
    raises at sync time via checkify-style where supported; eager path
    checks immediately."""
    def fn(x):
        return jnp.all(x)
    out = _invoke(fn, (data,), name="constraint_check")
    try:
        ok = bool(out.asnumpy())
        if not ok:
            raise ValueError(msg)
    except (ValueError, TypeError) as e:
        if isinstance(e, ValueError) and str(e) == msg:
            raise
        # traced (inside jit): defer — return the boolean for lax.cond use
    return out


def amp_cast(data, dtype=None):
    """Reference: amp_cast op (src/operator/tensor/amp_cast.cc) — dtype
    cast inserted by the AMP graph rewrite (amp.convert_symbol).  Like
    the reference op, non-floating inputs pass through unchanged (an AMP
    rewrite must not alter integer/bool semantics)."""
    dt = np_dtype(dtype)

    def fn(x):
        if not (jnp.issubdtype(x.dtype, jnp.floating)
                or x.dtype == jnp.bfloat16):
            return x
        return x.astype(dt) if x.dtype != dt else x
    return _invoke(fn, (data,), name="amp_cast")


def amp_multicast(*data, num_outputs=None):
    """Reference: amp_multicast — cast all inputs to the widest
    *floating* dtype among them (integer inputs never win, so float data
    is not truncated)."""
    import numpy as onp
    widest = None
    for d in data:
        dt = onp.dtype(str(d.dtype))
        if dt.kind != "f" and str(dt) != "bfloat16":
            continue
        if widest is None or dt.itemsize > widest.itemsize:
            widest = dt
    if widest is None:
        return tuple(data)
    return tuple(amp_cast(d, dtype=widest) for d in data)


from ..ops.quantization import (  # noqa: E402
    quantize_v2, dequantize, quantized_fully_connected, quantized_conv,
    quantized_dense_fused, quantized_conv_fused, fp8_dense_fused)
from ..ops.bbox import (  # noqa: E402
    box_iou, box_nms, box_encode, box_decode, bipartite_matching)
from ..ops.multibox import (  # noqa: E402
    multibox_prior, multibox_target, multibox_detection)


def nonzero(data):
    """Reference: _npx_nonzero — returns (N, ndim) int64 indices (unlike
    np.nonzero's tuple). Eager-only (data-dependent shape)."""
    import numpy as onp
    arr = data.asnumpy() if hasattr(data, "asnumpy") else onp.asarray(data)
    idx = onp.argwhere(arr)
    from ..numpy.multiarray import array as _array
    return _array(idx.astype("int64"))


from . import image  # noqa: E402,F401  (npx.image.* operator namespace)


def savez(file, *args, **kwargs):
    """Save arrays to .npz (reference numpy_extension/utils.py savez over
    cnpy): positional arrays become arr_0.. keys, keyword arrays keep
    their names."""
    merged = {f"arr_{i}": a for i, a in enumerate(args)}
    clash = sorted(set(merged) & set(kwargs))
    if clash:  # numpy.savez raises for exactly this
        raise MXNetError(
            f"cannot use un-named arrays with keyword(s) {clash}; "
            "rename the keyword or name every array")
    merged.update(kwargs)
    save(file, merged)


def seed(seed, ctx="all"):  # noqa: A002,ARG001 — parity signature
    """Seed the global RNG stream (reference numpy_extension/random.py:27
    — per-ctx seeding collapses to one splittable key stream here)."""
    from .. import random as _random
    _random.seed(seed)


def bernoulli(prob=None, logit=None, size=None, dtype=None, ctx=None,
              out=None):
    """Binary samples from probs or logits, exactly one given
    (reference numpy_extension/random.py:77). Hardened front door over
    mx.np.random.bernoulli: validates the prob/logit exclusivity and
    dispatches through _invoke (async + autograd-recorded)."""
    from .. import random as _r
    from ..numpy.multiarray import _invoke, _writeback

    if (prob is None) == (logit is None):
        raise MXNetError("pass exactly one of prob or logit")
    key = _r._next_key()

    def fn(p_or_l):
        p = jax.nn.sigmoid(p_or_l) if prob is None else p_or_l
        shape = jnp.shape(p) if size is None else size
        s = jax.random.bernoulli(key, p, shape)
        return s.astype(dtype or "float32")

    res = _invoke(fn, (prob if logit is None else logit,),
                  name="bernoulli")
    return _writeback(out, res)


def _sample_n(name, draw, a, b, batch_shape, dtype):
    """Shared sample_n scaffold: output shape is batch_shape PREPENDED to
    broadcast(a, b).shape (reference numpy_extension/random.py:130,187);
    64-bit dtypes run under the scoped x64 mode like every other op."""
    from .. import random as _r
    from ..numpy.multiarray import _invoke, _wants_x64
    from ..numpy.random import _shape

    key = _r._next_key()
    bshape = _shape(batch_shape)
    dt = dtype or "float32"

    def fn(a_, b_):
        pshape = jnp.broadcast_shapes(jnp.shape(a_), jnp.shape(b_))
        return draw(key, bshape + pshape, jnp.dtype(dt), a_, b_)

    return _invoke(fn, (a, b), name=name, x64=_wants_x64(dt))


def uniform_n(low=0.0, high=1.0, batch_shape=None, dtype=None, ctx=None):
    """Uniform samples of shape batch_shape + broadcast(low, high).shape
    (reference numpy_extension/random.py:130)."""
    return _sample_n(
        "uniform_n",
        lambda key, shape, dt, lo, hi:
            lo + jax.random.uniform(key, shape, dt) * (hi - lo),
        low, high, batch_shape, dtype)


def normal_n(loc=0.0, scale=1.0, batch_shape=None, dtype=None, ctx=None):
    """Normal samples of shape batch_shape + broadcast(loc, scale).shape
    (reference numpy_extension/random.py:187)."""
    return _sample_n(
        "normal_n",
        lambda key, shape, dt, mu, sigma:
            mu + sigma * jax.random.normal(key, shape, dt),
        loc, scale, batch_shape, dtype)


from . import random  # noqa: E402,F401 — npx.random submodule (must
# import after the sampler defs above; reference exposes both spellings)


def rsqrt(data):
    """1/sqrt (reference: src/operator/tensor/elemwise_unary_op_pow.cc
    rsqrt) — lax has the fused primitive."""
    return _invoke(lax.rsqrt, (data,), name="rsqrt")


def rcbrt(data):
    """1/cbrt (reference: elemwise_unary_op_pow.cc rcbrt)."""
    return _invoke(lambda x: 1.0 / jnp.cbrt(x), (data,), name="rcbrt")


def shape_array(data):
    """Shape as an int64 host-meaning array (reference:
    src/operator/tensor/matrix_op.cc shape_array; shapes are static
    under XLA so this is a constant)."""
    return _invoke(
        lambda x: jnp.asarray(jnp.shape(x), jnp.int64)
        if jax.config.read("jax_enable_x64")
        else jnp.asarray(jnp.shape(x), jnp.int32),
        (data,), name="shape_array")


def size_array(data):
    """Total element count as a 1-element array (reference:
    matrix_op.cc size_array)."""
    return _invoke(lambda x: jnp.asarray([x.size], jnp.int32), (data,),
                   name="size_array")


def split_v2(data, indices_or_sections, axis=0, squeeze_axis=False):
    """np.split with the reference's squeeze_axis flag (reference:
    matrix_op.cc _split_v2)."""
    def fn(x):
        parts = jnp.split(x, indices_or_sections, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return tuple(parts)
    return _invoke(fn, (data,), name="split_v2")


def space_to_depth(data, block_size):
    """NCHW (N,C,H,W) -> (N, C*b*b, H/b, W/b) (reference:
    src/operator/tensor/matrix_op.cc space_to_depth, DCR mode)."""
    b = int(block_size)

    def fn(x):
        n, c, h, w = x.shape
        if h % b or w % b:
            raise MXNetError(
                f"H and W must be divisible by block_size {b}, "
                f"got H={h} W={w}")
        x = x.reshape(n, c, h // b, b, w // b, b)
        x = x.transpose(0, 3, 5, 1, 2, 4)
        return x.reshape(n, c * b * b, h // b, w // b)
    return _invoke(fn, (data,), name="space_to_depth")


def depth_to_space(data, block_size):
    """Inverse of space_to_depth (reference: matrix_op.cc
    depth_to_space)."""
    b = int(block_size)

    def fn(x):
        n, c, h, w = x.shape
        if c % (b * b):
            raise MXNetError(
                f"C must be divisible by block_size^2 = {b * b}, got C={c}")
        x = x.reshape(n, b, b, c // (b * b), h, w)
        x = x.transpose(0, 3, 4, 1, 5, 2)
        return x.reshape(n, c // (b * b), h * b, w * b)
    return _invoke(fn, (data,), name="depth_to_space")
