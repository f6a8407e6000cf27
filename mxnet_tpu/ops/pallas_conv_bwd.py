"""Fused conv3x3 + BatchNorm + ReLU backward — Pallas TPU mega-kernel.

Round-3 profiling localized the ResNet-50 training
wall: backward convs sit AT the HBM roofline because the standard
decomposition reads the conv-output cotangent dy three times (BN-backward
reductions, dgrad, wgrad) and materializes it once. This kernel changes the
decomposition for the hot 3x3 / stride-1 / SAME blocks:

  XLA baseline per layer (big-tensor passes):
     stats:  R(da) R(y)            (fused dz + reductions)
     dy:     R(da) R(y) W(dy)
     dgrad:  R(dy)         W(dx)
     wgrad:  R(dy) R(x)    W(dw)       => 7 reads + 2 big writes
  here:
     stats:  R(da) R(y)            (XLA, one fused pass)
     kernel: R(da) R(y) R(x) W(dx)     (dy recomputed in VMEM, never
                                         materialized; dgrad + wgrad both
                                         consume the same VMEM tiles)
                                        => 5 reads + 1 big write  (~33% less)

Layout: NHWC with C on lanes (MXU-native). The convolutions become 9
shifted (M, O) x (O, C) / (C, M) x (M, O) MXU dots over spatially
zero-padded VMEM scratch — the standard Pallas conv formulation
(pallas_guide.md: Grid/BlockSpec + scratch patterns).

Reference parity: replaces the backward of src/operator/nn/convolution.cc +
batch_norm.cc + activation.cc for this shape class; forward is unchanged
(XLA's conv is already MXU-optimal there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bwd_kernel(vec_ref, da_ref, y_ref, x_ref, wf_ref, dx_ref, dw_ref,
                dyp_ref, xp_ref, *, H, W, C, O, NB):
    """One grid step: NB images. Recompute dy in VMEM, emit dx and
    accumulate dw.

    vec: (8, O) f32 rows = [mu, inv, gamma, beta, c1, c2, s1, 0]
    da/y: (NB, H, W, O); x: (NB, H, W, C); wf: (9*O, C) flipped weights
    dx: (NB, H, W, C); dw out: (9*C, O) f32, constant index map — the block
    stays VMEM-resident across the sequential grid and is accumulated in
    place (standard Pallas reduction pattern).
    scratch: dyp (NB, H+2, W+2, O), xp (NB, H+2, W+2, C).
    """
    step = pl.program_id(0)
    mu = vec_ref[0, :]
    inv = vec_ref[1, :]
    gamma = vec_ref[2, :]
    beta = vec_ref[3, :]
    c1 = vec_ref[4, :]
    c2 = vec_ref[5, :]
    s1 = vec_ref[6, :]

    da = da_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    xhat = (y - mu) * inv
    mask = (gamma * xhat + beta) > 0.0
    dz = jnp.where(mask, da, 0.0)
    dy = (s1 * (dz - c1 - xhat * c2)).astype(da_ref.dtype)

    # zero-padded copies in VMEM (SAME padding for both convolutions)
    dyp_ref[:] = jnp.zeros_like(dyp_ref)
    xp_ref[:] = jnp.zeros_like(xp_ref)
    dyp_ref[:, 1:H + 1, 1:W + 1, :] = dy
    xp_ref[:, 1:H + 1, 1:W + 1, :] = x_ref[:]

    @pl.when(step == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    M = NB * H * W
    acc = jnp.zeros((M, C), jnp.float32)
    dyf = dy.reshape(M, O)
    for kh in range(3):
        for kw in range(3):
            k = kh * 3 + kw
            # dgrad: dx = sum_k shift_k(dy) @ wflip_k   ((M,O) x (O,C))
            dsh = dyp_ref[:, kh:kh + H, kw:kw + W, :].reshape(M, O)
            acc += jax.lax.dot_general(
                dsh, wf_ref[k * O:(k + 1) * O, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # wgrad: dw_k = shift_k(x)^T @ dy            ((C,M) x (M,O))
            xsh = xp_ref[:, kh:kh + H, kw:kw + W, :].reshape(M, C)
            dw_ref[k * C:(k + 1) * C, :] += jax.lax.dot_general(
                xsh, dyf, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    dx_ref[:] = acc.reshape(NB, H, W, C).astype(dx_ref.dtype)


def fused_conv3x3_bn_relu_bwd(da, x, y, w, gamma, beta, mean, var,
                              eps=1e-5, interpret=False):
    """Backward of relu(bn(conv3x3_s1_same(x, w))) through batch statistics.

    da, x, y: (N, H, W, C_in/out) NHWC; w: (3, 3, C, O) HWIO.
    Returns (dx, dw, dgamma, dbeta). dgamma/dbeta are the BN parameter
    grads; dx/dw come from the Pallas kernel with dy recomputed in VMEM.
    """
    N, H, W, O = da.shape
    C = x.shape[-1]
    M = N * H * W

    # ---- stats pass (XLA: one fused read of da, y) -----------------------
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    daf = da.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xhat = (yf - mean.astype(jnp.float32)) * inv
    mask = (gamma.astype(jnp.float32) * xhat + beta.astype(jnp.float32)) > 0
    dz = jnp.where(mask, daf, 0.0)
    dbeta = jnp.sum(dz, axis=(0, 1, 2))
    dgamma = jnp.sum(dz * xhat, axis=(0, 1, 2))

    gf = gamma.astype(jnp.float32)
    vec = jnp.stack([
        mean.astype(jnp.float32), inv, gf, beta.astype(jnp.float32),
        dbeta / M, dgamma / M, gf * inv,
        jnp.zeros_like(inv)])                                  # (8, O)

    # flipped weights for dgrad: wf[kh,kw] = w[2-kh, 2-kw].T  (O, C)
    wf = jnp.flip(w, axis=(0, 1)).transpose(0, 1, 3, 2).reshape(9 * O, C)

    # pick NB so each grid step has >=256 spatial rows for the MXU
    NB = 1
    while NB < N and NB * H * W < 256:
        NB *= 2
    while N % NB:
        NB //= 2
    grid = N // NB

    kernel = functools.partial(_bwd_kernel, H=H, W=W, C=C, O=O, NB=NB)
    dx, dw9 = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((8, O), lambda i: (0, 0)),
            pl.BlockSpec((NB, H, W, O), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((NB, H, W, O), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((NB, H, W, C), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * O, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((NB, H, W, C), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * C, O), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, W, C), x.dtype),
            jax.ShapeDtypeStruct((9 * C, O), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((NB, H + 2, W + 2, O), da.dtype),
            pltpu.VMEM((NB, H + 2, W + 2, C), x.dtype),
        ],
        interpret=interpret,
        name="mx_conv3x3_bwd",
    )(vec, da, y, x, wf)

    dw = dw9.reshape(3, 3, C, O).astype(w.dtype)
    return dx, dw, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype)


def conv3x3_bn_relu_ref(x, w, gamma, beta, eps=1e-5):
    """Reference forward (training-mode BN over batch statistics), used by
    the oracle tests and as the residual-producing forward."""
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    yf = y.astype(jnp.float32)
    mean = jnp.mean(yf, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(yf - mean), axis=(0, 1, 2))
    inv = jax.lax.rsqrt(var + eps)
    z = (yf - mean) * inv * gamma.astype(jnp.float32) \
        + beta.astype(jnp.float32)
    return jax.nn.relu(z).astype(x.dtype), y, mean, var


# ---------------------------------------------------------------------------
# custom-VJP composite: forward stays XLA, backward is the Pallas kernel
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_cbr_train(x, w, gamma, beta, eps=1e-5, interpret=False):
    """relu(bn_train(conv3x3_s1_same(x, w))) over NHWC.

    Returns (activation, batch_mean, batch_var); mean/var feed the
    running-stat update (stop-gradient there — their cotangents are
    discarded in the bwd rule, matching the reference's BN aux semantics).
    """
    a, _y, mean, var = conv3x3_bn_relu_ref(x, w, gamma, beta, eps)
    return a, mean, var


def _fused_cbr_fwd(x, w, gamma, beta, eps, interpret):
    a, y, mean, var = conv3x3_bn_relu_ref(x, w, gamma, beta, eps)
    return (a, mean, var), (x, w, gamma, beta, y, mean, var)


def _fused_cbr_bwd(eps, interpret, res, cts):
    da, _dmean, _dvar = cts   # mean/var only feed stop-gradient stat updates
    x, w, gamma, beta, y, mean, var = res
    dx, dw, dgamma, dbeta = fused_conv3x3_bn_relu_bwd(
        da, x, y, w, gamma, beta, mean, var, eps=eps, interpret=interpret)
    return dx, dw, dgamma, dbeta


fused_cbr_train.defvjp(_fused_cbr_fwd, _fused_cbr_bwd)


def eligible(kernel, strides, padding, dilation, groups, use_bias):
    """Shape class the kernel covers: 3x3, stride 1, SAME, dense, no bias."""
    return (tuple(kernel) == (3, 3) and tuple(strides) == (1, 1)
            and tuple(padding) == (1, 1) and tuple(dilation) == (1, 1)
            and groups == 1 and not use_bias)


def fits_vmem(n, h, w, c, o, itemsize=2, budget=12 * 2 ** 20):
    """Conservative VMEM estimate for one grid step (incl. the double
    buffering Pallas adds for HBM<->VMEM pipelining). Over-budget shapes
    (e.g. the 512-channel 7x7 stage, dominated by the 9*C*O f32 dw block)
    fall back to XLA — which handles that compute-dense stage well; the
    kernel's bandwidth win lives in the high-spatial stages anyway."""
    nb = 1
    while nb < n and nb * h * w < 256:
        nb *= 2
    while n % nb:
        nb //= 2
    m = nb * h * w
    blocks = nb * h * w * (2 * o + 2 * c) * itemsize      # da, y, x, dx
    halo = nb * (h + 2) * (w + 2) * (o + c) * itemsize    # dyp, xp scratch
    weights = 9 * o * c * itemsize + 9 * c * o * 4        # wf + dw (f32)
    live = m * c * 4 + m * o * itemsize                   # acc + dy flat
    return 2 * blocks + halo + weights + live <= budget
