"""A selective state-space mixer's three device functions (Mamba-2).

Raw ``jax`` arrays in, raw arrays out; ``nn.Mamba2Mixer`` composes them.

* ``ssd_scan``: per head ``h`` with a state ``S`` of ``(P, N)``,
  ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t`` from ``S_0 = 0``,
  ``y_t = S_t C_t + D_h x_t``; head ``h`` reads B / C group
  ``h // (H / G)``.  Computed in chunks of ``chunk`` tokens (the
  "state-space duality" form of the Mamba-2 paper): inside a chunk the
  recurrence is a ``(chunk, chunk)`` lower-triangular product a head,
  ``y_q += sum_{k<=q} (C_q . B_k) exp(a_q - a_k) dt_k x_k`` with ``a`` the
  running sum of ``dt A``; a chunk's contribution to the state at its end
  is one more product; the state is carried from chunk to chunk by a
  ``lax.scan`` over ``seq / chunk`` steps; and what the entering state
  adds to a chunk's outputs is a last product.  The largest array is
  ``(heads, seq, chunk)``: nothing is ``(heads, seq, seq)``, and
  ``chunk`` changes no value.
* ``causal_conv1d``: depthwise, ``kernel`` taps to the left with a bias,
  as shifted multiply-adds (optionally through SiLU).
* ``gated_rms_norm``: ``RMSNorm(y * silu(z)) * w`` over groups of
  channels: the gate first, then the norm.

**What is float32**: the log-decays ``dt A``, their running sums, every
exponential of them, the state carried between chunks, the
accumulation of every product, the convolution's sums and the norm.
The four products' operands (``C . B``, the triangular product, the
chunk's state, the state's output) take the type ``x`` arrives in —
bfloat16 under ``mx.amp``, as a ``Dense``'s do.

**The backward pass** is autodiff of the chunked form, with each of the
three functions under ``jax.checkpoint``: nothing a function computes
inside is kept for its backward pass — not the ``(heads, seq, chunk)``
decays, not the chunk states — only its arguments, and the function is
made again when its gradient is taken (a third more scan time for
~0.7 GB a layer at 8192 tokens).  ``docs/STATE_SPACE.md`` has the
equations and the accounting.

A sequence ``chunk`` does not divide is padded at its end with ``dt = 0``
and ``x = 0``: a padded step decays nothing and adds nothing, and its
output row is dropped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry

_F32 = jnp.float32


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _ssd_chunked(x, dt, a_head, b_mat, c_mat, d_skip, chunk):
    with jax.named_scope("mx.ssm.scan"):
        batch, seq, heads, dim = x.shape
        groups, state = b_mat.shape[2:]
        per = heads // groups
        dtype = x.dtype
        pad = -seq % chunk
        if pad:
            x, dt, b_mat, c_mat = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (x, dt, b_mat, c_mat))
        n = (seq + pad) // chunk
        xs = x.reshape(batch, n, chunk, groups, per, dim)
        xf = xs.astype(_F32)
        bs = b_mat.reshape(batch, n, chunk, groups, state)
        cs = c_mat.reshape(batch, n, chunk, groups, state)
        dts = dt.astype(_F32).reshape(batch, n, chunk, groups, per)
        # running sums of the log-decay inside a chunk, head-major so
        # that the (chunk, chunk) decays tile the lanes: (b, n, G, R, Q)
        cum = jnp.cumsum(
            (dts * a_head.astype(_F32).reshape(groups, per))
            .transpose(0, 1, 3, 4, 2), axis=-1)

        # inside a chunk: query q reads key k <= q through exp(a_q - a_k)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        cb = jnp.einsum("bnqgs,bnkgs->bngqk", cs, bs,
                        preferred_element_type=_F32)
        xdt = (xf * dts[..., None]).astype(dtype)
        y = jnp.einsum("bngrqk,bnkgrp->bnqgrp",
                       (cb[:, :, :, None] * decay).astype(dtype), xdt,
                       preferred_element_type=_F32)

        # a chunk's own contribution to the state at its end, and the
        # decay over the whole chunk
        to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)
        local = jnp.einsum(
            "bnkgs,bnkgrp->bngrps", bs,
            (xf * (dts * to_end)[..., None]).astype(dtype),
            preferred_element_type=_F32)
        whole = jnp.exp(cum[..., -1])

        def carry(s, chunk_):
            keep, add = chunk_
            return keep[..., None, None] * s + add, s

        _, entering = jax.lax.scan(
            carry, jnp.zeros(local.shape[:1] + local.shape[2:], _F32),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)

        # what the entering state adds to each output of the chunk
        y = y + jnp.einsum("bnqgs,bngrps->bnqgrp", cs,
                           entering.astype(dtype),
                           preferred_element_type=_F32) \
            * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
        y = y + d_skip.astype(_F32).reshape(groups, per, 1) * xf
        return y.reshape(batch, seq + pad, heads, dim)[:, :seq].astype(dtype)


def ssd_scan(x, dt, a_head, b_mat, c_mat, d_skip, chunk=128):
    """The state-space scan: ``x (b, s, H, P)``, ``dt (b, s, H)`` the
    step sizes (positive, after their softplus), ``a_head (H,)`` the
    negative decay rate a head, ``b_mat`` / ``c_mat (b, s, G, N)``,
    ``d_skip (H,)`` -> ``y (b, s, H, P)`` in ``x``'s type."""
    heads, groups = x.shape[2], b_mat.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} heads do not group over {groups} "
                         "B / C groups")
    if _telemetry._active:
        _telemetry.inc("ssm.scan_tokens_total", x.shape[0] * x.shape[1])
        _telemetry.inc("ssm.scan_chunks_total",
                       x.shape[0] * -(-x.shape[1] // chunk) * heads)
    return _ssd_chunked(x, dt, a_head, b_mat, c_mat, d_skip, int(chunk))


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _conv(x, weight, bias, silu):
    with jax.named_scope("mx.ssm.conv"):
        seq, taps = x.shape[1], weight.shape[1]
        padded = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
        w = weight.astype(_F32)
        y = bias.astype(_F32)
        for k in range(taps):
            y = y + padded[:, k:k + seq] * w[:, k]
        return (jax.nn.silu(y) if silu else y).astype(x.dtype)


def causal_conv1d(x, weight, bias, activation=None):
    """``x (b, s, channels)``, ``weight (channels, kernel)``, ``bias
    (channels,)``: ``y_t = bias + sum_k weight[:, k] x_{t - (kernel-1)
    + k}`` with zeros before the sequence, through SiLU where
    ``activation="silu"``; float32 inside, ``x``'s type out."""
    if activation not in (None, "silu"):
        raise ValueError(f"activation {activation!r} is neither None nor "
                         "'silu'")
    return _conv(x, weight, bias, activation == "silu")


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def gated_rms_norm(y, z, weight, groups, eps):
    """``RMSNorm(y * silu(z)) * weight`` with the mean square taken over
    each of ``groups`` equal groups of the last axis; float32 inside,
    ``y``'s type out."""
    gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    g = gated.reshape(gated.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(gated.shape) * weight.astype(_F32)).astype(y.dtype)
