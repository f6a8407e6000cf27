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
  is one more product; the state is carried from chunk to chunk; and
  what the entering state adds to a chunk's outputs is a last product.
  Nothing is ``(heads, seq, seq)``, and ``chunk`` changes no value.
  Which pass runs is decided by what the call can see.  On a TPU, at
  shapes their tiles fill (``ops/pallas/ssd_scan.py::fits``), it is the
  Pallas kernels ``mx_ssd_fwd`` and, backward, ``mx_ssd_bwd``: a chunk's
  decays and masked products live in VMEM only, the state is carried in
  a VMEM scratch down a sequential grid axis, and the operands are read
  once.  Everywhere else — the CPU, the tier-1 tests, small or ragged
  shapes — it is the XLA composition ``_ssd_chunked``, whose largest
  array is ``(heads, seq, chunk)`` and whose state is carried by a
  ``lax.scan`` over ``seq / chunk`` steps; it is also the kernels'
  oracle.
* ``causal_conv1d``: depthwise, ``kernel`` taps to the left with a bias,
  as shifted multiply-adds (optionally through SiLU).  The same choice
  by the same two questions: on a TPU, at shapes
  ``ops/pallas/ssm_conv.py::fits`` takes, the Pallas kernels
  ``mx_ssm_conv_fwd`` and, backward, ``mx_ssm_conv_bwd`` — a tile of
  ``x`` read once in its own type, the whole sequence of a block of
  channels in VMEM, the taps as lane shifts of it there; everywhere else
  the XLA composition ``_conv`` (float32 pads and shifted slices), their
  oracle.
* ``gated_rms_norm``: ``RMSNorm(y * silu(z)) * w`` over groups of
  channels: the gate first, then the norm.

**What is float32**: the log-decays ``dt A``, their running sums, every
exponential of them, the state carried between chunks, the
accumulation of every product, the convolution's sums and the norm.
The four products' operands (``C . B``, the triangular product, the
chunk's state, the state's output) take the type ``x`` arrives in —
bfloat16 under ``mx.amp``, as a ``Dense``'s do.

**The backward pass** of the norm and of the compositions ``_conv`` and
``_ssd_chunked`` is autodiff, each under ``jax.checkpoint``: nothing a
function computes inside is kept for its backward pass — not the
``(heads, seq, chunk)`` decays, not the chunk states, not the
convolution's float32 pad — only its arguments, and the function is made
again when its gradient is taken (a third more scan time for ~0.7 GB a
layer at 8192 tokens).  The kernels have their own (``jax.custom_vjp``)
and no ``jax.checkpoint``: ``mx_ssd_bwd`` walks the chunks in reverse
with the state's cotangent in its scratch and makes the decays again in
VMEM; beside the operands it keeps the float32 state entering each chunk
(134 MB a layer at 8192 tokens), and the forward is not run again.
``mx_ssm_conv_bwd`` keeps what the checkpoint kept — ``x``, the weights,
the bias —, walks the sequence's chunks in reverse with ``dy
silu'(pre)``'s first tokens of the chunk after in registers and makes
the pre-activation again in VMEM.
``docs/STATE_SPACE.md`` has the equations and the accounting.

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


def _whole_chunks(chunk, *tensors):
    """``(b, s, ...)`` tensors padded with zeros at the sequence's end to
    whole chunks."""
    pad = -tensors[0].shape[1] % chunk
    if not pad:
        return tensors
    return tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in tensors)


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _ssd_chunked(x, dt, a_head, b_mat, c_mat, d_skip, chunk):
    with jax.named_scope("mx.ssm.scan"):
        batch, seq, heads, dim = x.shape
        groups, state = b_mat.shape[2:]
        per = heads // groups
        dtype = x.dtype
        x, dt, b_mat, c_mat = _whole_chunks(chunk, x, dt, b_mat, c_mat)
        pad = x.shape[1] - seq
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
    ``d_skip (H,)`` -> ``y (b, s, H, P)`` in ``x``'s type.

    Which pass runs is decided by what the call can see: on a TPU, at
    shapes ``ops/pallas/ssd_scan.py``'s tiles fill (``fits``), the Pallas
    kernels; everywhere else the XLA composition, which is also their
    oracle."""
    from .. import runtime
    from .pallas import ssd_scan as kernels
    heads, groups = x.shape[2], b_mat.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} heads do not group over {groups} "
                         "B / C groups")
    chunk = int(chunk)
    by_kernel = runtime.on_tpu() and kernels.fits(
        x.shape[1], heads, x.shape[3], groups, b_mat.shape[3], chunk,
        x.dtype.itemsize)
    if _telemetry._active:
        _telemetry.inc("ssm.scan_tokens_total", x.shape[0] * x.shape[1])
        _telemetry.inc("ssm.scan_chunks_total",
                       x.shape[0] * -(-x.shape[1] // chunk) * heads)
        if by_kernel:
            _telemetry.inc("ssm.scan_kernel_calls_total")
    if by_kernel:
        # the scope round the custom_vjp call: its backward rule is traced
        # under the caller's scopes, ``transpose(jvp(...))`` round them
        with jax.named_scope("mx.ssm.scan"):
            return _ssd_kernels(x, dt, a_head, b_mat, c_mat, d_skip, chunk)
    return _ssd_chunked(x, dt, a_head, b_mat, c_mat, d_skip, chunk)


def _ssd_kernels(x, dt, a_head, b_mat, c_mat, d_skip, chunk):
    """``_ssd_chunked`` by ``ops/pallas/ssd_scan.py``, which takes its
    operands channel-major, tokens along the lanes: the layout XLA gives
    the mixer's arrays anyway, so each ``swapaxes`` is a layout to it.
    What else XLA does here is a few MB a call: the pad, the log-decays
    ``dt A``, ``D`` along a chunk's lanes; autodiff takes their
    gradients back to ``dt``, ``A`` and ``D``."""
    batch, seq, heads, dim = x.shape
    groups, state = b_mat.shape[2:]
    x, dt, b_mat, c_mat = _whole_chunks(chunk, x, dt, b_mat, c_mat)
    total = x.shape[1]
    steps = jnp.swapaxes(dt.astype(_F32), 1, 2)             # (b, H, s)
    y = _scan_kernels(
        jnp.swapaxes(x.reshape(batch, total, heads * dim), 1, 2),
        jnp.swapaxes(b_mat.reshape(batch, total, groups * state), 1, 2),
        jnp.swapaxes(c_mat.reshape(batch, total, groups * state), 1, 2),
        steps, steps * a_head.astype(_F32)[:, None],
        jnp.broadcast_to(d_skip.astype(_F32)[:, None],
                         (batch, heads, chunk)), groups, chunk)
    return jnp.swapaxes(y, 1, 2).reshape(batch, total, heads, dim)[:, :seq]


def _kernel_pass(name, operands, *static, **keywords):
    """``ops/pallas/``'s pass ``name`` (``module.function``) on operands
    that all have the batch first (``sparse_index._batch_over_dp``: a
    ``shard_map`` over 'dp' under a mesh, a group's heads and a channel's
    tokens whole on every device)."""
    import importlib
    from .. import runtime
    from .sparse_index import _batch_over_dp
    module, function = name.split(".")
    kernels = importlib.import_module(f"{__package__}.pallas.{module}")

    def kernel(*args):
        return getattr(kernels, function)(
            *args, *static, interpret=runtime.pallas_interpret(), **keywords)

    return _batch_over_dp(kernel, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_kernels(x, b_mat, c_mat, dt, log_decay, d_rows, groups, chunk):
    return _kernel_pass("ssd_scan.scan_pass",
                        (x, b_mat, c_mat, dt, log_decay, d_rows),
                        groups, chunk, keep=False)


def _scan_kernels_fwd(x, b_mat, c_mat, dt, log_decay, d_rows, groups, chunk):
    operands = (x, b_mat, c_mat, dt, log_decay, d_rows)
    y, entering = _kernel_pass("ssd_scan.scan_pass", operands, groups, chunk,
                               keep=True)
    return y, operands + (entering,)


def _scan_kernels_bwd(groups, chunk, res, dy):
    # traced under the caller's scopes, ``transpose(jvp(...))`` round
    # them, like any other backward operation
    return _kernel_pass("ssd_scan.scan_bwd_pass", res + (dy,), groups,
                        chunk)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


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
    ``activation="silu"``; float32 inside, ``x``'s type out.

    Which pass runs is decided by what the call can see, as in
    ``ssd_scan``: on a TPU, at shapes ``ops/pallas/ssm_conv.py``'s tiles
    fill (``fits``), the Pallas kernels; everywhere else the XLA
    composition ``_conv``, which is also their oracle."""
    from .. import runtime
    from .pallas import ssm_conv as kernels
    if activation not in (None, "silu"):
        raise ValueError(f"activation {activation!r} is neither None nor "
                         "'silu'")
    by_kernel = runtime.on_tpu() and kernels.fits(
        x.shape[1], x.shape[2], weight.shape[1], x.dtype.itemsize)
    if _telemetry._active:
        _telemetry.inc("ssm.conv_tokens_total", x.shape[0] * x.shape[1])
        if by_kernel:
            _telemetry.inc("ssm.conv_kernel_calls_total")
    if by_kernel:
        return _conv_by_kernels(x, weight, bias, activation == "silu")
    return _conv(x, weight, bias, activation == "silu")


def _conv_by_kernels(x, weight, bias, silu):
    """``_conv`` by ``ops/pallas/ssm_conv.py``, which takes ``x``
    channel-major like the scan's kernels (each ``swapaxes`` a layout to
    XLA) and the taps' weights with the bias as one float32 operand, a
    channel's number along 128 lanes, the same every batch row; autodiff
    takes that operand's gradient back to ``weight`` and ``bias``,
    summing over the lanes and the batch."""
    from .pallas.ssm_conv import _LANES
    columns = jnp.concatenate([weight.astype(_F32).T, bias.astype(_F32)[None]])
    columns = jnp.broadcast_to(columns[None, :, :, None],
                               x.shape[:1] + columns.shape + (_LANES,))
    x = jnp.swapaxes(x, 1, 2)
    # the scope round the custom_vjp call, as round the scan's, and round
    # nothing else: what cuts ``x`` out of the projection's output and
    # joins the scan's three cotangents was outside ``_conv``'s too
    with jax.named_scope("mx.ssm.conv"):
        y = _conv_kernels(x, columns, silu)
    return jnp.swapaxes(y, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_kernels(x, columns, silu):
    return _kernel_pass("ssm_conv.conv_pass", (x, columns), silu)


def _conv_kernels_fwd(x, columns, silu):
    # the pass itself and not ``_conv_kernels``: a custom_vjp called in
    # its own forward rule is traced without the caller's scopes
    return (_kernel_pass("ssm_conv.conv_pass", (x, columns), silu),
            (x, columns))


def _conv_kernels_bwd(silu, res, dy):
    # traced under the caller's scopes, like the scan's
    return _kernel_pass("ssm_conv.conv_bwd_pass", res + (dy,), silu)


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def gated_rms_norm(y, z, weight, groups, eps):
    """``RMSNorm(y * silu(z)) * weight`` with the mean square taken over
    each of ``groups`` equal groups of the last axis; float32 inside,
    ``y``'s type out."""
    gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    g = gated.reshape(gated.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(gated.shape) * weight.astype(_F32)).astype(y.dtype)
