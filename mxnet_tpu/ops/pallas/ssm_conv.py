"""The mixers' causal convolution ``ops/ssm.py::causal_conv1d`` as Pallas
passes (TPU): a tile of ``x`` is read once in its own type, the taps are
lane shifts of it in VMEM, and nothing float32 or padded reaches HBM.

``_conv`` in XLA casts ``x`` to float32, pads it along the sequence,
reads ``taps`` shifted slices of the padded array and, under
``jax.checkpoint``, makes all of it three times a training step.  Here a
forward pass reads ``x`` and writes ``y``; a backward pass reads ``x`` and
``dy`` and writes ``dx``.

* **Channel-major operands**: ``x``, ``y``, ``dy``, ``dx`` ``(batch,
  channels, seq)``, tokens along the lanes — the layout XLA gives the
  mixer's arrays between its two projections (``ssd_scan.py`` says how
  that was learned), so a tap is a shift along the lanes.  The weights
  and the bias travel as one float32 operand ``(batch, taps + 1,
  channels, 128)``, the bias last, a channel's number along all 128
  lanes and every batch row the same: a ``(rows, 1)`` column would pad
  to 128 lanes in VMEM anyway, and so a tap's weights are whole registers
  with no lane to pick or broadcast (16 MB a mixer, read once).  The
  gradient has the same shape — lane ``l`` holds the sum over the tokens
  at lane ``l`` of their registers, a row a batch element: no reduction
  over the lanes or the batch in the kernel, so the calls can sit in a
  ``shard_map`` over ``dp`` — and autodiff takes it back through the
  broadcast to ``weight`` and ``bias``, which is that sum.
* **A grid step holds the whole sequence of a block of channels**: grid
  (batch, channel block), nothing carried from step to step.  Inside, a
  sublane tile of channels at a time (its weights loaded once), the
  sequence is walked in chunks of 4096 tokens.  Wide on purpose: nothing
  of one iteration of the loop overlaps the next, so what an iteration
  pays whatever its width (~0.2 us on a v5e) was two thirds of the time
  at 512 tokens a chunk and is a tenth at 4096 (``PERF.md`` section 6,
  PR 43); the compiler schedules the chunk's 64 float32 registers a value
  through VMEM at no cost that shows.
* ``mx_ssm_conv_fwd``.  A chunk: ``x`` cast to float32 behind the 128
  tokens before it (an aligned load from the resident block; zeros at
  the sequence's start), rolled ``j`` lanes for tap ``taps - 1 - j``
  (``pltpu.roll``; the aligned slice after it drops the 128 tokens),
  times that tap's weights, summed with the bias, through SiLU, cast and
  written once.
* ``mx_ssm_conv_bwd``.  Keeps what ``jax.checkpoint`` kept — ``x``, the
  weights, the bias — and makes the pre-activation again in VMEM.  The
  chunks are walked **in reverse**: with ``g = dy silu'(pre)``, ``dx_t =
  sum_j w_{taps-1-j} g_{t+j}`` reads ``g`` ahead of it, so the loop
  carries the first 128 tokens of the chunk after (zeros at the
  sequence's end) and rolls ``g`` the other way; ``dw_{taps-1-j} = sum_t
  g_t x_{t-j}`` and ``dbias = sum_t g_t`` are a chunk's products folded
  register on register to 128 lanes, float32, carried down the loop in
  registers and written once a tile.
* The taps' sums, the bias, the SiLU and its derivative, ``dw`` and
  ``dbias`` are float32; ``x``, ``y``, ``dy`` and ``dx`` travel in ``x``'s
  type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dsa_scores import _VMEM_MAX, _VMEM_ROOM

_F32 = jnp.float32
_LANES = 128
_CHUNK = 4096           # most tokens of an iteration of the inner loop
_TILE_BYTES = 2 << 20   # most bytes of a step's tile of x


def _rows(itemsize):
    """Sublanes of a register tile of ``x``'s type."""
    return 32 // itemsize


def _block(channels, seq, itemsize):
    """Channels of a grid step: sublane tiles doubled while they divide
    ``channels`` and their sequences stay inside ``_TILE_BYTES``."""
    block = _rows(itemsize)
    while channels % (2 * block) == 0 \
            and 2 * block * seq * itemsize <= _TILE_BYTES:
        block *= 2
    return block


def _chunk(seq):
    """Tokens of an iteration of the inner loop: the largest power of two
    up to ``_CHUNK`` that divides ``seq``."""
    registers = seq // _LANES
    return _LANES * min(_CHUNK // _LANES, registers & -registers)


def _resident(channels, seq, taps, itemsize):
    """Bytes a backward step holds (it holds more than a forward one):
    the pipeline's two copies of every tile — ``x``, ``dy``, ``dx``, the
    weights and their gradient."""
    block = _block(channels, seq, itemsize)
    return 2 * (3 * block * seq * itemsize
                + 2 * (taps + 1) * block * _LANES * 4)


def fits(seq, channels, taps, itemsize):
    """Can the kernels take these shapes: tokens along whole 128-lane
    registers, channels down whole sublane tiles of ``x``'s type, taps
    that reach no further back than one register, and a step — the whole
    sequence of a tile of channels — inside what VMEM gives."""
    return (seq > 0 and seq % _LANES == 0
            and channels % _rows(itemsize) == 0 and 1 <= taps <= _LANES
            and _resident(channels, seq, taps, itemsize) + _VMEM_ROOM
            <= _VMEM_MAX)


def _behind(x, before, taps):
    """``x (rows, chunk)`` float32 with the 128 tokens ``before`` it ->
    ``[x_{t-j}]`` for j in 0 .. taps - 1: what a token reads at each tap,
    furthest back last."""
    wide = jnp.concatenate([before, x], axis=1)
    return [x] + [pltpu.roll(wide, j, 1)[:, _LANES:] for j in range(1, taps)]


def _ahead(g, after, taps):
    """``g (rows, chunk)`` with the 128 tokens ``after`` it ->
    ``[g_{t+j}]`` for j in 0 .. taps - 1."""
    chunk = g.shape[1]
    wide = jnp.concatenate([g, after], axis=1)
    return [g] + [pltpu.roll(wide, chunk + _LANES - j, 1)[:, :chunk]
                  for j in range(1, taps)]


def _along(w, chunk):
    """The taps' weights and the bias, ``(taps + 1, rows, 128)``, each
    along ``chunk`` lanes: the same registers again."""
    return [jnp.concatenate([w[k]] * (chunk // _LANES), axis=1)
            for k in range(w.shape[0])]


def _fold(t):
    """``(rows, chunk)`` -> ``(rows, 128)``: the registers of a row summed."""
    return sum(t[:, at:at + _LANES] for at in range(0, t.shape[1], _LANES))


def _pre(w, shifted):
    """``bias + sum_j w_{taps-1-j} x_{t-j}``."""
    taps = len(shifted)
    pre = w[taps]
    for j, xs in enumerate(shifted):
        pre = pre + w[taps - 1 - j] * xs
    return pre


@functools.partial(jax.jit, static_argnums=0)
def _fwd_chunk(silu, x, before, w):
    """One chunk of one sublane tile, values in and out (jitted: a step
    traces it once, not once a mixer): ``x (rows, chunk)`` and the 128
    tokens ``before`` it in their type, float32 ``w (taps + 1, rows,
    128)`` -> ``y``'s chunk."""
    w = _along(w, x.shape[1])
    pre = _pre(w, _behind(x.astype(_F32), before.astype(_F32), len(w) - 1))
    return (jax.nn.silu(pre) if silu else pre).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=0)
def _bwd_chunk(silu, x, before, w, dy, head, sums):
    """One chunk of one sublane tile, values in and out (jitted, as
    ``_fwd_chunk``): the operands of ``_fwd_chunk``, ``dy``'s chunk, the
    first 128 tokens ``head`` of ``g = dy silu'(pre)`` in the chunk
    after and the float32 ``sums (taps + 1, rows, 128)`` so far ->
    ``dx``'s chunk, this chunk's ``head``, the sums with this chunk's: tap
    k's is ``sum_t g_t x_{t-(taps-1)+k}``, the bias's, last, ``sum_t
    g_t``."""
    w = _along(w, x.shape[1])
    taps = len(w) - 1
    shifted = _behind(x.astype(_F32), before.astype(_F32), taps)
    g = dy.astype(_F32)
    if silu:
        pre = _pre(w, shifted)
        gate = jax.nn.sigmoid(pre)
        g = g * (gate * (1.0 + pre * (1.0 - gate)))
    dx = 0.0
    for j, gs in enumerate(_ahead(g, head, taps)):
        dx = dx + w[taps - 1 - j] * gs
    terms = [g * xs for xs in shifted[::-1]] + [g]
    return (dx.astype(x.dtype), g[:, :_LANES],
            sums + jnp.stack([_fold(t) for t in terms]))


def _walk(x_ref, wb_ref, chunk, reverse, carry, body, after=None):
    """For each sublane tile ``rows`` of the step's channels: ``carry =
    body(rows, tokens, x, before, w, carry)`` over the sequence's chunks
    ``tokens`` in order (or in ``reverse``), then ``after(rows, carry)``.
    ``x`` is the chunk, ``before`` the 128 tokens before it (zeros before
    the sequence), ``w`` the tile's weights and bias."""
    channels, seq = x_ref.shape[1:]
    step, n = _rows(x_ref.dtype.itemsize), seq // chunk

    def tile(r, _):
        rows = pl.ds(pl.multiple_of(r * step, step), step)
        w = wb_ref[0, :, rows, :]

        def one(i, carried):
            q = n - 1 - i if reverse else i
            before = x_ref[0, rows, pl.ds(pl.multiple_of(
                jnp.maximum(q * chunk - _LANES, 0), _LANES), _LANES)]
            tokens = pl.ds(pl.multiple_of(q * chunk, chunk), chunk)
            return body(rows, tokens, x_ref[0, rows, tokens],
                        jnp.where(q > 0, before, jnp.zeros_like(before)), w,
                        carried)

        last = jax.lax.fori_loop(0, n, one, carry)
        if after is not None:
            after(rows, last)
        return 0

    jax.lax.fori_loop(0, channels // step, tile, 0)


def _fwd_kernel(x_ref, wb_ref, y_ref, *, silu, chunk):
    """The whole sequence of one channel block: ``y``'s tile."""

    def body(rows, tokens, x, before, w, carried):
        y_ref[0, rows, tokens] = _fwd_chunk(silu, x, before, w)
        return carried

    _walk(x_ref, wb_ref, chunk, False, 0, body)


def _bwd_kernel(x_ref, wb_ref, dy_ref, dx_ref, dwb_ref, *, silu, chunk):
    """The whole sequence of one channel block, walked backwards: ``dx``'s
    tile and the weights' and the bias's sums."""

    def body(rows, tokens, x, before, w, carried):
        dx_ref[0, rows, tokens], head, sums = _bwd_chunk(
            silu, x, before, w, dy_ref[0, rows, tokens], *carried)
        return head, sums

    def after(rows, carried):
        dwb_ref[0, :, rows, :] = carried[1]

    step = _rows(x_ref.dtype.itemsize)
    _walk(x_ref, wb_ref, chunk, True,
          (jnp.zeros((step, _LANES), _F32),
           jnp.zeros((wb_ref.shape[1], step, _LANES), _F32)), body, after)


def _call(kernel, name, operands, with_dwb, silu, interpret):
    """``kernel`` over grid (batch, channel block) on ``operands`` — ``x``,
    ``wb`` and what else is laid out like ``x``: a result like ``x`` and,
    ``with_dwb``, a float32 one like ``wb``."""
    x, wb = operands[:2]
    batch, channels, seq = x.shape
    taps = wb.shape[1] - 1
    block = _block(channels, seq, x.dtype.itemsize)
    x_spec = pl.BlockSpec((1, block, seq), lambda i, c: (i, c, 0))
    wb_spec = pl.BlockSpec((1, taps + 1, block, _LANES),
                           lambda i, c: (i, 0, c, 0))
    out = [(x_spec, jax.ShapeDtypeStruct(x.shape, x.dtype))]
    if with_dwb:
        out.append((wb_spec, jax.ShapeDtypeStruct(wb.shape, _F32)))
    return pl.pallas_call(
        functools.partial(kernel, silu=silu, chunk=_chunk(seq)),
        grid=(batch, channels // block),
        in_specs=[x_spec, wb_spec] + [x_spec] * (len(operands) - 2),
        out_specs=[spec for spec, _ in out],
        out_shape=[shape for _, shape in out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_resident(channels, seq, taps, x.dtype.itemsize)
            + _VMEM_ROOM),
        interpret=interpret,
        name=name,
    )(*operands)


def conv_pass(x, wb, silu, interpret=False):
    """Channel-major: x (b, channels, s); wb float32 (b, taps + 1,
    channels, 128), the taps' weights then the bias, a channel's along
    the lanes, the same every batch row -> y like x: ``bias + sum_k w_k
    x_{t-(taps-1)+k}`` with zeros before the sequence, through SiLU where
    ``silu``."""
    return _call(_fwd_kernel, "mx_ssm_conv_fwd", (x, wb), False, silu,
                 interpret)[0]


def conv_bwd_pass(x, wb, dy, silu, interpret=False):
    """The operands of ``conv_pass`` and ``y``'s cotangent -> ``dx`` in
    ``x``'s type and the float32 gradient of ``wb`` in its shape: a row
    a batch element, a lane the sum over the tokens at that lane of their
    registers."""
    return tuple(_call(_bwd_kernel, "mx_ssm_conv_bwd", (x, wb, dy), True,
                       silu, interpret))
