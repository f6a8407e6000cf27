"""The sparse indexer's scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])`` and their gradients as Pallas passes (TPU).

``ops/sparse_index.py::index_scores`` in XLA writes a block of queries'
per-head products, ``(block, heads, seq)`` float32, to HBM, reads them
back for the relu, the weights and the sum over the heads, makes them
again in the backward pass and moves a cotangent of the same shape twice
more; it computes the whole square.  Here a tile's per-head products live
in VMEM only, forward and backward, and only tiles with a causal pair
run.

What the kernels move and visit:

* ``mx_dsa_scores`` (forward).  Grid (batch, q-block, k-block), the
  k-blocks innermost.  A q-block of **all** the indexer's heads,
  ``(heads, block_q, d)``, and its weights ``(heads, 1, block_q)`` stay
  resident along the row; a step fetches the tile ``(block_k, d)`` of
  the one key head.  In a tile the heads are walked in a loop: one MXU
  product a head, tiles transposed as in the flash kernels and
  ``mx_dsa_align`` (``sT = kI @ qI_h.T``, ``(block_k, block_q)``), so
  that a head's weight is a ``(1, block_q)`` row that broadcasts along
  sublanes; ``relu(sT) * w_h`` is added, in a fixed head order and in
  float32, into the output's tile in VMEM, which is written once.  The
  head loop is unrolled (here and backward), so that a head's product
  overlaps the vector work of the head before.
* The scores leave **key-major**, ``(batch, seq_k, seq_q)``: the layout
  the tiles have, and the one XLA gives every consumer of the scores
  (``select_topk``'s counting passes and the masked log-sum-exp reduce
  over the keys, which it lays along sublanes; the selection and the
  flash kernels' mask are key-major too).  ``index_scores`` returns
  ``swapaxes`` of it, which XLA folds into a layout, and
  ``mx_dsa_align`` takes and returns the same layout: no transpose in
  any kernel, no copy of a ``(seq, seq)`` array between them.
* ``mx_dsa_scores_bwd`` (backward), the same grid.  The cotangent's tile
  ``(block_k, block_q)`` float32 is fetched once a tile; a head's product
  is made again in VMEM, ``dPT_h = g * w_h * (sT_h > 0)`` takes the
  operands' type for the MXU, and three accumulations follow in
  float32: ``dqI_h.T += kI.T @ dPT_h`` into the q-block's resident output
  ``(heads, d, block_q)`` (transposed: ``kI.T`` is turned once a tile
  for all the heads where ``dPT_h.T`` would be turned a head, and the
  output's lanes are full at any ``d``; XLA turns the small result
  back), ``dw_h += sum_k g * relu(sT_h)`` into its ``(heads, 1,
  block_q)`` rows, and ``dkI += dPT_h @ qI_h`` into the
  **whole** ``(seq, d)`` float32 gradient of the one key head, resident
  for a batch row (2 MB at 8192 x 64; ``dqI`` whole would be 32 MB,
  which is why the q side is the blocked one).  One kernel, three
  products a head and tile: two kernels in the flash kernels' pattern
  would make the product a fourth time.
* Causal tiles (``flash_attention.tile_counts``): a tile above the
  diagonal is *skipped* — its blocks not fetched, because the index maps
  clamp to the row's last tile with work — and the forward writes its
  scores as zeros (they mean nothing: ``select_topk`` and ``align_loss``
  mask them).  The cotangent is nonzero on the selected entries only,
  but token by token, so no tile under the diagonal is empty.
* No reduction over the batch: the calls can sit in a ``shard_map`` over
  ``dp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry as _telemetry
from . import dsa_align
from .flash_attention import _NN, _NT, _count_tiles, _tile_runs

#: what a call may ask of VMEM (a v5e core has 128 MiB)
_VMEM_MAX = 96 << 20
#: room beside the pipeline's buffers for a tile's float32 temporaries
_VMEM_ROOM = 24 << 20


def _lanes(d):
    """``d`` as VMEM holds it: rows pad to whole 128-lane registers."""
    return -(-d // 128) * 128


def _resident(heads, s, d, itemsize, block):
    """Bytes the pipeline holds twice, (forward, backward): q of every
    head and kI's tile, the heads' weights (a row pads to 8 sublanes) and
    a ``(block, block)`` float32 tile; backward also q's gradient
    (transposed: no lane is padding), the weights' and the key head's
    whole."""
    rows = heads * 8 * block * 4
    fwd = 2 * ((heads + 1) * block * _lanes(d) * itemsize + rows
               + block * block * 4)
    bwd = fwd + 2 * (heads * -(-d // 8) * 8 * block * 4 + rows
                     + s * _lanes(d) * 4)
    return fwd, bwd


def fits(s, heads, d, itemsize):
    """Can the kernels take these shapes: a sequence the blocks divide
    into, and a backward call inside what VMEM gives."""
    block = dsa_align.BLOCK
    return s % block == 0 and (
        _resident(heads, s, d, itemsize, block)[1] + _VMEM_ROOM <= _VMEM_MAX)


def _scores_kernel(q_ref, k_ref, w_ref, out_ref, *, block, heads):
    """One (q-block, k-block) tile of the scores, key-major, summed over
    the heads in the output's tile."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    runs = _tile_runs(qi, kj, block, block, True)

    def weighed(h):
        sT = jax.lax.dot_general(k_ref[0], q_ref[0, h], _NT,
                                 preferred_element_type=jnp.float32)
        return jnp.maximum(sT, 0.0) * w_ref[0, h]

    @pl.when(runs)
    def _tile():
        out_ref[0] = weighed(0)

        def head(h, carry):
            out_ref[0] += weighed(h)
            return carry

        # unrolled: a head's product overlaps the head before's sum
        jax.lax.fori_loop(1, heads, head, 0, unroll=True)

    @pl.when(jnp.logical_not(runs))
    def _empty():
        out_ref[0] = jnp.zeros_like(out_ref[0])


def _scores_bwd_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref, *,
                       block, heads):
    """One tile's share of the three gradients.  ``dq_ref`` (a head's
    gradient transposed, ``(d, block_q)``: lanes full at any ``d``, and
    ``kI.T``, turned once a tile, is the product's left side) and
    ``dw_ref`` are the q-block's, revisited along the k-blocks;
    ``dk_ref`` is the batch row's whole key gradient."""
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when((qi == 0) & (kj == 0))
    def _init_row():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])

    @pl.when(kj == 0)
    def _init_block():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])
        dw_ref[0] = jnp.zeros_like(dw_ref[0])

    @pl.when(_tile_runs(qi, kj, block, block, True))
    def _tile():
        keys = pl.ds(pl.multiple_of(kj * block, block), block)
        kT = k_ref[0].T

        def head(h, carry):
            q = q_ref[0, h]
            sT = jax.lax.dot_general(k_ref[0], q, _NT,
                                     preferred_element_type=jnp.float32)
            live = jnp.where(sT > 0, g_ref[0], 0.0)
            dw_ref[0, h] += jnp.sum(sT * live, axis=0, keepdims=True)
            dpT = (live * w_ref[0, h]).astype(q.dtype)
            dq_ref[0, h] += jax.lax.dot_general(
                kT, dpT, _NN, preferred_element_type=jnp.float32)
            dk_ref[0, keys, :] += jax.lax.dot_general(
                dpT, q, _NN, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, heads, head, 0, unroll=True)


def _specs(heads, block, d):
    """Block specs of q, kI's tile, the weights' rows and a key-major
    ``(block, block)`` tile; a step above the diagonal names the row's
    last tile with work."""
    def last(a, c):     # square tiles: q-block ``a`` reaches k-block ``a``
        return jax.lax.min(c, a)

    return (pl.BlockSpec((1, heads, block, d), lambda i, a, c: (i, 0, a, 0)),
            pl.BlockSpec((1, block, d), lambda i, a, c: (i, last(a, c), 0)),
            pl.BlockSpec((1, heads, 1, block), lambda i, a, c: (i, 0, 0, a)),
            pl.BlockSpec((1, block, block),
                         lambda i, a, c: (i, last(a, c), a)))


def scores_pass(q, k, w, interpret=False, block=None):
    """q (b, heads, s, d), k (b, s, d), w (b, heads, s) float32 ->
    scores **key-major** (b, s_k, s_q) float32: ``sum_h w[h, t] *
    relu(q[h, t] . k[s])`` where ``s``'s tile reaches ``t``'s, zeros in
    the tiles above the diagonal."""
    b, heads, s, d = q.shape
    block = dsa_align.tile_block(s, block)
    if _telemetry._active:
        _count_tiles(("dsa_scores",), b, s, s, block, block, True)
    n = s // block
    q_spec, k_spec, w_spec, _ = _specs(heads, block, d)
    return pl.pallas_call(
        functools.partial(_scores_kernel, block=block, heads=heads),
        grid=(b, n, n),
        in_specs=[q_spec, k_spec, w_spec],
        out_specs=pl.BlockSpec((1, block, block), lambda i, a, c: (i, c, a)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_resident(
            heads, s, d, q.dtype.itemsize, block)[0] + _VMEM_ROOM),
        interpret=interpret,
        name="mx_dsa_scores",
    )(q, k, w[:, :, None, :])


def scores_bwd_pass(q, k, w, g, interpret=False, block=None):
    """The operands of ``scores_pass`` and the scores' cotangent
    key-major (b, s_k, s_q) float32 -> (dq (b, heads, s, d), dk (b, s,
    d), dw (b, heads, s)), float32."""
    b, heads, s, d = q.shape
    block = dsa_align.tile_block(s, block)
    if _telemetry._active:
        _count_tiles(("dsa_scores_bwd",), b, s, s, block, block, True)
    n = s // block
    q_spec, k_spec, w_spec, tile = _specs(heads, block, d)
    dq, dk, dw = pl.pallas_call(
        functools.partial(_scores_bwd_kernel, block=block, heads=heads),
        grid=(b, n, n),
        in_specs=[q_spec, k_spec, w_spec, tile],
        out_specs=[
            pl.BlockSpec((1, heads, d, block), lambda i, a, c: (i, 0, 0, a)),
            pl.BlockSpec((1, s, d), lambda i, a, c: (i, 0, 0)),
            w_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, d, s), jnp.float32),
            jax.ShapeDtypeStruct((b, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_resident(
            heads, s, d, q.dtype.itemsize, block)[1] + _VMEM_ROOM),
        interpret=interpret,
        name="mx_dsa_scores_bwd",
    )(q, k, w[:, :, None, :], g)
    return jnp.swapaxes(dq, 2, 3), dk, dw[:, :, 0]
