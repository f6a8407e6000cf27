"""The sparse indexer's alignment loss ``L_I`` as one Pallas pass (TPU).

``ops/sparse_index.py::align_loss`` wants, for every query ``t``, the
mean over the heads of the attention's probabilities on the selected
keys, ``p_t``, the KL of it against the softmax of the indexer's scores
over the same keys, and the closed-form gradient ``(softmax_{S_t}(I) -
p) / T``.  The XLA composition writes ``(heads, block, seq)`` float32
logits to HBM and reads them back for the mask, the max, the exponent,
the sum and the mean; here a tile's per-head probabilities live in VMEM
only.

What the kernel moves and visits (``mx_dsa_align``):

* Grid (batch, q-block, k-block), the k-blocks innermost.  A q-block of
  **all** the query heads, ``(heads, block_q, d)``, stays resident along
  the row; a step fetches K's tile of every KV head once
  (``(kv_heads, block_k, d)``), the scores' tile and the selection's.
* In a tile the query heads are walked in a loop: one MXU product a
  head, tiles transposed as in the flash kernels (``sT = k @ q.T``,
  ``(block_k, block_q)``), so that the row statistics — each head's
  ``lse`` over the selected keys, which the forward flash kernel already
  computed, and the scores' ``lse`` — are ``(1, block_q)`` rows that
  broadcast along sublanes.  ``exp(sT * scale - lse_h)`` is summed into
  one float32 ``(block_k, block_q)`` accumulator in VMEM: the only place
  the per-head probabilities exist.
* After the last head: ``p`` on the selected and causal entries, the
  tile's share of ``sum(xlogy(p, p) - p * logq)`` added to a per-query
  row ``(1, block_q)`` that stays resident along the row, and
  ``d_scores`` written once.  The scores, the selection and ``d_scores``
  are all **key-major**, ``(batch, seq_k, seq_q)``, as the tiles are:
  the layout ``mx_dsa_scores`` emits and XLA keeps the selection in, so
  no tile is turned in the kernel and the step holds one layout of each
  ``(seq, seq)`` array.
* Causal tiles (``flash_attention.tile_counts``): a tile above the
  diagonal is *skipped* — its blocks not fetched, because the index maps
  clamp to the row's last tile with work — and its ``d_scores`` written
  as zeros.  A selection empties no tile (it is token by token), so
  every tile under the diagonal runs.
* The sum over queries stays in XLA: the call holds no reduction over the
  batch and can sit in a ``shard_map`` over ``dp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry as _telemetry
from .flash_attention import (_LANES, _NT, _attended, _count_tiles, _div,
                              _selected, _tile_runs)

#: queries and keys a tile; a sequence the kernel takes is a multiple
BLOCK = 512


def _align_kernel(q_ref, k_ref, lse_ref, lse_i_ref, i_ref, sel_ref, d_ref,
                  kl_ref, acc_ref, *, block, seq, heads, group, scale,
                  tokens):
    """One (q-block, k-block) tile for every head.  ``sel_ref``,
    ``i_ref`` and ``d_ref`` are the selection's, the scores' and their
    gradient's tiles, key-major; ``kl_ref`` the q-block's per-query row,
    revisited along the k-blocks."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    runs = _tile_runs(qi, kj, block, block, True)

    @pl.when(kj == 0)
    def _init():
        kl_ref[0] = jnp.zeros_like(kl_ref[0])

    @pl.when(runs)
    def _tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def head(h, carry):
            sT = jax.lax.dot_general(
                k_ref[0, _div(h, group)], q_ref[0, h], _NT,
                preferred_element_type=jnp.float32) * scale
            acc_ref[...] += jnp.exp(sT - lse_ref[0, h])
            return carry

        jax.lax.fori_loop(0, heads, head, 0)
        valid = _attended(qi * block, kj * block, acc_ref.shape, 1, seq,
                          True) & _selected(sel_ref[0])
        p = jnp.where(valid, acc_ref[...] / heads, 0.0)
        logq = i_ref[0] - lse_i_ref[0]
        plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        kl_ref[0] += jnp.sum(plogp - jnp.where(valid, p * logq, 0.0),
                             axis=0, keepdims=True)
        d_ref[0] = jnp.where(valid, jnp.exp(logq) - p, 0.0) / tokens

    @pl.when(jnp.logical_not(runs))
    def _empty():
        d_ref[0] = jnp.zeros_like(d_ref[0])


def tile_block(s, block=None):
    """Queries and keys a tile at sequence ``s``: ``BLOCK`` (or the
    caller's), clamped to the sequence, which it has to divide."""
    block = min(BLOCK if block is None else block, s)
    if s % block:
        raise ValueError(f"sequence {s} is no multiple of the block {block}")
    return block


def align_pass(scores, selection, q, k, lse, tokens, interpret=False,
               block=None):
    """scores float32 and selection int8 (within the causal triangle),
    both **key-major** (b, s_k, s_q); q (b, heads, s, d), k (b,
    kv_heads, s, d), lse (b, heads, s) float32: each head's log-sum-exp
    of ``q . k * d**-0.5`` over the selected keys.  Returns (per-query
    KL (b, 1, s), ``d_scores`` key-major (b, s_k, s_q)), the latter
    divided by ``tokens``."""
    b, heads, s, d = q.shape
    kv_heads = k.shape[1]
    block = tile_block(s, block)
    if _telemetry._active:
        _count_tiles(("dsa_align",), b, s, s, block, block, True)
    scale = 1.0 / (d ** 0.5)
    if d % _LANES:
        pad = ((0, 0), (0, 0), (0, 0), (0, -d % _LANES))
        q, k = jnp.pad(q, pad), jnp.pad(k, pad)
        d = q.shape[-1]
    lse_i = jax.nn.logsumexp(
        jnp.where(selection != 0, scores, -1e30), axis=1, keepdims=True)
    sel = selection.astype(jnp.int8)
    n = s // block

    def last(a, c):     # square tiles: q-block ``a`` reaches k-block ``a``
        return jax.lax.min(c, a)

    # what the pipeline holds twice: q of every head and K's tile, the
    # heads' statistics (a row pads to 8 sublanes), the scores' tile, the
    # gradient's and the selection's; the limit leaves room for the
    # accumulator and a tile's float32 temporaries
    resident = 2 * ((heads + kv_heads) * block * d * q.dtype.itemsize
                    + heads * 8 * block * 4 + block * block * 9)
    # a key-major tile of the scores, and of the selection
    tile = pl.BlockSpec((1, block, block), lambda i, a, c: (i, last(a, c), a))
    d_scores, kl = pl.pallas_call(
        functools.partial(_align_kernel, block=block, seq=s, heads=heads,
                          group=heads // kv_heads, scale=scale,
                          tokens=tokens),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, heads, block, d), lambda i, a, c: (i, 0, a, 0)),
            pl.BlockSpec((1, kv_heads, block, d),
                         lambda i, a, c: (i, 0, last(a, c), 0)),
            pl.BlockSpec((1, heads, 1, block), lambda i, a, c: (i, 0, 0, a)),
            pl.BlockSpec((1, 1, block), lambda i, a, c: (i, 0, a)),
            tile,
            tile,
        ],
        out_specs=[
            pl.BlockSpec((1, block, block), lambda i, a, c: (i, c, a)),
            pl.BlockSpec((1, 1, block), lambda i, a, c: (i, 0, a)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, s), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=resident + (24 << 20)),
        interpret=interpret,
        name="mx_dsa_align",
    )(q, k, lse[:, :, None, :], lse_i, scores, sel)
    return kl, d_scores
