"""The decode step's cached attention read, ``ops/attention.py::
decode_attention``, as one Pallas pass (TPU): every slot's one query
against the rows of the KV cache that slot holds, the cache taken where it
lies.

The XLA composition reads all ``max_slots x max_seq`` rows whatever the
slots hold, and — the writer and the reader each choosing a layout — copies
every cache leaf twice a step (``PERF.md`` section 6, PR 49).  A Mosaic
call fixes its operands' layout, so with this kernel as the reader XLA
applies the step's scatter in place to the donated buffer and nothing is
copied.

* **Operands.**  ``q (slots, 1, heads x dim)``, the step's query; ``k`` and
  ``v (slots, max_seq, heads x dim)``, the cache leaves after the step's row
  was written, heads side by side along the lanes (a 64-wide head is not
  padded to 128 lanes, in HBM or in the DMA); ``rows (slots,)`` int32, how
  many rows each slot attends (0: an idle slot).  ``rows`` and two arrays
  made from it are scalar-prefetched: the block index maps read them.
* **Grid** (slot, block of cache rows).  A block past a slot's rows maps
  to the last block the slot needs, and every block of an idle slot to the
  block the step before it fetched: the index does not change, so no DMA
  is issued, and ``pl.when`` skips the arithmetic.  An idle slot's output
  is zeros.
* **All heads in one matmul.**  The query is spread block-diagonally,
  ``(heads, heads x dim)`` with head ``h``'s numbers in its own ``dim``
  columns and zeros elsewhere, so ``scores = Q K^T`` is one MXU call on the
  lane-dense block and ``P V`` another; head ``h``'s output is its own
  ``dim`` columns of row ``h``, picked out once a slot.  The MXU multiplies
  zeros, which costs nothing here: with one query a slot it is bound by
  loading the block, not by the rows streamed through it.
* Online softmax over the blocks in float32 (``m``, ``l``, the
  accumulator); K and V are read once each in the cache's type and meet the
  query in the query's type, as the composition's ``astype`` has it.
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
_NEG_INF = -1e30
_BLOCK_BYTES = 512 << 10    # most bytes of one block of K (or of V)


def _tile(itemsize):
    """Sublanes of a register tile of the cache's type."""
    return 32 // itemsize


def _block(max_seq, width, itemsize):
    """Cache rows of a grid step: sublane tiles doubled while they divide
    ``max_seq`` and a block stays inside ``_BLOCK_BYTES``."""
    block = _tile(itemsize)
    while max_seq % (2 * block) == 0 \
            and 2 * block * width * itemsize <= _BLOCK_BYTES:
        block *= 2
    return block


def _padded(heads):
    """Rows of the block-diagonal query: the heads, in whole sublane tiles
    of any type."""
    return -(-heads // 16) * 16


def _resident(block, heads, width, itemsize, q_itemsize):
    """Bytes a step holds: the pipeline's two copies of K's and V's
    blocks, the query and the output, and the scratch."""
    padded = _padded(heads)
    return (4 * block * width * itemsize + 4 * width * q_itemsize
            + padded * width * (q_itemsize + 4) + 2 * padded * _LANES * 4)


def fits(max_seq, width, itemsize):
    """Can the kernel take a cache of these shapes: a row's ``width = heads
    x dim`` along whole 128-lane registers, a float type, ``max_seq`` in
    whole blocks of whole sublane tiles, and a step's blocks of K and V,
    double-buffered, inside what VMEM gives."""
    if width % _LANES or itemsize not in (2, 4):
        return False
    block = _block(max_seq, width, itemsize)
    return (max_seq % block == 0
            and 4 * block * width * itemsize + _VMEM_ROOM <= _VMEM_MAX)


def _own(shape, dim):
    """``(heads, width)`` bool: the columns that are row ``h``'s head."""
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (col >= head * dim) & (col < (head + 1) * dim)


def _kernel(rows_ref, src_ref, last_ref, q_ref, k_ref, v_ref, o_ref,
            qd_ref, m_ref, l_ref, acc_ref, *, dim, block):
    """One block of one slot's rows."""
    slot, at = pl.program_id(0), pl.program_id(1)
    rows = rows_ref[slot]

    @pl.when((at == 0) & (rows > 0))
    def _():
        # selected as float32: a mask of 32-bit comparisons has no layout
        # on a packed type's tiles
        qd_ref[...] = jnp.where(_own(qd_ref.shape, dim),
                                q_ref[0].astype(_F32), 0.0
                                ).astype(qd_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(at * block < rows)
    def _():
        qd = qd_ref[...]
        s = jax.lax.dot_general(
            qd, k_ref[0].astype(qd.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=_F32) * (dim ** -0.5)
        row = at * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row < rows, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(qd.dtype), v_ref[0].astype(qd.dtype),
            preferred_element_type=_F32)
        m_ref[...] = m_new

    @pl.when(at == pl.num_programs(1) - 1)
    def _():
        @pl.when(rows > 0)
        def _():
            out = acc_ref[...] / l_ref[...][:, :1]
            o_ref[0] = jnp.sum(
                jnp.where(_own(out.shape, dim), out, 0.0), axis=0,
                keepdims=True).astype(o_ref.dtype)

        @pl.when(rows == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("heads", "block", "interpret"))
def decode_read(q, k, v, rows, heads, block=None, interpret=False):
    """``q (slots, 1, width)``; ``k``, ``v (slots, max_seq, width)``;
    ``rows (slots,)`` int: softmax(q_h K_h^T / sqrt(dim)) V_h over each
    slot's first ``rows`` cache rows, a head a ``dim = width / heads``
    columns -> ``(slots, 1, width)`` in ``q``'s type; zeros where ``rows``
    is 0.  ``block`` (cache rows a grid step) is from the shapes unless
    given.  Jitted: a decode step traces and lowers the kernel once, not
    once a layer."""
    slots, max_seq, width = k.shape
    if block is None:
        block = _block(max_seq, width, k.dtype.itemsize)
    padded = _padded(heads)
    rows = jnp.clip(rows.astype(jnp.int32), 0, max_seq)
    # what a slot fetches: its own blocks up to its last; an idle slot
    # the block the slot before it ended on
    at = jnp.arange(slots, dtype=jnp.int32)
    src = jnp.maximum(jax.lax.cummax(jnp.where(rows > 0, at, -1)), 0)
    last = jnp.maximum((rows[src] + block - 1) // block - 1, 0)

    def cache_block(slot, at, rows_ref, src_ref, last_ref):
        return (src_ref[slot],
                jnp.where(rows_ref[slot] > 0,
                          jnp.minimum(at, last_ref[slot]), last_ref[slot]),
                0)

    def own(slot, at, *_):
        return (slot, 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, dim=width // heads, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, max_seq // block),
            in_specs=[pl.BlockSpec((1, 1, width), own),
                      pl.BlockSpec((1, block, width), cache_block),
                      pl.BlockSpec((1, block, width), cache_block)],
            out_specs=pl.BlockSpec((1, 1, width), own),
            scratch_shapes=[pltpu.VMEM((padded, width), q.dtype),
                            pltpu.VMEM((padded, _LANES), _F32),
                            pltpu.VMEM((padded, _LANES), _F32),
                            pltpu.VMEM((padded, width), _F32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_resident(block, heads, width,
                                       k.dtype.itemsize, q.dtype.itemsize)
            + _VMEM_ROOM),
        interpret=interpret,
        name="mx_decode_attn",
    )(rows, src, last, q, k, v)
