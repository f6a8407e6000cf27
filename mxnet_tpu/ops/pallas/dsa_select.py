"""The sparse indexer's top-k as one Pallas pass (TPU): every query's
``topk``-th largest score found where the scores lie in VMEM.

``ops/sparse_index.py::select_topk`` in XLA finds each query's threshold
by bisection on the bits of an order-preserving integer key: 32 counting
passes, each of which reads the whole ``(seq, seq)`` key array from HBM.
Here a block of queries' scores are fetched once, the 32 passes count
over them in VMEM, and one more sweep writes the mask.

What the kernel moves and visits (``mx_dsa_select``):

* **Key-major**, like the two kernels beside it: it takes
  ``(batch, seq_k, seq_q)`` float32 scores (the layout ``mx_dsa_scores``
  emits) and returns the int8 mask in the same layout (the one the flash
  kernels and ``mx_dsa_align`` take it in).  Queries lie along lanes: a
  query's candidate threshold is a ``(1, block_q)`` row that broadcasts
  along sublanes, and a count is a sum along sublanes.
* Grid (batch, q-block).  A q-block's column panel of scores,
  ``(seq_k, block_q)`` float32, is fetched **once**; the keys are made
  once into a VMEM scratch of the same shape, signed: ``_ordered_key``'s
  ``uint32`` with the top bit flipped, so that Mosaic's signed
  comparison orders them as XLA's unsigned one does — ``-0.0`` under
  ``+0.0``, entries above the diagonal (``INT32_MIN``, the unsigned 0)
  under every score.
* The 32 passes are a ``fori_loop`` (not unrolled) over the panel in
  ``(block_q, block_q)`` chunks of keys **up to the diagonal only**:
  q-block ``qi`` reads keys ``< (qi + 1) * block_q``, a dynamic trip
  count.  A chunk's ``key >=
  cand`` are added into an ``(8, block_q)`` int32 accumulator, vreg on
  vreg, and the sublanes are summed once a pass.  The loop carries the
  threshold and the count of keys that reach it.
* **Ties.**  Where that count is more than a row may admit in some row
  of the panel (two float32 scores equal at the threshold: at 8192
  random scores a row in ~15,000, so most panels never do this), the
  panel takes, of the keys equal to the threshold, the first ``need``
  along the keys: ``need`` from one more counting pass (``key > thr``),
  and the key index they end under by a second bisection, on the
  index's 14 bits, each pass a count of ``key == thr & s < cand``.  The
  same selection as the XLA composition's running count, exactly.
* One more sweep writes the mask ``(key > thr | key == thr & s < upto)
  & causal`` up to the diagonal and zeros above it.
* A q-block whose every row has ``t < topk`` takes everything under the
  diagonal and skips the passes.
* No reduction over the batch: the call can sit in a ``shard_map`` over
  ``dp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry as _telemetry
from .dsa_scores import _VMEM_MAX, _VMEM_ROOM

#: queries a panel, the widest first; a sequence the kernel takes is a
#: multiple of one of them whose panel VMEM holds
BLOCKS = (512, 256, 128)
_MIN = -(1 << 31)


def _resident(s, block):
    """Bytes a call holds in VMEM: the scores' panel and the mask's, each
    twice (the pipeline's), and the keys' scratch."""
    return s * block * (2 * 4 + 2 * 1 + 4)


def panel_block(s):
    """Queries a panel at sequence ``s``: the widest of ``BLOCKS`` that
    divides it and whose panel VMEM holds, or None."""
    for block in BLOCKS:
        if s % block == 0 and _resident(s, block) + _VMEM_ROOM <= _VMEM_MAX:
            return block
    return None


def fits(s):
    """Can the kernel take this sequence."""
    return panel_block(s) is not None


def _signed_key(x):
    """float32 -> int32 with the same order (no NaNs):
    ``sparse_index._ordered_key`` with the top bit flipped."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _select_kernel(s_ref, mask_ref, key_ref, *, block, seq, topk):
    """One q-block's panel.  ``s_ref`` (1, seq_k, block_q) scores,
    ``mask_ref`` the same int8, ``key_ref`` (seq_k, block_q) int32
    scratch; walked in ``(block, block)`` chunks of keys."""
    qi = pl.program_id(1)
    live = qi + 1                           # chunks that reach the diagonal
    t = qi * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    every = jnp.full((1, block), seq, jnp.int32)

    def rows(c):
        return pl.ds(pl.multiple_of(c * block, block), block)

    def index(c):
        return c * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)

    def keys(c, carry):
        key_ref[rows(c), :] = jnp.where(
            index(c) <= t, _signed_key(s_ref[0, rows(c), :]),
            jnp.int32(_MIN))
        return carry

    def count(hit):
        """(1, block_q): a query's keys up to the diagonal that
        ``hit(keys, chunk)`` holds, added vreg on vreg, the sublanes
        summed once."""
        def one(c, acc):
            n = hit(key_ref[rows(c), :], c).astype(jnp.int32)
            return acc + jnp.sum(n.reshape(block // 8, 8, block), axis=0)

        return jnp.sum(jax.lax.fori_loop(
            0, live, one, jnp.zeros((8, block), jnp.int32)),
            axis=0, keepdims=True)

    def search():
        """-> (a query's threshold, the unsigned key's bits; the key
        index under which keys equal to it are taken)."""
        def bit(i, carry):
            thr, n_thr = carry
            cand = thr | (jnp.int32(1) << (31 - i))
            signed = cand ^ jnp.int32(_MIN)
            n = count(lambda k, c: k >= signed)
            take = n >= topk
            return jnp.where(take, cand, thr), jnp.where(take, n, n_thr)

        # the largest key ``topk`` or more of a row's keys reach, and how
        # many do
        thr, n_thr = jax.lax.fori_loop(
            0, 32, bit, (jnp.zeros((1, block), jnp.int32), t + 1))
        want = jnp.minimum(t + 1, topk)

        def ties():
            # more keys equal some row's threshold than it may admit:
            # the first ``need`` of them along the keys, by bisection on
            # the key index (the largest ``upto`` under which no more
            # than ``need`` of them lie)
            signed = thr ^ jnp.int32(_MIN)
            need = want - count(lambda k, c: k > signed)

            def bit(i, upto):
                cand = upto | (jnp.int32(1) << (seq.bit_length() - 1 - i))
                n = count(lambda k, c: (k == signed) & (index(c) < cand))
                return jnp.where(n <= need, cand, upto)

            return jax.lax.fori_loop(0, seq.bit_length(), bit,
                                     jnp.zeros((1, block), jnp.int32))

        tied = jnp.sum((n_thr != want).astype(jnp.float32)) > 0
        return thr, jax.lax.cond(tied, ties, lambda: every)

    jax.lax.fori_loop(0, live, keys, 0)
    # a panel whose rows all have fewer than ``topk`` keys takes them
    # all: threshold 0, which every key reaches
    thr, upto = jax.lax.cond(
        (qi + 1) * block > topk, search,
        lambda: (jnp.zeros((1, block), jnp.int32), every))
    signed = thr ^ jnp.int32(_MIN)

    def emit(c, carry):
        k, s = key_ref[rows(c), :], index(c)
        mask_ref[0, rows(c), :] = (
            ((k > signed) | ((k == signed) & (s < upto)))
            & (s <= t)).astype(jnp.int8)
        return carry

    def blank(c, carry):
        mask_ref[0, rows(c), :] = jnp.zeros((block, block), jnp.int8)
        return carry

    jax.lax.fori_loop(0, live, emit, 0)
    jax.lax.fori_loop(live, seq // block, blank, 0)


def select_pass(scores, topk, interpret=False, block=None):
    """scores **key-major** (b, s_k, s_q) float32 -> int8 (b, s_k, s_q):
    1 where query ``t`` reads key ``s``: ``s <= t`` and the score is
    among the ``topk`` largest of those, ties to the lower ``s``."""
    b, s, _ = scores.shape
    block = panel_block(s) if block is None else min(block, s)
    if block is None or s % block:
        raise ValueError(f"sequence {s} is no multiple of the block {block}")
    n = s // block
    if _telemetry._active:
        # once a traced call, as ``flash_attention._count_tiles`` counts
        # tiles: panels searched, and panels wholly under ``topk``, whose
        # passes are skipped
        searched = sum((a + 1) * block > topk for a in range(n))
        for kind, panels in (("computed", searched),
                             ("skipped", n - searched)):
            _telemetry.inc("kernel.flash_tiles_total", panels * b,
                           kernel="dsa_select", kind=kind)
    panel = pl.BlockSpec((1, s, block), lambda i, a: (i, 0, a))
    return pl.pallas_call(
        functools.partial(_select_kernel, block=block, seq=s,
                          topk=int(topk)),
        grid=(b, n),
        in_specs=[panel],
        out_specs=panel,
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.int8),
        scratch_shapes=[pltpu.VMEM((s, block), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_resident(s, block) + _VMEM_ROOM),
        interpret=interpret,
        name="mx_dsa_select",
    )(scores)
