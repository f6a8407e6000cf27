"""Flash attention Pallas kernel (TPU).

Reference parity: src/operator/contrib/transformer.cc:675-828 — MXNet's
fastest attention path is interleaved cuBLAS batched matmuls that still
materialize the (seq, seq) score matrix in HBM. TPU-native design: one
Pallas kernel per (batch*head, q-block) grid cell streams K/V blocks through
VMEM with an online-softmax accumulator, so scores never hit HBM and the
matmuls stay on the MXU. Backward is a recompute VJP (flash-style: saves
only out + logsumexp residuals, rebuilds P per block).

What the three kernels move and visit (``mx_flash_fwd``,
``mx_flash_bwd_dkv``, ``mx_flash_bwd_dq``):

* The softmax statistics (``lse``, ``delta``) are ``(bh, 1, seq)`` with the
  sequence on the lanes.  A minor dimension of 1 is stored as one live lane
  in 128, so nothing here has one.  The backward tiles are computed
  transposed (``sT = k @ q.T``, shape ``(block_k, block_q)``): the
  statistics then broadcast along sublanes, ``dv = pT @ do`` and
  ``dk = dsT @ q`` are plain products, and only ``dq = dsT.T @ k`` takes
  the transposed-LHS form.
* Gradients accumulate in fp32 VMEM scratch across the sequential grid
  axis and leave the kernel once, in the operands' dtype.
* Grouped KV heads: K and V keep their own head count.  The forward and
  dQ read the KV head of their query head through the index map (eight
  consecutive query heads name the same block, so it is fetched once);
  dK/dV walk the group's query heads into one accumulator.  A causal
  ``window`` skips the tiles left of the band as the diagonal skips
  those above it.  With one KV head a query head and no window every
  branch below is taken in Python and the kernels trace as they did.
* Causal tiles (``tile_counts``): a tile above the diagonal is *skipped* —
  not computed, and its blocks not fetched, because the index maps clamp
  to the nearest tile that has work, so a skipped step names the block
  already resident.  Only a tile the diagonal (or the ragged end of K)
  crosses is *masked* in the sense that its mask changes something.  The
  kernels build the mask in every tile they run wherever the shapes have
  one at all: a second, unmasked copy of the tile body for the tiles
  wholly under the diagonal gave no time on the v5e and cost set-up
  (PERF.md section 6, PR 26), so there is one body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry as _telemetry

_NEG_INF = -1e30
_LANES = 128
_VMEM_DEFAULT = 16 << 20    # Mosaic's scoped VMEM limit a kernel

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


# ---------------------------------------------------------------------------
# the tile schedule: which (q-block a, k-block b) tiles run, and in which
# of those the mask changes something.  ``_tile_runs`` serves python ints
# (the counter, the tests) and traced grid indices (the kernels) alike.
# ---------------------------------------------------------------------------

def _tile_runs(a, b, block_q, block_k, causal, window=None):
    """Some query of q-block ``a`` attends some key of k-block ``b``:
    the tile is not wholly above the diagonal and, with a window, not
    wholly left of the band (query ``i`` attends ``0 <= i - j <
    window``)."""
    if not causal:
        return True
    runs = a * block_q + (block_q - 1) >= b * block_k
    if window is None:
        return runs
    return runs & (a * block_q < (b + 1) * block_k - 1 + window)


def _tile_masked(a, b, block_q, block_k, seq_k, causal, window=None):
    """Some (query, key) pair of a running tile is not attended: the
    diagonal or the band's left edge crosses it, or K's zero padding
    starts inside it."""
    edge = (b + 1) * block_k > seq_k
    if not causal:
        return edge
    masked = edge or a * block_q < (b + 1) * block_k - 1
    if window is None:
        return masked
    return masked or a * block_q + (block_q - 1) - b * block_k >= window


def _when_runs(a, b, block_q, block_k, causal, window=None):
    """Decorator of a backward tile body: run it where the tile has work,
    which without a diagonal is everywhere (no branch is traced)."""
    if not causal:
        return lambda body: body()
    return pl.when(_tile_runs(a, b, block_q, block_k, causal, window))


def _div(x, n):
    """``x // n`` for ``x >= 0``.  On a traced grid index ``//`` is a
    floor division (a division, two signs, a select), traced and lowered
    at every use; the truncating division is one operation."""
    return x // n if isinstance(x, int) else jax.lax.div(x, np.int32(n))


def _fwd_visits(qi, nk, block_q, block_k, causal, minimum=min):
    """K blocks the forward visits for q-block ``qi``: those that hold a
    key some query of the block attends, ``ceil((qi+1)*block_q/block_k)``
    under the diagonal."""
    if not causal:
        return nk
    return minimum(nk, _div((qi + 1) * block_q + block_k - 1, block_k))


def _first_k_block(q0, block_k, window, maximum=max):
    """First K block the band reaches for queries starting at ``q0``:
    the block of key ``q0 - (window - 1)``."""
    return _div(maximum(q0 - (window - 1), 0), block_k)


def tile_counts(seq_q, seq_k, block_q, block_k, causal, window=None):
    """Tiles one head's kernels compute unmasked (``computed``), compute
    where the mask changes something (``masked``) and skip (``skipped``).
    A tile is one grid step of either backward kernel and one trip of the
    forward's k-loop (``_fwd_visits`` and ``_first_k_block`` bound the
    same tiles a q-block).  Blocks clamp to the sequence as in the
    kernels."""
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)
    counts = {"computed": 0, "masked": 0, "skipped": 0}
    for a in range(-(-seq_q // bq)):
        for b in range(-(-seq_k // bk)):
            if not _tile_runs(a, b, bq, bk, causal, window):
                counts["skipped"] += 1
            elif _tile_masked(a, b, bq, bk, seq_k, causal, window):
                counts["masked"] += 1
            else:
                counts["computed"] += 1
    return counts


def _count_tiles(kernels, bh, seq_q, seq_k, block_q, block_k, causal,
                 window=None):
    """``kernel.flash_tiles_total``: once a traced call, tiles x ``bh``
    (query heads: each runs its own tiles, grouped or not)."""
    counts = tile_counts(seq_q, seq_k, block_q, block_k, causal, window)
    for kernel in kernels:
        for kind, n in counts.items():
            _telemetry.inc("kernel.flash_tiles_total", n * bh,
                           kernel=kernel, kind=kind)


def _attended(q0, k0, shape, q_axis, seq_k, causal, window=None):
    """The mask of one tile whose queries start at ``q0`` along
    ``q_axis`` and whose keys start at ``k0`` along the other axis.
    In-kernel loads of a zero-padded final K block see zeros, not
    nothing: keys at or beyond ``seq_k`` are masked explicitly."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    valid = k_pos < seq_k
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        valid &= q_pos >= k_pos
        if window is not None:
            valid &= q_pos - k_pos < window
    return valid


def _selected(tile):
    """A selection tile (int8, key-major) as a mask: widened first, so
    that the comparison is made in the layout of the iota masks."""
    return tile.astype(jnp.int32) != 0


def _pad_selection(sel, sk_full, sq_full):
    """Zero rows and columns (nothing selected) up to the padded
    ``(seq_k, seq_q)``."""
    _, sk, sq = sel.shape
    if sk == sk_full and sq == sq_full:
        return sel
    return jnp.pad(sel, ((0, 0), (0, sk_full - sk), (0, sq_full - sq)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *refs, block_k, seq_k, causal, scale,
                block_q, window=None):
    """One q-block against its k-blocks, tiles transposed as in the
    backward (``sT = k @ q.T``): the running max and sum are ``(1, bq)``
    rows, reduced over sublanes and broadcast along them, and ``lse``
    leaves as it is kept.  The accumulator is ``(d, bq)`` and is turned
    once, at the end.  ``refs`` are the outputs, after the selection's
    ``(seq_k, bq)`` column block where the call has one."""
    *sel_ref, o_ref, lse_ref = refs
    qi = pl.program_id(1)
    # keep MXU operands in the input dtype (bf16 on TPU): fp32 matmul
    # costs ~8x the MXU passes; accumulation is fp32 regardless via
    # preferred_element_type. Softmax math stays fp32.
    q = q_ref[0]                                      # (bq, d)
    bq, d = q.shape
    nk = k_ref.shape[1] // block_k

    masked = causal or seq_k % block_k != 0 or bool(sel_ref)

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(k0, block_k), :]
        v = v_ref[0, pl.ds(k0, block_k), :]
        sT = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            valid = _attended(qi * block_q, k0, sT.shape, 1, seq_k, causal,
                              window)
            if sel_ref:
                valid &= _selected(sel_ref[0][0, pl.ds(k0, block_k), :])
            sT = jnp.where(valid, sT, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sT, axis=0, keepdims=True))
        pT = jnp.exp(sT - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(pT, axis=0, keepdims=True)
        acc = corr * acc + jax.lax.dot_general(
            v, pT.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    carry = (jnp.full((1, bq), _NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32),
             jnp.zeros((d, bq), jnp.float32))
    n_visit = _fwd_visits(qi, nk, block_q, block_k, causal, jnp.minimum)
    first = 0 if window is None else _first_k_block(
        qi * block_q, block_k, window, jnp.maximum)
    carry = jax.lax.fori_loop(first, n_visit, body, carry)
    m, l, acc = carry
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).T.astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def _kv_row(group):
    """The K/V row of query row ``i`` (rows are batch-major, heads
    minor, so consecutive ``group`` query heads share one KV head)."""
    if group == 1:
        return lambda i: i
    return lambda i: _div(i, group)


# ``_fwd`` and ``_bwd_once`` are traced once a signature and their
# equations inlined where they are called: a kernel's body is 25 ms of
# Python to trace (130 inside a recomputation region's transposition on
# the chip's host) and a step calls each once a layer (PERF.md section
# 6, PR 44).  The tiles are counted outside them, a call.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8), inline=True)
def _fwd(q, k, v, causal, scale, block_q, block_k, interpret, window=None,
         sel=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    kv = _kv_row(bh // k.shape[0])
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # pad to block multiples: in-kernel pl.ds loads clamp at the array end,
    # which would silently misalign a partial final block; padded keys are
    # masked out via seq_k inside the kernel, padded queries sliced off below
    sq_pad = -sq % block_q
    sk_pad = -sk % block_k
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0)))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, sk_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad), (0, 0)))
    sq_full, sk_full = sq + sq_pad, sk + sk_pad
    grid = (bh, sq_full // block_q)
    # whole K and V of a head stay resident, double-buffered: past the
    # default scoped limit (16 MiB: fp32 at seq 8192) the call asks for
    # what it holds; shorter calls are compiled as they always were
    resident = 4 * sk_full * d * k.dtype.itemsize
    operands, sel_specs = (q, k, v), []
    if sel is not None:
        # a q-block's column of the selection, whole like K and V, named
        # by the batch row of the head: (seq_k, block_q) int8, twice
        heads = bh // sel.shape[0]
        operands += (_pad_selection(sel, sk_full, sq_full),)
        sel_specs = [pl.BlockSpec((1, sk_full, block_q),
                                  lambda i, j: (_div(i, heads), 0, j))]
        resident += 2 * sk_full * block_q
    extra = {} if resident <= _VMEM_DEFAULT - (4 << 20) else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=resident + (16 << 20))}
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, seq_k=sk, causal=causal, scale=scale,
        block_q=block_q, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk_full, d), lambda i, j: (kv(i), 0, 0)),
            pl.BlockSpec((1, sk_full, d), lambda i, j: (kv(i), 0, 0)),
        ] + sel_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_full, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq_full), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_fwd",
        **extra,
    )(*operands)
    if sq_pad:
        out = out[:, :sq]
        lse = lse[:, :, :sq]
    return out, lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret, window=None, stats=False, sel=None):
    """``sel``: a selection ``(batch, seq_k, seq_q)`` int8, or None: an
    empty pytree, so such a call has no operand and no residual for it.
    ``stats``: return ``(out, lse)``, the forward kernel's second result
    (the backward's residual) beside its first."""
    out, lse = _fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                    window, sel)
    return (out, lse) if stats else out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret, window=None, stats=False, sel=None):
    out, lse = _fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                    window, sel)
    return ((out, lse) if stats else out), (q, k, v, out, lse, sel)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_tile(q, do, lse, delta, kb, vb, q0, k0, seq_k, causal, scale,
              window=None, sel=None):
    """Shared recompute for one tile, transposed: returns (pT, dsT), both
    ``(block_k, block_q)``.

    pT = exp(sT - lse) rebuilt from the saved logsumexp; dsT =
    pT*(dpT - delta)*scale (standard flash-attention backward tile math).
    ``lse`` and ``delta`` are ``(1, block_q)`` rows.  MXU operands stay in
    the input dtype with fp32 accumulation; only the softmax algebra is
    fp32.  Zero-padded queries need no mask: their ``do`` and ``q`` rows
    are zero, so they add nothing to dv and dk.
    """
    sT = jax.lax.dot_general(kb, q, _NT,
                             preferred_element_type=jnp.float32) * scale
    pT = jnp.exp(sT - lse)
    if causal or seq_k % kb.shape[0] != 0 or sel is not None:
        valid = _attended(q0, k0, sT.shape, 1, seq_k, causal, window)
        if sel is not None:
            valid &= _selected(sel)
        pT = jnp.where(valid, pT, 0.0)
    dpT = jax.lax.dot_general(vb, do, _NT,
                              preferred_element_type=jnp.float32)
    dsT = pT * (dpT - delta) * scale
    return pT, dsT


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, *refs,
                    block_q, block_k, seq_k, causal, scale, window=None,
                    group=1):
    """dK/dV for one k-block, accumulated in VMEM over sequential q-block
    steps (grid (bh, nk, nq): the last axis revisits the same output
    block, written once on its last step).  With grouped KV heads the
    grid is (b * kv_heads, nk, group, nq): the last two axes walk the
    q-blocks of every query head of the group into the same
    accumulator.  ``refs``: the selection's tile where the call has one,
    the two outputs, the two accumulators."""
    *sel_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    j = pl.program_id(1)
    q_axis = 2 if group == 1 else 3
    if group == 1:
        qi = pl.program_id(2)

        def is_step(g_at, q_at):    # traced where it is used, as before
            return qi == q_at
    else:
        g, qi = pl.program_id(2), pl.program_id(3)

        def is_step(g_at, q_at):
            return (g == g_at) & (qi == q_at)

    @pl.when(is_step(0, 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @_when_runs(qi, j, block_q, block_k, causal, window)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        pT, dsT = _bwd_tile(q, do, lse_ref[0], delta_ref[0], k_ref[0],
                            v_ref[0], qi * block_q, j * block_k, seq_k,
                            causal, scale, window,
                            *(r[0] for r in sel_ref))
        dv_acc[...] += jax.lax.dot_general(
            pT.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            dsT.astype(q.dtype), q, _NN,
            preferred_element_type=jnp.float32)

    @pl.when(is_step(group - 1, pl.num_programs(q_axis) - 1))
    def _store():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, *refs,
                   block_q, block_k, seq_k, causal, scale, window=None):
    """dQ for one q-block, accumulated in VMEM over sequential k-block
    steps.  ``refs``: the selection's tile where the call has one, the
    output, the accumulator."""
    *sel_ref, dq_ref, dq_acc = refs
    qi, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @_when_runs(qi, j, block_q, block_k, causal, window)
    def _compute():
        kb = k_ref[0]
        _, dsT = _bwd_tile(q_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
                           kb, v_ref[0], qi * block_q, j * block_k, seq_k,
                           causal, scale, window,
                           *(r[0] for r in sel_ref))
        dq_acc[...] += jax.lax.dot_general(
            dsT.astype(kb.dtype), kb, _TN,
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, window, stats, res, do):
    """The backward of ``_flash``: its tiles counted a call, its kernels
    traced once a signature (``_bwd_once``)."""
    (bh, sq, _), sk = res[0].shape, res[1].shape[1]
    if _telemetry._active:
        _count_tiles(("bwd_dkv", "bwd_dq"), bh, sq, sk, min(bwd_block_q, sq),
                     min(bwd_block_k, sk), causal, window)
    return _bwd_once(causal, scale, block_q, block_k, bwd_block_q,
                     bwd_block_k, interpret, window, stats, res, do)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8),
                   inline=True)
def _bwd_once(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
              interpret, window, stats, res, do):
    """Blocked Pallas backward (flash-style residuals: out + logsumexp).

    Memory is O(seq): P is rebuilt per (q-block, k-block) tile in VMEM from
    the saved lse, never materialized in HBM — the training-side completion
    of the forward kernel's claim (round-1 VJP materialized (s, s) scores).
    Tiles independently of the forward (bwd_block_q/bwd_block_k): the
    backward holds ~2x the forward's accumulators per tile, so its tuned
    optimum is usually smaller.
    """
    q, k, v, out, lse, sel = res
    if stats:
        do, _ = do      # the statistics are handed out as constants
    bh, sq, d = q.shape
    bkv, sk = k.shape[:2]
    group = bh // bkv
    bq = min(bwd_block_q, sq)
    bk = min(bwd_block_k, sk)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]

    sq_pad, sk_pad = -sq % bq, -sk % bk
    if sq_pad:
        pad = ((0, 0), (0, sq_pad), (0, 0))
        q, do = jnp.pad(q, pad), jnp.pad(do, pad)
        pad = ((0, 0), (0, 0), (0, sq_pad))
        lse, delta = jnp.pad(lse, pad), jnp.pad(delta, pad)
    if sk_pad:
        pad = ((0, 0), (0, sk_pad), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    sq_full, sk_full = sq + sq_pad, sk + sk_pad
    nq, nk = sq_full // bq, sk_full // bk
    # the kernels' operand list: empty without a selection
    sel = [] if sel is None else [_pad_selection(sel, sk_full, sq_full)]

    # a step that has no work names the block of the nearest step that
    # has, so the pipeline finds it resident and fetches nothing.  An
    # index map is traced once a BlockSpec a layer: lax primitives on the
    # int32 grid indices, not jnp's operators (a jit dispatch each)
    if causal and window is not None:
        # the band ends on both sides: clamp to the first and the last
        # tile of the row (dQ) or column (dK/dV) that has work
        def q_of(a, b):
            k0 = jax.lax.mul(b, np.int32(bk))
            first = _div(k0, bq)
            last = _div(jax.lax.add(k0, np.int32(bk - 2 + window)), bq)
            return jax.lax.min(jax.lax.min(jax.lax.max(a, first), last),
                               np.int32(nq - 1))

        def k_of(a, b):
            q0 = jax.lax.mul(a, np.int32(bq))
            first = _div(jax.lax.max(jax.lax.sub(q0, np.int32(window - 1)),
                                     np.int32(0)), bk)
            last = _div(jax.lax.add(q0, np.int32(bq - 1)), bk)
            return jax.lax.min(jax.lax.max(b, first), last)
    elif causal:
        def q_of(a, b):     # dK/dV: first q-block that reaches k-block b
            first = _div(jax.lax.mul(b, np.int32(bk)), bq)
            return jax.lax.min(jax.lax.max(a, first), np.int32(nq - 1))

        def k_of(a, b):     # dQ: last k-block that q-block a reaches
            last = _div(jax.lax.add(jax.lax.mul(a, np.int32(bq)),
                                    np.int32(bq - 1)), bk)
            return jax.lax.min(b, last)
    else:
        def q_of(a, b):
            return a

        def k_of(a, b):
            return b

    kw = dict(block_q=bq, block_k=bk, seq_k=sk, causal=causal, scale=scale,
              window=window)
    if group == 1:
        dkv_grid = (bh, nk, nq)
        dkv_q = pl.BlockSpec((1, bq, d), lambda i, b, a: (i, q_of(a, b), 0))
        dkv_r = pl.BlockSpec((1, 1, bq), lambda i, b, a: (i, 0, q_of(a, b)))
        dkv_k = pl.BlockSpec((1, bk, d), lambda i, b, a: (i, b, 0))
        dkv_sel = [pl.BlockSpec(
            (1, bk, bq), lambda i, b, a: (
                _div(i, bh // s.shape[0]), b, q_of(a, b))) for s in sel]
    else:
        # one KV head a grid row; its query heads are rows i*group + g
        def q_row(i, g):
            return jax.lax.add(jax.lax.mul(i, np.int32(group)), g)

        dkv_grid = (bkv, nk, group, nq)
        dkv_q = pl.BlockSpec(
            (1, bq, d), lambda i, b, g, a: (q_row(i, g), q_of(a, b), 0))
        dkv_r = pl.BlockSpec(
            (1, 1, bq), lambda i, b, g, a: (q_row(i, g), 0, q_of(a, b)))
        dkv_k = pl.BlockSpec((1, bk, d), lambda i, b, g, a: (i, b, 0))
        dkv_sel = [pl.BlockSpec(
            (1, bk, bq), lambda i, b, g, a: (
                _div(i, bkv // s.shape[0]), b, q_of(a, b))) for s in sel]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, group=group, **kw),
        grid=dkv_grid,
        in_specs=[dkv_q, dkv_q, dkv_r, dkv_r, dkv_k, dkv_k] + dkv_sel,
        out_specs=[dkv_k, dkv_k],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, sk_full, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, sk_full, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="mx_flash_bwd_dkv",
    )(q, do, lse, delta, k, v, *sel)

    q_spec = pl.BlockSpec((1, bq, d), lambda i, a, b: (i, a, 0))
    r_spec = pl.BlockSpec((1, 1, bq), lambda i, a, b: (i, 0, a))
    kv = _kv_row(group)
    k_spec = pl.BlockSpec((1, bk, d),
                          lambda i, a, b: (kv(i), k_of(a, b), 0))
    sel_spec = [pl.BlockSpec(
        (1, bk, bq), lambda i, a, b: (
            _div(i, bh // s.shape[0]), k_of(a, b), a)) for s in sel]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(bh, nq, nk),
        in_specs=[q_spec, q_spec, r_spec, r_spec, k_spec, k_spec] + sel_spec,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq_full, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="mx_flash_bwd_dq",
    )(q, do, lse, delta, k, v, *sel)

    if sq_pad:
        dq = dq[:, :sq]
    if sk_pad:
        dk, dv = dk[:, :sk], dv[:, :sk]
    # a selection is data with no tangent
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, bwd_block_q=None, bwd_block_k=None,
                    interpret=False, window=None, selection=None,
                    return_lse=False):
    """Multi-head attention, scores never materialized in HBM.

    q: (batch, heads, seq_q, head_dim); k/v: (batch, kv_heads, seq_k,
    head_dim) with ``heads`` a multiple of ``kv_heads``: query head ``i``
    reads KV head ``i // (heads // kv_heads)`` through the K/V index maps
    (nothing is repeated in HBM) and dK/dV sum over the group inside the
    kernel.  q, k and v share one ``head_dim`` (a ValueError otherwise);
    compiled for a v5e and run there at 64 (padded to the 128 lanes), 128 and
    256.  ``window`` (with ``causal``) keeps query ``i`` to the keys
    ``0 <= i - j < window``; tiles left of that band are skipped like tiles
    above the diagonal.  ``selection`` (batch, seq_q, seq_k), integer or
    bool, keeps query ``i`` of a batch row to the keys ``j`` where it is
    nonzero, for all the row's heads alike and on top of ``causal`` /
    ``window``; it has no gradient and skips no tile.  A query it leaves no
    key gets an undefined (finite) row.  Returns q's shape; with
    ``return_lse`` a pair of that and the log-sum-exp of every query's scaled
    logits over its keys, (batch, heads, seq_q) float32, a constant.

    Blocks default to ``mx.autotune.resolve_blocks`` (a bucket's tuned
    winner, else the device's static row), the backward's independently;
    explicit values win.  On a TPU a block under its sequence is a multiple
    of the 128 lanes.
    """
    b, h, sq, d = q.shape
    hk, sk = k.shape[1:3]
    if h % hk:
        raise ValueError(f"{h} query heads do not group over {hk} KV heads")
    if not d == k.shape[3] == v.shape[3]:
        raise ValueError(f"head widths {d}, {k.shape[3]} and {v.shape[3]} of "
                         "q, k and v: the kernels take one head width")
    if window is not None and not causal:
        raise ValueError("a window is a causal band: pass causal=True")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if block_q is None or block_k is None:
        from ...autotune.kernels import resolve_blocks
        fb = resolve_blocks("flash_attention", (sq, sk, d))
        block_q = fb["block_q"] if block_q is None else block_q
        block_k = fb["block_k"] if block_k is None else block_k
    if bwd_block_q is None or bwd_block_k is None:
        from ...autotune.kernels import resolve_blocks
        bb = resolve_blocks("flash_attention_bwd", (sq, sk, d))
        bwd_block_q = bb["block_q"] if bwd_block_q is None else bwd_block_q
        bwd_block_k = bb["block_k"] if bwd_block_k is None else bwd_block_k
    # counted here, where a call is traced once: under jax.grad the
    # forward kernel's python runs for the primal and again for the VJP
    if _telemetry._active:
        _count_tiles(("fwd",), b * h, sq, sk, block_q, block_k, causal,
                     window)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hk, sk, d)
    vr = v.reshape(b * hk, sk, d)
    # TPU lanes are 128 wide: a 64-dim head halves every load/store and
    # forces relayouts. Zero-pad head_dim to the lane width — zeros add
    # nothing to q·k^T and the padded tail of out is exactly zero.
    d_pad = -d % _LANES if d < _LANES else 0
    if d_pad:
        pad = ((0, 0), (0, 0), (0, d_pad))
        qr, kr, vr = (jnp.pad(qr, pad), jnp.pad(kr, pad), jnp.pad(vr, pad))
    sel = None
    if selection is not None:
        if selection.shape != (b, sq, sk):
            raise ValueError(f"selection {selection.shape} is not (batch, "
                             f"seq_q, seq_k) = {(b, sq, sk)}")
        # key-major once, for all three kernels
        sel = jnp.swapaxes(selection.astype(jnp.int8), 1, 2)
    out = _flash(qr, kr, vr, causal, scale, block_q, block_k, bwd_block_q,
                 bwd_block_k, interpret, window, return_lse, sel)
    if return_lse:
        out, lse = out
    if d_pad:
        out = out[..., :d]
    out = out.reshape(b, h, sq, d)
    if return_lse:
        return out, jax.lax.stop_gradient(lse).reshape(b, h, sq)
    return out
