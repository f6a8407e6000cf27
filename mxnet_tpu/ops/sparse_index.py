"""A learned sparse attention's indexer: scores, selection, alignment.

A small side network (the "lightning indexer" of the DeepSeek-V3.2
report) scores every earlier position for every query; the attention
then reads only the ``topk`` best.  Three functions on raw ``jax``
arrays, composed by ``nn.IndexedAttention``:

* ``index_scores``: ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) /
  sqrt(d)`` for ``j`` over the indexer's heads.  The per-head products
  are ``(heads, seq, seq)``; only the ``(seq, seq)`` sum is ever whole.
  Which pass runs is decided by what the call can see.  On a TPU, at a
  sequence of whole 512-blocks (and a head count and width whose
  q-block VMEM holds), it is the Pallas kernels ``mx_dsa_scores`` and,
  backward, ``mx_dsa_scores_bwd`` (``ops/pallas/dsa_scores.py``): a
  tile's per-head products live in VMEM only, tiles above the diagonal
  are skipped, and the scores are emitted **key-major** — the layout
  XLA gives their every consumer here (the top-k's counts and the
  log-sum-exp reduce over the keys; the selection is key-major too), so
  the ``swapaxes`` round the kernels are layouts and the step holds no
  second copy of a ``(seq, seq)`` array.  Everywhere else — the CPU, the
  tier-1 tests, short or ragged sequences — it is the XLA composition
  ``_composed_scores``: the products exist a block of queries at a time
  (``lax.map``), each block made again in the backward pass; it is also
  the kernels' oracle.
* ``select_topk``: for each query the ``topk`` positions ``s <= t`` with
  the largest scores (all of them while ``t < topk``), ties to the lower
  ``s``, as an int8 mask.  No sort: the ``topk``-th largest score of a
  row is found by bisection on the bits of an order-preserving integer
  key — 32 counting passes over the scores, whatever ``topk`` — and the
  mask is one comparison with it.  Which pass runs is decided by what
  the call can see.  On a TPU, at a sequence of whole blocks whose panel
  VMEM holds, it is the Pallas kernel ``mx_dsa_select``
  (``ops/pallas/dsa_select.py``): a block of queries' scores, key-major
  as ``mx_dsa_scores`` emits them, are fetched once, the 32 passes count
  over them in VMEM up to the diagonal, and one sweep writes the mask
  key-major, as the flash kernels and ``mx_dsa_align`` take it; a panel
  in which some row ties at its threshold finds, by a second bisection
  on the key index, the first of the equal keys it may admit.
  Everywhere else — the CPU, the tier-1 tests, short or ragged
  sequences — it is the XLA bisection ``_composed_select``, each of
  whose passes reads the ``(seq, seq)`` keys from HBM, and whose rows
  that tie take a second path (a running count along the row) that a
  ``lax.cond`` enters only when some row needs it; it is also the
  kernel's oracle.
* ``align_loss``: ``mean_t KL(p_t || softmax_{S_t}(I[t, :]))`` with
  ``p_t`` the attention's own probabilities over the selected keys,
  averaged over the heads — what the indexer is trained towards.  The
  attention's probabilities are ``(heads, seq, seq)`` too: one pass over
  q and k computes the value and, in the same pass, the closed-form
  gradient ``(softmax_{S_t}(I) - p) / T``, which is all the backward
  pass keeps.  Nothing else gets a gradient from it.  Which pass runs is
  decided by what the call can see.  Handed each head's log-sum-exp over
  the selected keys (the attention core's forward flash kernel computed
  it: a TPU, a sequence of 512 or more) at a sequence its blocks divide
  into, it is the Pallas kernel ``mx_dsa_align``
  (``ops/pallas/dsa_align.py``), in which a tile's per-head
  probabilities never leave VMEM.  Everywhere else — the CPU, the
  tier-1 tests, short or ragged sequences — it is the XLA composition
  ``_align_pass``, in query blocks (``lax.map``), which writes each
  block's ``(heads, block, seq)`` logits to HBM; it is also the kernel's
  oracle.

The selection is a mask ``(batch, seq, seq)`` and not gathered keys: the
flash kernels take it as an operand (``ops/pallas/flash_attention.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: bytes of per-head products one block of queries may hold
_BLOCK_BYTES = 1 << 27
#: sixteenths of the sequence ``selection_counts`` sorts pairs into
GRID = 16


def _query_block(seq, heads):
    """Queries a block: a power of two that divides ``seq`` and keeps
    ``heads x block x seq`` float32 under ``_BLOCK_BYTES``."""
    want = max(1, _BLOCK_BYTES // (4 * heads * seq))
    return math.gcd(seq, 1 << (want.bit_length() - 1))


def _blocked(t, block):
    """(b, s, ...) -> (s / block, b, block, ...): the mapped axis first."""
    b, s = t.shape[:2]
    return jnp.moveaxis(t.reshape(b, s // block, block, *t.shape[2:]), 1, 0)


def _unblocked(t):
    """(n, b, block, ...) -> (b, n * block, ...)."""
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape(t.shape[0], t.shape[1] * t.shape[2], *t.shape[3:])


def index_scores(q_idx, k_idx, weights):
    """q_idx (b, s, heads, d), k_idx (b, s, d), weights (b, s, heads) ->
    scores (b, s, s) float32; entries above the diagonal mean nothing
    (the composition computes them, the kernels write zeros in the tiles
    wholly above it).  The products take their operands in the type they
    come in and accumulate in float32; everything after is float32.

    Which pass runs is decided by what the call can see: on a TPU, at a
    sequence ``ops/pallas/dsa_scores.py``'s blocks divide into and a
    head count and width whose q-block it can hold, the Pallas kernels;
    everywhere else the XLA composition, which is also their oracle."""
    from .. import runtime
    from .pallas import dsa_scores
    b, s, heads, d = q_idx.shape
    weights = weights.astype(jnp.float32) * (1.0 / math.sqrt(d))
    if runtime.on_tpu() and dsa_scores.fits(s, heads, d,
                                            q_idx.dtype.itemsize):
        return _kernel_scores(q_idx, k_idx, weights)
    return _composed_scores(q_idx, k_idx, weights)


def _composed_scores(q_idx, k_idx, weights):
    """The XLA composition (and the kernels' oracle): the per-head
    products a block of queries at a time, each block made again in the
    backward pass.  ``weights`` float32, the scale in them."""
    b, s, heads, d = q_idx.shape
    block = _query_block(s, heads)

    @jax.checkpoint
    def one(args):
        qb, wb = args                                   # (b, block, h, ..)
        per_head = jnp.einsum("bqhd,bkd->bqhk", qb, k_idx,
                              preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(per_head) * wb[..., None], axis=2)

    return _unblocked(jax.lax.map(
        one, (_blocked(q_idx, block), _blocked(weights, block))))


def _batch_over_dp(kernel, *operands):
    """``kernel(*operands)``, every operand and result with the batch
    first.  GSPMD cannot partition a Mosaic call, so under a mesh it is
    a ``shard_map`` with the batch over 'dp' (``ops/attention.py::
    _flash``); the indexer's and the attention's heads are summed inside
    the kernels, so they stay whole on every device."""
    from .attention import _kernel_mesh, _mesh_axis
    mesh = _kernel_mesh()
    if mesh is None:
        return kernel(*operands)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    spec = P(_mesh_axis(mesh, "dp", operands[0].shape[0]))
    return shard_map(kernel, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)(*operands)


def _heads_first(t):
    """(b, s, heads, ...) <-> (b, heads, s, ...)."""
    return jnp.swapaxes(t, 1, 2)


def _scores_kernel_pass(q_idx, k_idx, weights):
    """``_composed_scores`` by ``ops/pallas/dsa_scores.py``.  The kernel
    emits the scores key-major; the ``swapaxes`` is a layout to XLA."""
    from .. import runtime
    from .pallas.dsa_scores import scores_pass

    def kernel(q, k, w):
        return scores_pass(_heads_first(q), k, _heads_first(w),
                           interpret=runtime.pallas_interpret())

    return jnp.swapaxes(_batch_over_dp(kernel, q_idx, k_idx, weights), 1, 2)


_kernel_scores = jax.custom_vjp(_scores_kernel_pass)


def _kernel_scores_fwd(q_idx, k_idx, weights):
    return (_scores_kernel_pass(q_idx, k_idx, weights),
            (q_idx, k_idx, weights))


def _kernel_scores_bwd(res, g):
    from .. import runtime
    from .pallas.dsa_scores import scores_bwd_pass
    q_idx, k_idx, weights = res

    def kernel(q, k, w, g_t):
        dq, dk, dw = scores_bwd_pass(_heads_first(q), k, _heads_first(w),
                                     g_t, interpret=runtime.pallas_interpret())
        return (_heads_first(dq).astype(q.dtype), dk.astype(k.dtype),
                _heads_first(dw))

    # traced under the caller's scopes, ``transpose(jvp(...))`` round
    # them, like any other backward operation
    return _batch_over_dp(kernel, q_idx, k_idx, weights,
                          jnp.swapaxes(g, 1, 2))


_kernel_scores.defvjp(_kernel_scores_fwd, _kernel_scores_bwd)


def _ordered_key(x):
    """float32 -> uint32 with the same order (no NaNs): the sign bit
    flipped on positives, every bit on negatives.  Finite scores give
    keys above 0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return bits ^ jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                            jnp.uint32(0x80000000))


def select_topk(scores, topk):
    """scores (b, s, s) float32 -> int8 (b, s, s): 1 where query ``t``
    reads key ``s``: ``s <= t`` and the score is among the row's ``topk``
    largest of those (ties to the lower ``s``).  Exactly
    ``min(t + 1, topk)`` a row.

    Which pass runs is decided by what the call can see: on a TPU, at a
    sequence one of ``ops/pallas/dsa_select.py``'s blocks divides into
    and whose panel VMEM holds, the Pallas kernel; everywhere else the
    XLA bisection, which is also its oracle."""
    from .. import runtime
    from .pallas import dsa_select
    if runtime.on_tpu() and dsa_select.fits(scores.shape[1]):
        return _kernel_select(scores, topk)
    return _composed_select(scores, topk)


def _kernel_select(scores, topk):
    """``_composed_select`` by ``ops/pallas/dsa_select.py``, which takes
    the scores and returns the mask key-major: each ``swapaxes`` is a
    layout to XLA."""
    from .. import runtime
    from .pallas.dsa_select import select_pass

    def kernel(i_t):
        return select_pass(i_t, topk, interpret=runtime.pallas_interpret())

    return jnp.swapaxes(_batch_over_dp(kernel, jnp.swapaxes(
        jax.lax.stop_gradient(scores), 1, 2)), 1, 2)


def _composed_select(scores, topk):
    """The XLA bisection (and the kernel's oracle): 32 counting passes
    over the ``(seq, seq)`` keys; rows that tie at their threshold by a
    running count along the row, inside a ``lax.cond``."""
    b, s, _ = scores.shape
    rows = jnp.arange(s, dtype=jnp.int32)[:, None]
    causal = jnp.arange(s, dtype=jnp.int32)[None, :] <= rows
    # keys above the diagonal are 0, under every score's key
    key = jnp.where(causal, _ordered_key(jax.lax.stop_gradient(scores)),
                    jnp.uint32(0))
    want = jnp.minimum(rows[:, 0] + 1, topk)[None, :]          # (1, s)

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        n = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= topk, cand, thr)

    # the largest value ``topk`` or more keys reach: the topk-th largest
    # key of a row that has as many, else 0 (everything under the
    # diagonal is taken)
    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((b, s), jnp.uint32))
    thr = thr[..., None]
    reach = (key >= thr) & causal

    def with_ties():
        # more keys equal the threshold than it may admit: the first
        # ``need`` of them along the row
        above = (key > thr) & causal
        need = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
        equal = (key == thr) & causal
        nth = jnp.cumsum(equal.astype(jnp.int32), axis=-1)
        return above | (equal & (nth <= need[..., None]))

    exact = jnp.all(jnp.sum(reach, axis=-1, dtype=jnp.int32) == want)
    return jax.lax.cond(exact, lambda: reach, with_ties).astype(jnp.int8)


def selection_counts(selection):
    """selection (b, s, s) -> (pairs selected (1,), pairs by sixteenth of
    the sequence the query and the key lie in (16, 16)), int32, summed
    over the batch.  Position ``t`` lies in sixteenth ``16 t // s``."""
    b, s, _ = selection.shape
    hot = ((jnp.arange(s) * GRID // s)[:, None]
           == jnp.arange(GRID)).astype(jnp.int8)
    # integer products, int32 sums: exact whatever the back-end's
    # floating-point product would round
    by_key = jax.lax.dot_general(
        selection.astype(jnp.int8), hot, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                    # (b, s, 16)
    grid = jnp.sum(hot.astype(jnp.int32)[:, :, None]
                   * jnp.sum(by_key, axis=0)[:, None, :], axis=0)
    return jnp.sum(grid).reshape(1), grid


def align_loss(scores, selection, q, k, heads, kv_heads, lse=None):
    """``mean_t KL(p_t || softmax_{S_t}(scores[t]))`` over all ``b * s``
    queries: ``p_t`` is the mean over the ``heads`` of the attention's
    probabilities on the selected keys ``S_t`` (q (b, s, heads * d) and
    k (b, s, kv_heads * d) as the attention core gets them, scaled by
    ``1 / sqrt(d)``), a constant here.  Differentiable in ``scores``
    alone, by the closed form ``(softmax_{S_t}(scores) - p) / (b s)``.

    ``lse`` (b, heads, s), where the core hands it out
    (``multi_head_attention(return_lse=True)``: its flash kernels ran),
    is each head's log-sum-exp over ``S_t``, a constant; with it and a
    sequence ``mx_dsa_align``'s blocks divide into, the pass is the
    Pallas kernel, else the XLA composition."""
    return _align(scores, selection, jax.lax.stop_gradient(q),
                  jax.lax.stop_gradient(k), lse, heads, kv_heads)


def _align_pass(scores, selection, q, k, heads, kv_heads):
    """The XLA composition (and the kernel's oracle): per-head
    probabilities a block of queries at a time."""
    b, s, hd = q.shape
    d = hd // heads
    group = heads // kv_heads
    scale = 1.0 / math.sqrt(d)
    block = _query_block(s, heads)
    kh = k.reshape(b, s, kv_heads, d)

    def one(args):
        qb, ib, mb = args                   # (b, block, ...) of one block
        chosen = mb != 0
        att = jnp.einsum("bqngd,bknd->bngqk",
                         qb.reshape(b, block, kv_heads, group, d), kh,
                         preferred_element_type=jnp.float32) * scale
        att = jax.nn.softmax(jnp.where(chosen[:, None, None], att, -1e30),
                             axis=-1)
        p = jnp.where(chosen, jnp.mean(att, axis=(1, 2)), 0.0)
        logq = jax.nn.log_softmax(
            jnp.where(chosen, ib.astype(jnp.float32), -1e30), axis=-1)
        kl = jnp.sum(jax.scipy.special.xlogy(p, p)
                     - jnp.where(chosen, p * logq, 0.0))
        return kl, jnp.where(chosen, jnp.exp(logq), 0.0) - p

    kl, d_scores = jax.lax.map(one, (
        _blocked(q, block), _blocked(scores, block),
        _blocked(selection, block)))
    tokens = b * s
    return jnp.sum(kl) / tokens, _unblocked(d_scores) / tokens


def _align_kernel_pass(scores, selection, q, k, lse, heads, kv_heads):
    """The same by ``ops/pallas/dsa_align.py``, which takes the scores
    and the selection and returns ``d_scores`` key-major: the layout
    ``mx_dsa_scores`` emits and XLA keeps the selection in, so each
    ``swapaxes`` here is a layout and not a copy."""
    from .. import runtime
    from .pallas.dsa_align import align_pass
    tokens = q.shape[0] * q.shape[1]
    d = q.shape[2] // heads

    def kernel(i_t, m_t, q_, k_, l):
        def split(t, n):        # (b, s, n*d) -> (b, n, s, d)
            return t.reshape(*t.shape[:2], n, d).transpose(0, 2, 1, 3)

        return align_pass(i_t, m_t, split(q_, heads), split(k_, kv_heads), l,
                          tokens, interpret=runtime.pallas_interpret())

    kl, d_scores = _batch_over_dp(
        kernel, jnp.swapaxes(scores, 1, 2), jnp.swapaxes(selection, 1, 2),
        q, k, lse)
    return jnp.sum(kl) / tokens, jnp.swapaxes(d_scores, 1, 2)


def _pass(scores, selection, q, k, lse, heads, kv_heads):
    from .pallas.dsa_align import BLOCK
    if lse is not None and q.shape[1] % BLOCK == 0:
        return _align_kernel_pass(scores, selection, q, k, lse, heads,
                                  kv_heads)
    return _align_pass(scores, selection, q, k, heads, kv_heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _align(scores, selection, q, k, lse, heads, kv_heads):
    return _pass(scores, selection, q, k, lse, heads, kv_heads)[0]


def _align_fwd(scores, selection, q, k, lse, heads, kv_heads):
    return _pass(scores, selection, q, k, lse, heads, kv_heads)


def _align_bwd(heads, kv_heads, d_scores, g):
    return g * d_scores, None, None, None, None


_align.defvjp(_align_fwd, _align_bwd)
