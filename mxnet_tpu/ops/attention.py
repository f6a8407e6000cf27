"""Fused attention dispatch.

Reference parity: src/operator/contrib/transformer.cc:675-828 (interleaved
matmul attention ops, the reference's fastest attention path).

TPU-native design: a single multi_head_attention entry that routes to the
Pallas flash-attention kernel on TPU (ops/pallas/flash_attention.py) and to
an XLA dot_general composition elsewhere — the composition alone already
fuses well (softmax rides the MXU output), flash-attention additionally
avoids materializing the (seq, seq) scores in HBM for long sequences.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import runtime as _runtime, telemetry as _telemetry
from ..numpy.multiarray import _invoke


def _reference_attention(q, k, v, heads, mask=None, causal=False, scale=None,
                         dropout_p=0.0, kv_heads=None, window=None):
    """(batch, seq, heads*dim) XLA composition; K and V carry
    ``kv_heads * dim`` (each KV head repeated over its query heads)."""
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // heads
    kv_heads = heads if kv_heads is None else kv_heads
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qh = q.reshape(b, sq, heads, d).transpose(0, 2, 1, 3)
    kh = k.reshape(b, sk, kv_heads, d).transpose(0, 2, 1, 3)
    vh = v.reshape(b, sk, kv_heads, d).transpose(0, 2, 1, 3)
    if kv_heads != heads:
        kh = jnp.repeat(kh, heads // kv_heads, axis=1)
        vh = jnp.repeat(vh, heads // kv_heads, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        if window is not None:
            cm &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool), -window)
        scores = jnp.where(cm, scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, -1e30)
    att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p:
        from .. import random as _random
        keep = 1.0 - dropout_p
        att = att * jax.random.bernoulli(
            _random._next_key(), keep, att.shape).astype(att.dtype) / keep
    out = jnp.einsum("bhqk,bhkd->bhqd", att, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, sq, heads * d)


# measured on TPU v5e (bf16 operands, bq1024/bk512 blocks, interleaved
# A/B at bs8-16 h12 d64): causal flash wins from seq 512 (5.3 vs 7.7 ms
# at 512, ~6.0 vs ~7.6 at 1024 — the tril mask makes XLA materialize and
# mask the full (s, s) scores); non-causal XLA keeps its fused-softmax
# edge until ~2k where O(s^2) HBM takes over
_FLASH_MIN_SEQ = 2048
_FLASH_MIN_SEQ_CAUSAL = 512


def _scope_mesh():
    """The mesh of the active activation_sharding scope (ShardedTrainStep
    traces under one), or None."""
    from ..parallel import mesh as _pmesh
    rules = _pmesh._act_rules
    return None if rules is None else rules[0]


def _sp_mesh():
    """Active sequence-parallel mesh from an activation_sharding scope, or
    None. The 'sp' axis is the ring-attention ring (parallel/ring_attention
    — the long-context path the brief makes first-class)."""
    mesh = _scope_mesh()
    if mesh is not None and "sp" in mesh.shape and mesh.shape["sp"] > 1:
        return mesh
    return None


def _kernel_mesh():
    """The mesh a Mosaic call made here has to be ``shard_map``ped over,
    or None: no mesh scope, one device, or already inside a shard_map
    over the whole mesh (the compressed-gradient path), where operands
    are per-device and a kernel is called as is."""
    mesh = _scope_mesh()
    if (mesh is None or mesh.size == 1
            or set(jax.sharding.get_abstract_mesh().manual_axes)
            >= set(mesh.axis_names)):
        return None
    return mesh


def _mesh_axis(mesh, name, dim):
    """``name`` where the mesh has that axis and it divides ``dim``,
    else None (the dim stays replicated)."""
    n = int(mesh.shape.get(name, 1))
    return name if n > 1 and dim % n == 0 else None


def _flash(qh, kh, vh, causal, window=None, selection=None,
           return_lse=False):
    """The Pallas flash kernel on (batch, heads, seq, dim) queries and
    (batch, kv_heads, seq, dim) keys and values; ``selection`` (batch,
    seq_q, seq_k), where given, goes with the batch.  With
    ``return_lse`` the result is ``(out, lse (batch, heads, seq))``.

    GSPMD cannot partition a Mosaic kernel — on a multi-device mesh the
    lowering stops with "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map." (four-chip run,
    PR 21; the parent swallowed the error and trained on the XLA
    composition).  So inside a mesh scope the call IS a
    shard_map over every mesh axis: batch over 'dp', heads over 'tp'
    (where the axis exists and divides), each device running the kernel
    on its own block; axes that do not divide leave the dim replicated.
    Heads shard by the KV head count, for all three operands alike: a
    'tp' that divides the KV heads divides the query heads too and keeps
    every group of query heads beside its KV head, and one that does not
    would split a group, so the heads then stay replicated.
    Already inside a shard_map over the whole mesh (the compressed-
    gradient path) the operands are per-device and the kernel is called
    as is.
    """
    from .pallas.flash_attention import flash_attention
    operands = (qh, kh, vh) + (() if selection is None else (selection,))

    def kernel(q, k, v, *sel):
        return flash_attention(q, k, v, causal=causal, window=window,
                               **({"selection": sel[0]} if sel else {}),
                               **({"return_lse": True} if return_lse
                                  else {}))

    mesh = _kernel_mesh()
    if mesh is None:
        return kernel(*operands)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(_mesh_axis(mesh, "dp", qh.shape[0]),
             _mesh_axis(mesh, "tp", kh.shape[1]), None, None)
    # a selection goes with the batch, whole a head
    specs = (spec, spec, spec) + (P(spec[0], None, None),) * (
        len(operands) - 3)
    out_specs = (spec, P(*spec[:3])) if return_lse else spec
    return shard_map(kernel, mesh=mesh, in_specs=specs, out_specs=out_specs,
                     check_vma=False)(*operands)


# -- The KV cache (mx.serve) --------------------------------------------
# A cache leaf is (max_slots, max_seq, heads*dim): a row is the K or V
# projection's row, the heads side by side along the lanes.  The decode
# step's kernel reads it where it lies (a Mosaic operand's layout is fixed,
# so XLA writes the step's row in place and copies nothing); the
# compositions view a row as (heads, dim) for their einsums.  An int8 cache
# is a (values, scales) pair a leaf, scales (max_slots, max_seq, heads)
# float32, one a written row and head.  Rows past a slot's position hold
# whatever was there: nothing reads them before they are written again.


def _int32(x):
    """A slot or row operand, traced or a Python number, as int32."""
    return x.astype(jnp.int32) if hasattr(x, "astype") else jnp.int32(x)


def _heads_apart(rows, heads):
    """(..., heads*dim) cache rows -> (..., heads, dim): the view the
    compositions' einsums take (the cache itself keeps a row whole)."""
    return rows.reshape(rows.shape[:-1] + (heads, rows.shape[-1] // heads))


def _dequantized(values, scales, dtype):
    """int8 (..., heads*dim) rows with their (..., heads) scales ->
    (..., heads, dim) in ``dtype``; fuses into the einsum that reads it."""
    return _heads_apart(values, scales.shape[-1]).astype(dtype) \
        * scales.astype(dtype)[..., None]


def _slot_rows(leaf, slot):
    """One slot of a cache leaf: (max_seq, ...)."""
    return jax.lax.dynamic_index_in_dim(leaf, slot, 0, keepdims=False)


def _step_rows(pos, max_seq):
    """``(lane, row)`` of the decode step's scatter: each slot's own row,
    clipped into the cache."""
    return (jnp.arange(pos.shape[0]),
            jnp.clip(pos.astype(jnp.int32), 0, max_seq - 1))


def _multi_rows(pos, t, max_seq):
    """``(lane, rows)`` of the verify step's scatter: t rows a slot from
    its position on, clipped into the cache."""
    rows = jnp.clip(pos.astype(jnp.int32)[:, None] + jnp.arange(t),
                    0, max_seq - 1)
    return jnp.arange(pos.shape[0])[:, None], rows


def decode_read_block(k_cache):
    """Cache rows a grid step of ``decode_attention``'s kernel reads, where
    the kernel takes a cache leaf like ``k_cache`` — a TPU, one array a
    leaf (no int8 pair), shapes ``ops/pallas/decode_attention.py::fits``
    holds for; None where the composition runs.  ``ServeEngine`` reads
    its ``decode_rows_read_share`` off this."""
    if isinstance(k_cache, (tuple, list)) or len(k_cache.shape) != 3 \
            or not _runtime.on_tpu():
        return None
    from .pallas import decode_attention as kernel
    shape = k_cache.shape[1:] + (jnp.dtype(k_cache.dtype).itemsize,)
    if not kernel.fits(*shape):
        return None
    return kernel._block(*shape)


def _softmax_over_rows(scores, visible, dtype):
    """The compositions' softmax: masked, float32, back in ``dtype``."""
    scores = jnp.where(visible, scores, -1e30)
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)


def write_prefill_kv(k_cache, v_cache, key, value, slot, heads):
    """Write a whole prompt's projected K/V into one cache slot.

    ``key``/``value`` are (1, L, heads*dim) projections; the caches are
    (max_slots, max_seq, heads*dim): a row is the projection's row, the
    heads side by side (what ``decode_attention``'s kernel reads where it
    lies). Rows [slot, :L] are overwritten (rows
    beyond L keep stale values — they are never attended because the decode
    mask is bounded by the slot's position counter and every row below it
    is rewritten in order before it becomes visible). ``slot`` may be a
    traced scalar, so one compiled prefill serves every slot.
    """
    def fn(kc, vc, k, v, s):
        start = (_int32(s), 0, 0)
        return (jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), start),
                jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), start))

    return _invoke(fn, (k_cache, v_cache, key, value, slot),
                   name="write_prefill_kv")


def _quantize_kv_rows(x, heads, int8_max=127.0):
    """Symmetric int8 over each head's ``dim`` of (..., heads*dim) rows:
    one scale per (slot, row, head), (..., heads) float32 — each written
    row computes its own scale, so the fixed-footprint cache never needs
    requantization."""
    xf = _heads_apart(x, heads).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / int8_max
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / scale), -int8_max, int8_max)
    return q.astype(jnp.int8).reshape(x.shape), scale[..., 0]


def write_prefill_kv_q8(k_cache, k_scale, v_cache, v_scale, key, value,
                        slot, heads):
    """int8-cache variant of :func:`write_prefill_kv`: quantizes the
    prompt's projected K/V per (row, head) and writes values + scales.
    Caches are (max_slots, max_seq, heads*dim) int8; scales
    (max_slots, max_seq, heads) float32."""
    def fn(kc, ks, vc, vs, k, v, s):
        kq, ksc = _quantize_kv_rows(k, heads)
        vq, vsc = _quantize_kv_rows(v, heads)
        start = (_int32(s), 0, 0)
        return (jax.lax.dynamic_update_slice(kc, kq, start),
                jax.lax.dynamic_update_slice(ks, ksc, start),
                jax.lax.dynamic_update_slice(vc, vq, start),
                jax.lax.dynamic_update_slice(vs, vsc, start))

    return _invoke(fn, (k_cache, k_scale, v_cache, v_scale, key, value,
                        slot), name="write_prefill_kv_q8")


def copy_cache_rows(cache, src_slot, src_row, dst_slot, dst_row, rows):
    """Copy ``rows`` cache rows (one prefix-cache block) between slots.

    ``cache`` is any pytree whose leaves are (max_slots, max_seq, ...)
    arrays — the fp32 (k, v) pairs and the int8 ((values, scales), ...)
    layout alike, since the per-(slot, row, head) scales share the
    leading two axes and copy with their rows.  Slot/row operands may be
    traced scalars, so ONE compiled executable serves every (src, dst)
    pair; ``rows`` must be static (the serve.prefix_block bucket).  The
    engine's block-copy executable is this function jitted with the
    caches donated."""
    def one(leaf):
        tail = (0,) * (leaf.ndim - 2)
        sizes = (1, rows) + leaf.shape[2:]
        blk = jax.lax.dynamic_slice(
            leaf, (src_slot, src_row) + tail, sizes)
        return jax.lax.dynamic_update_slice(
            leaf, blk, (dst_slot, dst_row) + tail)

    return jax.tree_util.tree_map(one, cache)


def gather_cache_rows(cache, src_slots, src_rows, dst_slot):
    """Rebuild one destination slot from per-row source coordinates:
    row ``r`` of ``dst_slot`` becomes row ``src_rows[r]`` of slot
    ``src_slots[r]``, for every leaf of ``cache`` (same pytree contract
    as :func:`copy_cache_rows`).  ONE gather plus ONE slot-sized write
    per leaf — a whole matched prefix path (blocks scattered across
    donor slots) lands in a single pass, where a per-block
    dynamic_update_slice chain would rewrite the full cache buffer once
    per block.  Rows the caller wants untouched are encoded as identity
    coordinates (``dst_slot``, own row); the gather reads them back
    unchanged.  All operands may be traced; shapes are static."""
    def one(leaf):
        rows = leaf[src_slots, src_rows]
        return jax.lax.dynamic_update_slice(
            leaf, rows[None], (dst_slot,) + (0,) * (leaf.ndim - 1))

    return jax.tree_util.tree_map(one, cache)


def suffix_prefill_attention(q, k, v, k_cache, v_cache, slot, start, heads):
    """Prefix-cache suffix prefill: causal attention of a prompt
    *suffix* (1, Ls, heads*dim) over cache slot ``slot`` whose rows
    [0, start) already hold a copied prefix.  Writes the suffix K/V at
    rows [start, start + Ls) and lets query i attend every cache row
    <= start + i — the copied prefix plus the causal suffix.  ``slot``
    and ``start`` may be traced; the caller guarantees
    start + Ls <= max_seq (the engine falls back to full prefill
    otherwise)."""
    def fn(q, k, v, kc, vc, s, st):
        s32, st32 = _int32(s), _int32(st)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (s32, st32, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (s32, st32, 0))
        kslot = _heads_apart(_slot_rows(kc, s32), heads).astype(q.dtype)
        vslot = _heads_apart(_slot_rows(vc, s32), heads).astype(q.dtype)
        return _suffix_attend(q, kslot, vslot, st32, heads), kc, vc

    return _invoke(fn, (q, k, v, k_cache, v_cache, slot, start),
                   name="suffix_prefill_attention")


def suffix_prefill_attention_q8(q, k, v, k_cache, k_scale, v_cache,
                                v_scale, slot, start, heads):
    """int8-cache variant of :func:`suffix_prefill_attention`: the
    suffix rows quantize with their own per-(row, head) scales before
    the write (scales land beside the copied prefix's scales), and the
    slot's cached K/V dequantizes into the score/value einsums."""
    def fn(q, k, v, kc, ks, vc, vs, s, st):
        s32, st32 = _int32(s), _int32(st)
        kq, ksc = _quantize_kv_rows(k, heads)
        vq, vsc = _quantize_kv_rows(v, heads)
        kc = jax.lax.dynamic_update_slice(kc, kq, (s32, st32, 0))
        ks = jax.lax.dynamic_update_slice(ks, ksc, (s32, st32, 0))
        vc = jax.lax.dynamic_update_slice(vc, vq, (s32, st32, 0))
        vs = jax.lax.dynamic_update_slice(vs, vsc, (s32, st32, 0))
        kslot = _dequantized(_slot_rows(kc, s32), _slot_rows(ks, s32),
                             q.dtype)
        vslot = _dequantized(_slot_rows(vc, s32), _slot_rows(vs, s32),
                             q.dtype)
        return (_suffix_attend(q, kslot, vslot, st32, heads),
                kc, ks, vc, vs)

    return _invoke(fn, (q, k, v, k_cache, k_scale, v_cache, v_scale,
                        slot, start), name="suffix_prefill_attention_q8")


def decode_multi_attention(query, key, value, k_cache, v_cache, positions,
                           heads):
    """k-token cached attention — the speculative-decoding verify step.

    ``query``/``key``/``value`` are (slots, t, heads*dim) projections of
    t tokens per slot; slot i's token j lands at cache row
    positions[i] + j (scatter rows clip at max_seq - 1 like
    :func:`decode_attention` — clipped writes only ever touch rows above
    the slot's position counter, which are rewritten before becoming
    visible).  Query j attends rows <= positions + j, so the t tokens
    verify causally in ONE batched call.  The XLA composition on every
    backend (``decode_attention``'s kernel takes one query a slot)."""
    def fn(q, k, v, kc, vc, pos):
        lane, rows = _multi_rows(pos, q.shape[1], kc.shape[1])
        kc = kc.at[lane, rows].set(k.astype(kc.dtype))
        vc = vc.at[lane, rows].set(v.astype(vc.dtype))
        out = _multi_attend(q, _heads_apart(kc, heads).astype(q.dtype),
                            _heads_apart(vc, heads).astype(q.dtype), pos,
                            heads)
        return out, kc, vc

    return _invoke(fn, (query, key, value, k_cache, v_cache, positions),
                   name="decode_multi_attention")


def decode_multi_attention_q8(query, key, value, k_cache, k_scale, v_cache,
                              v_scale, positions, heads):
    """int8-cache variant of :func:`decode_multi_attention`: each of the
    t written rows quantizes with its own (slot, row, head) scale, the
    dequant fusing into the einsums exactly like
    :func:`decode_attention_q8`."""
    def fn(q, k, v, kc, ks, vc, vs, pos):
        lane, rows = _multi_rows(pos, q.shape[1], kc.shape[1])
        kq, ksc = _quantize_kv_rows(k, heads)
        vq, vsc = _quantize_kv_rows(v, heads)
        kc = kc.at[lane, rows].set(kq)
        ks = ks.at[lane, rows].set(ksc)
        vc = vc.at[lane, rows].set(vq)
        vs = vs.at[lane, rows].set(vsc)
        out = _multi_attend(q, _dequantized(kc, ks, q.dtype),
                            _dequantized(vc, vs, q.dtype), pos, heads)
        return out, kc, ks, vc, vs

    return _invoke(fn, (query, key, value, k_cache, k_scale, v_cache,
                        v_scale, positions),
                   name="decode_multi_attention_q8")


def decode_attention_q8(query, key, value, k_cache, k_scale, v_cache,
                        v_scale, positions, heads):
    """int8-cache variant of :func:`decode_attention`: the cache crosses
    HBM as int8 + per-(slot, row, head) scales and the dequant
    (``astype * scale``) fuses into the score/value einsums, so decode —
    memory-bound on the cache at long contexts — moves a quarter of the
    fp32 bytes. The current token's K/V is quantized with its own row
    scale before the write; attention math itself stays in the query
    dtype with an f32 softmax, exactly like the fp path's composition
    (which this is on every backend: the kernel reads no scales)."""
    def fn(q, k, v, kc, ks, vc, vs, pos):
        lane, row = _step_rows(pos, kc.shape[1])
        kq, ksc = _quantize_kv_rows(k[:, 0], heads)
        vq, vsc = _quantize_kv_rows(v[:, 0], heads)
        kc = kc.at[lane, row].set(kq)
        ks = ks.at[lane, row].set(ksc)
        vc = vc.at[lane, row].set(vq)
        vs = vs.at[lane, row].set(vsc)
        out = _step_attend(q, _dequantized(kc, ks, q.dtype),
                           _dequantized(vc, vs, q.dtype), row, heads)
        return out, kc, ks, vc, vs

    return _invoke(fn, (query, key, value, k_cache, k_scale, v_cache,
                        v_scale, positions), name="decode_attention_q8")


def decode_attention(query, key, value, k_cache, v_cache, positions, heads,
                     live=None):
    """Single-token cached attention for continuous-batching decode.

    ``query``/``key``/``value`` are (slots, 1, heads*dim) projections of the
    current token in every slot; caches are (slots, max_seq, heads*dim);
    ``positions`` (slots,) is the row each slot's new K/V lands in. Writes
    the new K/V — a scatter XLA applies in place to a donated cache — and
    attends rows <= positions; returns (out, k_cache, v_cache).  ``live``
    (slots,) bool, where given, names the slots whose output is read.

    The read adapts to what the call can see: on a TPU, at shapes
    ``ops/pallas/decode_attention.py::fits`` takes, the Pallas kernel
    ``mx_decode_attn`` reads each live slot's rows in blocks, where the
    cache lies, and skips the blocks past a position and the idle slots
    whole (their output is zeros); everywhere else the XLA composition
    reads all ``max_seq`` rows of every slot behind a mask (static shapes
    — the mask, not the extent, varies), which is also the kernel's
    reference.
    """
    by_kernel = decode_read_block(k_cache) is not None
    if by_kernel and _telemetry._active:
        _telemetry.inc("serve.decode_kernel_calls_total")

    def fn(q, k, v, kc, vc, pos, *read):
        lane, row = _step_rows(pos, kc.shape[1])
        kc = kc.at[lane, row].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[lane, row].set(v[:, 0].astype(vc.dtype))
        if by_kernel:
            from .pallas.decode_attention import decode_read
            rows = jnp.where(read[0], row + 1, 0) if read else row + 1
            return decode_read(q, kc, vc, rows, heads,
                               interpret=_runtime.pallas_interpret()), kc, vc
        out = _step_attend(q, _heads_apart(kc, heads).astype(q.dtype),
                           _heads_apart(vc, heads).astype(q.dtype), row,
                           heads)
        return out, kc, vc

    return _invoke(fn, (query, key, value, k_cache, v_cache, positions)
                   + (() if live is None else (live,)),
                   name="decode_attention")


def multi_head_attention(query, key, value, heads, mask=None, dropout_p=0.0,
                         causal=False, kv_heads=None, window=None,
                         selection=None, return_lse=False):
    """Fused MHA on (batch, seq, heads*dim) queries and (batch, seq,
    kv_heads*dim) keys and values.

    ``kv_heads`` (default ``heads``) divides ``heads``: query head ``i``
    reads KV head ``i // (heads // kv_heads)``.  ``window`` (causal only)
    keeps query ``i`` to the keys ``0 <= i - j < window``.  ``selection``
    (batch, seq_q, seq_k), integer or bool, is a mask that is data: query
    ``i`` of a batch row reads key ``j`` only where it is nonzero, all
    heads alike, on top of ``causal`` / ``window`` (a learned sparse
    attention's top-k).  Unlike ``mask`` it stays on the kernels: they
    take it as one more operand.  It has no gradient.

    ``return_lse``: return ``(out, lse)``.  ``lse`` (batch, heads, seq)
    float32 is each query's log-sum-exp of its scaled logits over the
    keys it read, a constant — what the forward flash kernel keeps for
    the backward, so it exists only where the kernels ran; on every other
    route ``lse`` is None.

    Routing: sp-sharded scope -> ring attention (sequence parallelism over
    ICI; ungrouped, unwindowed calls without a selection only); long unmasked sequences on
    TPU -> Pallas flash kernel; otherwise the XLA dot_general
    composition. Attention-prob dropout (training only, reference:
    transformer attention cells) forces the XLA path.
    """
    kv_heads = heads if kv_heads is None else kv_heads
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads do not group over "
                         f"{kv_heads} KV heads")
    if window is not None and not causal:
        raise ValueError("a window is a causal band: pass causal=True")
    from .. import autograd
    if not autograd.is_training():
        dropout_p = 0.0
    pure = mask is None and dropout_p == 0.0
    plain = kv_heads == heads and window is None and selection is None
    sp_mesh = _sp_mesh() if pure and plain else None
    sel = selection._data if hasattr(selection, "_data") else selection

    # one scope a layer: split, pad to the kernel's lanes, kernel, slice,
    # merge — the glue is this scope's time less its kernels'
    @jax.named_scope("mx.attn")
    def fn(q, k, v):
        b, sq, hd = q.shape
        sk = k.shape[1]
        d = hd // heads

        def split(t, n=heads):      # (b, s, n*d) -> (b, n, s, d)
            return t.reshape(b, -1, n, d).transpose(0, 2, 1, 3)

        def merge(t):
            return t.transpose(0, 2, 1, 3).reshape(b, sq, hd)

        if (sp_mesh is not None and sq == sk
                and sq % int(sp_mesh.shape["sp"]) == 0):
            from ..parallel.ring_attention import ring_attention
            return merge(ring_attention(split(q), split(k), split(v),
                                        sp_mesh, axis="sp", causal=causal))
        min_seq = _FLASH_MIN_SEQ_CAUSAL if causal else _FLASH_MIN_SEQ
        if _runtime.on_tpu() and pure and sk >= min_seq:
            # no fallback here: a kernel Mosaic refuses must surface as
            # the compiler's error, not as a slower step
            out = _flash(split(q), split(k, kv_heads), split(v, kv_heads),
                         causal, window, sel, return_lse)
            if return_lse:
                return merge(out[0]), out[1]
            return merge(out)
        m = mask._data if hasattr(mask, "_data") else mask
        if sel is not None:
            chosen = (sel != 0)[:, None]
            m = chosen if m is None else chosen & m.astype(bool)
        return _reference_attention(q, k, v, heads, m, causal, None,
                                    dropout_p, kv_heads, window)

    out = _invoke(fn, (query, key, value), name="multi_head_attention")
    if return_lse and not isinstance(out, tuple):
        return out, None        # a route that keeps no such statistic
    return out


def _step_attend(q, kh, vh, row, heads):
    """The decode step's read as an XLA composition: q (slots, 1,
    heads*dim) over kh / vh (slots, max_seq, heads, dim) in q's type, rows
    <= ``row`` visible."""
    n, _, hd = q.shape
    d = hd // heads
    scores = jnp.einsum("nhd,nshd->nhs", q.reshape(n, heads, d),
                        kh) * (1.0 / (d ** 0.5))
    visible = (jnp.arange(kh.shape[1])[None, :] <= row[:, None])[:, None, :]
    att = _softmax_over_rows(scores, visible, q.dtype)
    return jnp.einsum("nhs,nshd->nhd", att, vh).reshape(n, 1, hd)


def _multi_attend(q, kh, vh, pos, heads):
    """The verify step's read: q (slots, t, heads*dim), query j of a slot
    over the rows <= its position + j."""
    n, t, hd = q.shape
    d = hd // heads
    scores = jnp.einsum("nqhd,nshd->nhqs", q.reshape(n, t, heads, d),
                        kh) * (1.0 / (d ** 0.5))
    limit = pos.astype(jnp.int32)[:, None] + jnp.arange(t)
    visible = (jnp.arange(kh.shape[1])[None, None, :]
               <= limit[:, :, None])[:, None, :, :]
    att = _softmax_over_rows(scores, visible, q.dtype)
    return jnp.einsum("nhqs,nshd->nqhd", att, vh).reshape(n, t, hd)


def _suffix_attend(q, kslot, vslot, start, heads):
    """The suffix prefill's read: q (1, Ls, heads*dim) over one slot's
    kslot / vslot (max_seq, heads, dim), query i over the rows <= start
    + i."""
    _, ls, hd = q.shape
    d = hd // heads
    scores = jnp.einsum("qhd,shd->hqs", q.reshape(ls, heads, d),
                        kslot) * (1.0 / (d ** 0.5))
    visible = (jnp.arange(kslot.shape[0])[None, :]
               <= (start + jnp.arange(ls))[:, None])
    att = _softmax_over_rows(scores, visible[None, :, :], q.dtype)
    return jnp.einsum("hqs,shd->qhd", att, vslot).reshape(1, ls, hd)
