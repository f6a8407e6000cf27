"""Flash attention Pallas kernel (TPU).

Reference parity: src/operator/contrib/transformer.cc:675-828 — MXNet's
fastest attention path is interleaved cuBLAS batched matmuls that still
materialize the (seq, seq) score matrix in HBM. TPU-native design: one
Pallas kernel per (batch*head, q-block) grid cell streams K/V blocks through
VMEM with an online-softmax accumulator, so scores never hit HBM and the
matmuls stay on the MXU. Backward is a recompute VJP (flash-style: saves
only out + logsumexp residuals, rebuilds P per block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, seq_k,
                causal, scale, block_q):
    qi = pl.program_id(1)
    # keep MXU operands in the input dtype (bf16 on TPU): fp32 matmul
    # costs ~8x the MXU passes; accumulation is fp32 regardless via
    # preferred_element_type. Softmax math stays fp32.
    q = q_ref[0]                                      # (bq, d)
    bq, d = q.shape
    nk = pl.cdiv(seq_k, block_k)

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        # dynamic-slice loads clamp at the array end, so a partial final
        # block would re-read earlier keys — mask beyond seq_k explicitly
        s = jnp.where(k_pos < seq_k, s, _NEG_INF)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # only blocks with k_start <= q_end contribute
        nk_eff = jnp.minimum(nk, (qi + 1) * block_q // block_k
                             + (1 if block_q % block_k else 0) + 1)
        nk_eff = jnp.minimum(nk_eff, nk)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
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
    grid = (bh, pl.cdiv(sq_full, block_q))
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, seq_k=sk, causal=causal, scale=scale,
        block_q=block_q)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk_full, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk_full, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_full, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_full, 1), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_fwd",
    )(q, k, v)
    if sq_pad:
        out = out[:, :sq]
        lse = lse[:, :sq]
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret):
    out, _ = _fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret):
    out, lse = _fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _bwd_block(q, do, lse, delta, kb, vb, q0, k0, seq_q, seq_k, causal,
               scale):
    """Shared recompute for one (q-block, k-block) tile: returns (p, ds).

    p = exp(s - lse) rebuilt from saved logsumexp; ds = p*(dp - delta)*scale
    (standard flash-attention backward tile math). MXU operands stay in
    the input dtype with fp32 accumulation; only the softmax algebra is
    fp32.
    """
    bq, bk = q.shape[0], kb.shape[0]
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = (q_pos < seq_q) & (k_pos < seq_k)
    if causal:
        valid &= q_pos >= k_pos
    s = jnp.where(valid, s, _NEG_INF)
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p, ds


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                    dk_ref, dv_ref, *, block_q, block_k, seq_q, seq_k,
                    causal, scale):
    """dK/dV for one k-block, accumulated over sequential q-block steps
    (grid (bh, nk, nq): last axis revisits the same output block)."""
    j, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        p, ds = _bwd_block(q, do, lse, delta, kb, vb, qi * block_q,
                           j * block_k, seq_q, seq_k, causal, scale)
        dv_ref[0] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_ref[0] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # the tile is all-masked when every q_pos < the k block start
        pl.when((qi + 1) * block_q - 1 >= j * block_k)(_compute)
    else:
        _compute()


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref,
                   *, block_q, block_k, seq_q, seq_k, causal, scale):
    """dQ for one q-block, accumulated over sequential k-block steps."""
    qi, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        _, ds = _bwd_block(q, do, lse, delta, kb, vb, qi * block_q,
                           j * block_k, seq_q, seq_k, causal, scale)
        dq_ref[0] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((qi + 1) * block_q - 1 >= j * block_k)(_compute)
    else:
        _compute()


def _flash_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, res, do):
    """Blocked Pallas backward (flash-style residuals: out + logsumexp).

    Memory is O(seq): P is rebuilt per (q-block, k-block) tile in VMEM from
    the saved lse, never materialized in HBM — the training-side completion
    of the forward kernel's claim (round-1 VJP materialized (s, s) scores).
    Tiles independently of the forward (bwd_block_q/bwd_block_k): the
    backward holds ~2x the forward's accumulators per tile, so its tuned
    optimum is usually smaller.
    """
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(bwd_block_q, sq)
    bk = min(bwd_block_k, sk)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    sq_pad, sk_pad = -sq % bq, -sk % bk
    if sq_pad:
        pad = ((0, 0), (0, sq_pad), (0, 0))
        q, do = jnp.pad(q, pad), jnp.pad(do, pad)
        lse, delta = (jnp.pad(lse, ((0, 0), (0, sq_pad), (0, 0))),
                      jnp.pad(delta, ((0, 0), (0, sq_pad), (0, 0))))
    if sk_pad:
        pad = ((0, 0), (0, sk_pad), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    sq_full, sk_full = sq + sq_pad, sk + sk_pad
    nq, nk = sq_full // bq, sk_full // bk

    q_spec = pl.BlockSpec((1, bq, d), lambda i, a, b: (i, a, 0))
    r_spec = pl.BlockSpec((1, bq, 1), lambda i, a, b: (i, a, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda i, a, b: (i, b, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          seq_q=sq, seq_k=sk, causal=causal, scale=scale),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, b, a: (i, a, 0)),
            pl.BlockSpec((1, bq, d), lambda i, b, a: (i, a, 0)),
            pl.BlockSpec((1, bq, 1), lambda i, b, a: (i, a, 0)),
            pl.BlockSpec((1, bq, 1), lambda i, b, a: (i, a, 0)),
            pl.BlockSpec((1, bk, d), lambda i, b, a: (i, b, 0)),
            pl.BlockSpec((1, bk, d), lambda i, b, a: (i, b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, b, a: (i, b, 0)),
            pl.BlockSpec((1, bk, d), lambda i, b, a: (i, b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_full, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk_full, d), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_bwd_dkv",
    )(q, do, lse, delta, k, v)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk,
                          seq_q=sq, seq_k=sk, causal=causal, scale=scale),
        grid=(bh, nq, nk),
        in_specs=[q_spec, q_spec, r_spec, r_spec, k_spec, k_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq_full, d), jnp.float32),
        interpret=interpret,
        name="mx_flash_bwd_dq",
    )(q, do, lse, delta, k, v)

    if sq_pad:
        dq = dq[:, :sq]
    if sk_pad:
        dk, dv = dk[:, :sk], dv[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, bwd_block_q=None, bwd_block_k=None,
                    interpret=False):
    """Multi-head attention, scores never materialized in HBM.

    q: (batch, heads, seq_q, head_dim); k/v: (batch, heads, seq_k, head_dim).
    Returns (batch, heads, seq_q, head_dim).

    Block shapes default to ``mx.autotune.resolve_blocks`` — the tuned
    winner for this (seq_q, seq_k, head_dim) bucket when one is loaded,
    else the per-device static table (CPU row keeps the historical
    1024/512).  The backward tiles independently via bwd_block_q /
    bwd_block_k.  Explicit values always win.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
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
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    # TPU lanes are 128 wide: a 64-dim head halves every load/store and
    # forces relayouts. Zero-pad head_dim to the lane width — zeros add
    # nothing to q·k^T and the padded tail of out is exactly zero.
    d_pad = -d % 128 if d < 128 else 0
    if d_pad:
        pad = ((0, 0), (0, 0), (0, d_pad))
        qr, kr, vr = (jnp.pad(qr, pad), jnp.pad(kr, pad), jnp.pad(vr, pad))
    out = _flash(qr, kr, vr, causal, scale, block_q, block_k, bwd_block_q,
                 bwd_block_k, interpret)
    if d_pad:
        out = out[..., :d]
    return out.reshape(b, h, sq, d)
