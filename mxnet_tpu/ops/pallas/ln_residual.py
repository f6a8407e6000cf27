"""Fused dropout + residual-add + LayerNorm Pallas kernel (TPU).

Reference parity: the reference fuses exactly this elementwise tail for
its transformer path with dedicated CUDA ops
(src/operator/contrib/transformer.cc:675-828 fused attention/bias
epilogues; src/operator/nn/layer_norm.cu). TPU-native design: the
post-norm residual update ``LN(x + dropout(h))`` normally costs three HBM
round-trips of the residual stream (mask-apply+add materializes s, then
LayerNorm's reduce and normalize passes re-read it). This kernel streams a
row block through VMEM ONCE: apply mask, add, reduce mean/var on-chip,
normalize, write — and the backward recomputes s from (x, h, mask) so only
two (rows,1) vectors of residuals persist.

The dropout mask is a kernel INPUT (generated by XLA bernoulli outside):
``pltpu.prng_*`` has no interpret-mode lowering, and a mask input keeps the
kernel bit-exact testable on CPU while XLA fuses mask generation into the
surrounding graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fwd_kernel(x_ref, h_ref, m_ref, g_ref, b_ref, o_ref, mean_ref,
                rstd_ref, *, scale, eps):
    x = x_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    s = x + h * m * scale
    mean = s.mean(-1, keepdims=True)
    d = s - mean
    var = (d * d).mean(-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = d * rstd
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (xhat * g + b).astype(o_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, h_ref, m_ref, g_ref, mean_ref, rstd_ref, do_ref,
                dx_ref, dh_ref, dg_ref, db_ref, *, scale, eps):
    x = x_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    s = x + h * m * scale                       # recompute: saves HBM
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (s - mean) * rstd
    do = do_ref[...].astype(jnp.float32)
    # dgamma/dbeta: accumulate into one (8, dim) block revisited by every
    # grid step (TPU grid is sequential; Mosaic requires sublane blocks
    # >= 8, so partials are folded 8-wide instead of to a single row).
    dim = xhat.shape[-1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += (do * xhat).reshape(-1, 8, dim).sum(0)
    db_ref[...] += do.reshape(-1, 8, dim).sum(0)
    dxhat = do * g_ref[...].astype(jnp.float32)
    ds = rstd * (dxhat - dxhat.mean(-1, keepdims=True)
                 - xhat * (dxhat * xhat).mean(-1, keepdims=True))
    dx_ref[...] = ds.astype(dx_ref.dtype)
    dh_ref[...] = (ds * m * scale).astype(dh_ref.dtype)


def _pad_rows(a, rows_p):
    pad = rows_p - a.shape[0]
    return a if pad == 0 else jnp.pad(a, ((0, pad), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _ln_residual(x, h, mask, gamma, beta, p, eps, block_rows, interpret):
    return _run_fwd(x, h, mask, gamma, beta, p, eps, block_rows,
                    interpret)[0]


def _run_fwd(x, h, mask, gamma, beta, p, eps, block_rows, interpret):
    n, dim = x.shape
    grid = pl.cdiv(n, block_rows)
    rows_p = grid * block_rows
    scale = 1.0 / (1.0 - p) if p > 0 else 1.0
    xp, hp, mp = (_pad_rows(a, rows_p) for a in (x, h, mask))
    row_spec = pl.BlockSpec((block_rows, dim), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, dim), lambda i: (0, 0))
    out, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, eps=eps),
        grid=(grid,),
        in_specs=[row_spec, row_spec, row_spec, vec_spec, vec_spec],
        out_specs=[row_spec,
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, dim), x.dtype),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.float32)],
        interpret=interpret,
        name="mx_ln_residual_fwd",
    )(xp, hp, mp, gamma[None], beta[None])
    return out[:n], mean, rstd


def _ln_residual_fwd(x, h, mask, gamma, beta, p, eps, block_rows,
                     interpret):
    out, mean, rstd = _run_fwd(x, h, mask, gamma, beta, p, eps,
                               block_rows, interpret)
    return out, (x, h, mask, gamma, beta, mean, rstd)


def _ln_residual_bwd(p, eps, block_rows, interpret, res, do):
    x, h, mask, gamma, beta, mean, rstd = res
    n, dim = x.shape
    grid = pl.cdiv(n, block_rows)
    rows_p = grid * block_rows
    scale = 1.0 / (1.0 - p) if p > 0 else 1.0
    xp, hp, mp, dop = (_pad_rows(a, rows_p) for a in (x, h, mask, do))
    row_spec = pl.BlockSpec((block_rows, dim), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, dim), lambda i: (0, 0))
    acc_spec = pl.BlockSpec((8, dim), lambda i: (0, 0))
    dx, dh, dgp, dbp = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, eps=eps),
        grid=(grid,),
        in_specs=[row_spec, row_spec, row_spec, vec_spec, stat_spec,
                  stat_spec, row_spec],
        out_specs=[row_spec, row_spec, acc_spec, acc_spec],
        out_shape=[jax.ShapeDtypeStruct((rows_p, dim), x.dtype),
                   jax.ShapeDtypeStruct((rows_p, dim), h.dtype),
                   jax.ShapeDtypeStruct((8, dim), jnp.float32),
                   jax.ShapeDtypeStruct((8, dim), jnp.float32)],
        interpret=interpret,
        name="mx_ln_residual_bwd",
    )(xp, hp, mp, gamma[None], mean, rstd, dop)
    dgamma = dgp.sum(0).astype(gamma.dtype)
    dbeta = dbp.sum(0).astype(beta.dtype)
    return dx[:n], dh[:n], jnp.zeros_like(mask), dgamma, dbeta


_ln_residual.defvjp(_ln_residual_fwd, _ln_residual_bwd)


def ln_residual_dropout(x, h, gamma, beta, *, p=0.0, mask=None, eps=1e-5,
                        block_rows=None, interpret=False):
    """``LayerNorm(x + dropout(h))`` in one VMEM pass over the last axis.

    x, h: (..., D) with D the normalized axis (must be a multiple of 128
    on real TPUs); gamma/beta: (D,). p: dropout rate applied to h; mask:
    precomputed keep-mask (same shape as h, any 0/1 dtype) — required when
    p > 0. Fully differentiable wrt x, h, gamma, beta.
    """
    shape = x.shape
    dim = shape[-1]
    x2 = x.reshape(-1, dim)
    h2 = h.reshape(-1, dim)
    if p > 0:
        if mask is None:
            raise ValueError("p > 0 requires a dropout keep-mask")
        m2 = mask.reshape(-1, dim)
    else:
        m2 = jnp.ones_like(x2, dtype=jnp.float32)
    if block_rows is None:
        from ...autotune.kernels import resolve_blocks
        block_rows = resolve_blocks(
            "ln_residual", (x2.shape[0], dim))["block_rows"]
    br = min(block_rows, max(8, x2.shape[0]))
    br = (br + 7) // 8 * 8    # bwd folds partials 8-wide (Mosaic sublane)
    out = _ln_residual(x2, h2, m2, gamma, beta, float(p), float(eps),
                       int(br), bool(interpret))
    return out.reshape(shape)
