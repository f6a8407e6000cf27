"""Mosaic-compile + numerics check for every Pallas kernel, on a TPU.

Interpret-mode tests (the CPU suite) say a kernel's arithmetic is right;
only libtpu's Mosaic compiler says whether it fits VMEM, whether its
layouts and its int8/fp8 dots are accepted on this ``device_kind``.  This
script compiles each of the kernels with ``interpret=False`` at the
shapes the model zoo uses and the static blocks the device gets
(``autotune.kernels._STATIC_DEFAULTS``), and checks numerics against a
plain-jnp reference:

    flash_attention        fwd + bwd, bf16 and fp32, seq 512..8192, with
                           grouped KV heads and a causal window
    dsa_align              the sparse indexer's loss and its gradient,
                           against the XLA composition
    dsa_scores             the sparse indexer's scores and their three
                           gradients, against the XLA composition
    dsa_select             the sparse indexer's top-k mask, against the
                           XLA bisection, with and without ties
    ssd_scan               the state-space scan and its six gradients,
                           against the XLA composition
    ssm_conv               the mixers' causal convolution and its three
                           gradients, against the XLA composition
    ln_residual            fwd + bwd, bf16 and fp32
    quantized_matmul       int8 x int8 -> int32
    fp8_matmul             e4m3 and e5m2
    conv3x3_bn_relu_bwd    fused backward

Every case is tried (one refusal must not hide the next), each prints one
``PASS``/``FAIL`` line, and the table is written to
``chiprun_out/kernel_check.json``.  Exit code 0 only if every case
compiled AND matched; 2 when there is no TPU.

Usage:  python tools/tpu_kernel_check.py [kernel-name ...]
"""
from __future__ import annotations

import json
import os
import sys
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _maxerr(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _relerr(grads, refs):
    """Max per-tensor relative error: maxerr / (max|ref| per tensor)."""
    rel = []
    for a, b in zip(grads, refs):
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-6
        rel.append(_maxerr(a, b) / scale)
    return max(rel)


def _flash_case(B, H, S, D, dtype, causal, bwd=True, kv_heads=None,
                window=None):
    KV = H if kv_heads is None else kv_heads

    def check():
        from mxnet_tpu.ops.pallas import flash_attention as fa
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, S, D), dtype)
        k, v = (jax.random.normal(kk, (B, KV, S, D), dtype) for kk in ks[1:])

        def flash_attention(q, k, v, causal):
            return fa.flash_attention(q, k, v, causal=causal, window=window)

        def ref(q, k, v):
            k, v = (jnp.repeat(t.astype(jnp.float32), H // KV, axis=1)
                    for t in (k, v))
            s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k,
                           precision=_HI) / np.sqrt(D)
            if causal:
                band = jnp.tril(jnp.ones((S, S), bool))
                if window is not None:
                    band &= ~jnp.tril(jnp.ones((S, S), bool), -window)
                s = jnp.where(band, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=_HI)

        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal))(q, k, v)
        res = {"fwd_maxerr": _maxerr(out, jax.jit(ref)(q, k, v))}
        assert res["fwd_maxerr"] < 0.05, res
        if bwd:
            def loss(q, k, v):
                return jnp.sum(flash_attention(q, k, v, causal=causal)
                               .astype(jnp.float32) ** 2)

            def loss_ref(q, k, v):
                return jnp.sum(ref(q, k, v) ** 2)
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
            gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
            res["bwd_relerr"] = _relerr(g, gr)
            assert res["bwd_relerr"] < 0.05, res
        return res
    name = (f"flash_attention b{B}h{H}s{S}d{D} {jnp.dtype(dtype).name} "
            f"{'causal' if causal else 'full'}"
            f"{'' if KV == H else f' kv{KV}'}"
            f"{'' if window is None else f' window{window}'}"
            f"{'' if bwd else ' fwd-only'}")
    return name, check


def _ln_case(rows, dim, dtype):
    def check():
        from mxnet_tpu.ops.pallas.ln_residual import ln_residual_dropout
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        x = jax.random.normal(ks[0], (rows, dim), dtype)
        h = jax.random.normal(ks[1], (rows, dim), dtype)
        gamma = jax.random.normal(ks[2], (dim,), jnp.float32)
        beta = jax.random.normal(ks[3], (dim,), jnp.float32)
        mask = jax.random.uniform(ks[4], (rows, dim)) > 0.1
        p = 0.1

        def ref(x, h, gamma, beta):
            s = x.astype(jnp.float32) + jnp.where(
                mask, h.astype(jnp.float32) / (1 - p), 0.0)
            mu = jnp.mean(s, -1, keepdims=True)
            var = jnp.mean((s - mu) ** 2, -1, keepdims=True)
            return ((s - mu) * jax.lax.rsqrt(var + 1e-5)) * gamma + beta

        def fused(x, h, gamma, beta):
            return ln_residual_dropout(x, h, gamma, beta, p=p, mask=mask)

        res = {"fwd_maxerr": _maxerr(jax.jit(fused)(x, h, gamma, beta),
                                     ref(x, h, gamma, beta))}
        assert res["fwd_maxerr"] < 0.05, res
        g = jax.jit(jax.grad(lambda *a: jnp.sum(
            fused(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3)))(
                x, h, gamma, beta)
        gr = jax.jit(jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                              argnums=(0, 1, 2, 3)))(x, h, gamma, beta)
        res["bwd_relerr"] = _relerr(g, gr)
        assert res["bwd_relerr"] < 0.05, res
        return res
    return f"ln_residual {rows}x{dim} {jnp.dtype(dtype).name}", check


def _int8_case(M, N, K, act):
    def check():
        from mxnet_tpu.ops.pallas.quant_matmul import quantized_matmul
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        x = jax.random.normal(ks[0], (M, K), jnp.float32)
        w = jax.random.normal(ks[1], (N, K), jnp.float32) * 0.05
        b = jax.random.normal(ks[2], (N,), jnp.float32)
        ws = jnp.max(jnp.abs(w), axis=1) / 127.0
        wq = jnp.clip(jnp.round(w / ws[:, None]), -127, 127).astype(jnp.int8)
        xs = jnp.max(jnp.abs(x)) / 127.0

        def ref(x):
            xq = jnp.clip(jnp.round(x / xs), -127, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(xq, wq, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * (xs * ws) + b
            return {None: lambda z: z, "relu": jax.nn.relu,
                    "gelu": jax.nn.gelu}[act](out)

        out = jax.jit(lambda x: quantized_matmul(
            x, wq, ws, xs, bias=b, act=act))(x)
        res = {"maxerr": _maxerr(out, jax.jit(ref)(x))}
        # the int8 dot and the dequant are the XLA expression bit for
        # bit (measured 0.0 on the v5e); a transcendental epilogue is
        # Mosaic's own approximation (gelu: 7.2e-3 against XLA's)
        assert res["maxerr"] < (2e-2 if act == "gelu" else 1e-6), res
        return res
    return f"quantized_matmul {M}x{N}x{K} int8 act={act}", check


def _fp8_case(M, N, K, fmt):
    def check():
        from mxnet_tpu.ops.pallas.quant_matmul import FP8_FORMATS, fp8_matmul
        dtype, fmax = FP8_FORMATS[fmt]
        ks = jax.random.split(jax.random.PRNGKey(4), 2)
        x = jax.random.normal(ks[0], (M, K), jnp.float32)
        w = jax.random.normal(ks[1], (N, K), jnp.float32) * 0.05
        ws = jnp.max(jnp.abs(w), axis=1) / fmax
        wq = (w / ws[:, None]).astype(dtype)
        xs = jnp.max(jnp.abs(x)) / fmax

        def ref(x):
            xq = (x / xs).astype(dtype)
            acc = jax.lax.dot_general(
                xq.astype(jnp.float32), wq.astype(jnp.float32),
                (((1,), (1,)), ((), ())), precision=_HI)
            return acc * (xs * ws)

        out = jax.jit(lambda x: fp8_matmul(x, wq, ws, xs, fmt=fmt))(x)
        r = jax.jit(ref)(x)
        res = {"relerr": _relerr([out], [r])}
        assert res["relerr"] < 0.02, res
        return res
    return f"fp8_matmul {M}x{N}x{K} {fmt}", check


def _dsa_align_case(B, H, KV, S, D, topk, dtype):
    """``mx_dsa_align`` on the statistics the forward flash kernel hands
    out, against the XLA composition it replaces (its oracle)."""
    def check():
        from mxnet_tpu.ops import sparse_index
        from mxnet_tpu.ops.pallas import flash_attention as fa
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (B, S, H * D), dtype)
        k, v = (jax.random.normal(kk, (B, S, KV * D), dtype)
                for kk in ks[1:3])
        scores = 2.0 * jax.random.normal(ks[3], (B, S, S), jnp.float32)
        sel = jax.jit(lambda i: sparse_index.select_topk(i, topk))(scores)

        def split(t, n):
            return t.reshape(B, S, n, D).transpose(0, 2, 1, 3)

        lse = jax.jit(lambda q, k, v: fa.flash_attention(
            split(q, H), split(k, KV), split(v, KV), causal=True,
            selection=sel, return_lse=True)[1])(q, k, v)
        got, g = jax.jit(jax.value_and_grad(
            lambda i: sparse_index.align_loss(i, sel, q, k, H, KV, lse)))(
                scores)
        want, gr = jax.jit(jax.value_and_grad(
            lambda i: sparse_index.align_loss(i, sel, q, k, H, KV)))(scores)
        res = {"loss_relerr": abs(float(got) / float(want) - 1.0),
               "grad_relerr": _relerr([g], [gr]),
               "off_selection": float(jnp.max(jnp.abs(
                   jnp.where(sel == 0, g, 0.0))))}
        assert res["loss_relerr"] < 1e-4 and res["grad_relerr"] < 1e-3 \
            and res["off_selection"] == 0.0, res
        return res
    return (f"dsa_align b{B}h{H}kv{KV}s{S}d{D} top{topk} "
            f"{jnp.dtype(dtype).name}"), check


def _dsa_scores_case(B, H, S, D, dtype):
    """``index_scores`` as the chip takes it (``mx_dsa_scores`` and
    ``mx_dsa_scores_bwd``) against the XLA composition it replaces (its
    oracle): every causal pair's score, and ``dqI``, ``dkI``, ``dw`` for
    a cotangent on under half of the causal pairs."""
    def check():
        from mxnet_tpu.ops import sparse_index
        from mxnet_tpu.ops.pallas import dsa_scores
        assert dsa_scores.fits(S, H, D, jnp.dtype(dtype).itemsize)
        ks = jax.random.split(jax.random.PRNGKey(4), 5)
        q = jax.random.normal(ks[0], (B, S, H, D), dtype)
        k = jax.random.normal(ks[1], (B, S, D), dtype)
        w = jax.random.normal(ks[2], (B, S, H), jnp.float32) / H ** 0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        g = jnp.where(causal & (jax.random.uniform(ks[3], (B, S, S)) < 0.44),
                      jax.random.normal(ks[4], (B, S, S), jnp.float32), 0.0)

        def kernels(q, k, w):
            return jnp.where(causal, sparse_index.index_scores(q, k, w), 0.0)

        def composed(q, k, w):
            return jnp.where(causal, sparse_index._composed_scores(
                q, k, w * (1.0 / D ** 0.5)), 0.0)

        def both(f):
            out, vjp = jax.vjp(f, q, k, w)
            return out, vjp(g)

        got, grads = jax.jit(lambda: both(kernels))()
        # float32 operands: XLA's default on the chip is one bf16 pass,
        # Mosaic's several; the oracle is the composition at ``highest``
        with jax.default_matmul_precision("highest"):
            want, refs = jax.jit(lambda: both(composed))()
        res = {"scores_relerr": _relerr([got], [want]),
               "dq_relerr": _relerr(grads[:1], refs[:1]),
               "dk_relerr": _relerr(grads[1:2], refs[1:2]),
               "dw_relerr": _relerr(grads[2:], refs[2:])}
        # bf16: the composition hands dq and dk back in bf16 and sums
        # dk's query blocks in it; the kernels accumulate in float32
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
        assert res["scores_relerr"] < 1e-4 and res["dw_relerr"] < 1e-3 \
            and res["dq_relerr"] < tol and res["dk_relerr"] < tol, res
        return res
    return f"dsa_scores b{B}h{H}s{S}d{D} {jnp.dtype(dtype).name}", check


def _dsa_select_case(B, S, topk, ties):
    """``select_topk`` as the chip takes it (``mx_dsa_select``) against
    the XLA bisection it replaces (its oracle): equal masks, exactly
    ``min(t + 1, topk)`` a row.  ``ties``: scores rounded to a few
    values, so that every row ties at its threshold."""
    def check():
        from mxnet_tpu.ops import sparse_index
        from mxnet_tpu.ops.pallas import dsa_select
        assert dsa_select.fits(S)
        scores = 2.0 * jax.random.normal(jax.random.PRNGKey(5), (B, S, S),
                                         jnp.float32)
        if ties:
            scores = jnp.round(scores * 4.0) * 0.25
        # as ``mx_dsa_scores`` leaves them: zeros above the diagonal
        scores = jnp.tril(scores)
        got = jax.jit(lambda i: sparse_index.select_topk(i, topk))(scores)
        want = jax.jit(
            lambda i: sparse_index._composed_select(i, topk))(scores)
        rows = jnp.sum(got.astype(jnp.int32), axis=-1)
        res = {"differ": int(jnp.sum(got != want)),
               "rows_off": int(jnp.sum(
                   rows != jnp.minimum(jnp.arange(S) + 1, topk))),
               "above_diagonal": int(jnp.sum(jnp.triu(got, 1) != 0))}
        assert res["differ"] == 0 and res["rows_off"] == 0 \
            and res["above_diagonal"] == 0, res
        return res
    return f"dsa_select b{B}s{S} top{topk}{' ties' if ties else ''}", check


def _ssd_scan_case(B, S, H, P, G, N, dtype):
    """``ssd_scan`` as the chip takes it (``mx_ssd_fwd`` and
    ``mx_ssd_bwd``) against the XLA composition it replaces (its oracle):
    ``y`` and the gradients of ``x``, ``dt``, ``A``, ``B``, ``C``, ``D``."""
    def check():
        from mxnet_tpu.ops import ssm
        from mxnet_tpu.ops.pallas import ssd_scan
        assert ssd_scan.fits(S, H, P, G, N, 128, jnp.dtype(dtype).itemsize)
        ks = jax.random.split(jax.random.PRNGKey(6), 7)
        args = (jax.random.normal(ks[0], (B, S, H, P), dtype),
                jax.random.uniform(ks[1], (B, S, H), jnp.float32, 0.001, 0.1),
                -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0),
                (0.3 * jax.random.normal(ks[3], (B, S, G, N))).astype(dtype),
                (0.3 * jax.random.normal(ks[4], (B, S, G, N))).astype(dtype),
                jax.random.normal(ks[5], (H,), jnp.float32))
        g = jax.random.normal(ks[6], (B, S, H, P), dtype)

        def both(f):
            out, vjp = jax.vjp(lambda *a: f(*a, 128), *args)
            return out, vjp(g)

        got, grads = jax.jit(lambda: both(
            lambda *a: ssm.ssd_scan(*a[:-1], chunk=a[-1])))()
        # float32 operands: the kernels' products, like XLA's on the
        # chip, run at the default precision (bf16 passes), so the oracle
        # is the composition as the chip runs it and the tolerance bf16's
        want, refs = jax.jit(lambda: both(ssm._ssd_chunked))()
        res = {"y_relerr": _relerr([got], [want])}
        for name, a, r in zip(("dx", "ddt", "dA", "dB", "dC", "dD"),
                              grads, refs):
            res[f"{name}_relerr"] = _relerr([a], [r])
        assert all(v < 3e-2 for v in res.values()), res
        return res
    return (f"ssd_scan b{B}s{S}h{H}p{P}g{G}n{N} {jnp.dtype(dtype).name}",
            check)


def _ssm_conv_case(B, S, C, K, dtype):
    """``causal_conv1d`` as the chip takes it (``mx_ssm_conv_fwd`` and
    ``mx_ssm_conv_bwd``) against the XLA composition it replaces (its
    oracle): ``y`` through SiLU and the gradients of ``x``, ``weight``,
    ``bias``."""
    def check():
        from mxnet_tpu.ops import ssm
        from mxnet_tpu.ops.pallas import ssm_conv
        assert ssm_conv.fits(S, C, K, jnp.dtype(dtype).itemsize)
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        args = (jax.random.normal(ks[0], (B, S, C), dtype),
                jax.random.uniform(ks[1], (C, K), jnp.float32, -0.5, 0.5),
                0.1 * jax.random.normal(ks[2], (C,), jnp.float32))
        g = jax.random.normal(ks[3], (B, S, C), dtype)

        def both(f):
            out, vjp = jax.vjp(f, *args)
            return out, vjp(g)

        got, grads = jax.jit(lambda: both(
            lambda *a: ssm.causal_conv1d(*a, "silu")))()
        want, refs = jax.jit(lambda: both(
            lambda *a: ssm._conv(*a, True)))()
        res = {"y_relerr": _relerr([got], [want])}
        for name, a, r in zip(("dx", "dw", "dbias"), grads, refs):
            res[f"{name}_relerr"] = _relerr([a], [r])
        # both sides sum in float32 and round once: half a unit of bf16's
        # last place, 1e-5 in float32
        tol = 8e-3 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-5
        assert all(v < tol for v in res.values()), res
        return res
    return f"ssm_conv b{B}s{S}c{C}k{K} {jnp.dtype(dtype).name}", check


def _decode_attn_case(slots, max_seq, heads, dim, dtype):
    """``decode_attention`` as the chip takes it (``mx_decode_attn``: the
    step's row written in place, each live slot's rows read in blocks)
    against the XLA composition it replaces (its oracle), at ragged
    positions with idle slots: the output of the live slots, zeros for
    the idle ones, and the caches."""
    def check():
        from mxnet_tpu.ops import attention
        width = heads * dim
        ks = jax.random.split(jax.random.PRNGKey(11), 7)
        q, k, v = (jax.random.normal(ks[i], (slots, 1, width), dtype)
                   for i in range(3))
        kc, vc = (jax.random.normal(ks[i], (slots, max_seq, width), dtype)
                  for i in (3, 4))
        pos = jax.random.randint(ks[5], (slots,), 0, max_seq)
        live = jax.random.uniform(ks[6], (slots,)) < 0.7
        assert attention.decode_read_block(kc) is not None

        def raw(out):
            return [getattr(a, "_data", a) for a in out]

        got = raw(attention.decode_attention(q, k, v, kc, vc, pos, heads,
                                             live))
        lane, row = attention._step_rows(pos, max_seq)
        kw = kc.at[lane, row].set(k[:, 0])
        vw = vc.at[lane, row].set(v[:, 0])
        want = attention._step_attend(
            q, attention._heads_apart(kw, heads),
            attention._heads_apart(vw, heads), row, heads)
        keep = live[:, None, None]
        res = {"out_maxerr": _maxerr(jnp.where(keep, got[0], 0),
                                     jnp.where(keep, want, 0)),
               "idle_max": float(jnp.max(jnp.abs(
                   jnp.where(keep, 0, got[0]).astype(jnp.float32)))),
               "cache_maxerr": max(_maxerr(got[1], kw), _maxerr(got[2], vw))}
        # float32 scores against the composition's rounded ones
        tol = 3e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 2e-2
        assert res["out_maxerr"] < tol and res["idle_max"] == 0 \
            and res["cache_maxerr"] == 0, res
        return res
    return (f"decode_attn n{slots}s{max_seq}h{heads}d{dim} "
            f"{jnp.dtype(dtype).name}", check)


def _conv_case(N, H, W, Cin, Cout):
    def check():
        from mxnet_tpu.ops.pallas_conv_bwd import (conv3x3_bn_relu_ref,
                                                   fused_cbr_train)
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        x = jax.random.normal(ks[0], (N, H, W, Cin), jnp.bfloat16)
        w = jax.random.normal(ks[1], (3, 3, Cin, Cout), jnp.bfloat16) * 0.1
        gamma = jnp.abs(jax.random.normal(ks[2], (Cout,), jnp.float32)) + 0.5
        beta = jax.random.normal(ks[3], (Cout,), jnp.float32)

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32) ** 2)
        g = jax.jit(jax.grad(loss(fused_cbr_train), argnums=(0, 1, 2, 3)))(
            x, w, gamma, beta)
        gr = jax.jit(jax.grad(loss(conv3x3_bn_relu_ref),
                              argnums=(0, 1, 2, 3)))(x, w, gamma, beta)
        res = {"bwd_relerr": _relerr(g, gr)}
        assert res["bwd_relerr"] < 0.06, res
        return res
    return f"conv3x3_bn_relu_bwd n{N} {H}x{W} c{Cin}->{Cout} bf16", check


def cases():
    bf16, f32 = jnp.bfloat16, jnp.float32
    out = []
    for dtype in (bf16, f32):
        # GPT-2 124M training (12 heads x 64), the chip_smoke train shape
        out.append(_flash_case(2, 12, 1024, 64, dtype, causal=True))
        out.append(_flash_case(2, 12, 2048, 64, dtype, causal=True))
        # the non-causal threshold (ops/attention._FLASH_MIN_SEQ)
        out.append(_flash_case(2, 12, 2048, 64, dtype, causal=False))
        # serve prefill at the largest bucket: one prompt, forward only
        out.append(_flash_case(1, 12, 512, 64, dtype, causal=True,
                               bwd=False))
        # long context: the forward holds whole K and V per grid cell
        out.append(_flash_case(1, 2, 8192, 64, dtype, causal=True))
        # grouped KV heads (8 query heads a KV head, 128-wide: the afmoe
        # zoo family's shape) with and without its 2048 window
        out.append(_flash_case(2, 8, 2048, 128, dtype, causal=True,
                               kv_heads=2))
        out.append(_flash_case(1, 16, 2048, 128, dtype, causal=True,
                               kv_heads=4, window=512))
        out.append(_flash_case(1, 8, 4096, 128, dtype, causal=True,
                               kv_heads=1, window=2048))
        out.append(_flash_case(1, 8, 8192, 128, dtype, causal=True,
                               kv_heads=1, window=2048, bwd=False))
    # the sparse indexer's loss: the keye zoo family's heads at the
    # cell's length, and a batch of shorter float32 rows
    out.append(_dsa_align_case(1, 32, 4, 8192, 128, 2048, bf16))
    out.append(_dsa_align_case(2, 8, 2, 1024, 128, 256, f32))
    # the indexer's scores: the cell's 16 heads of 64 against one key
    # head, and a batch of shorter float32 rows
    out.append(_dsa_scores_case(1, 16, 8192, 64, bf16))
    out.append(_dsa_scores_case(2, 4, 1024, 64, f32))
    # the indexer's top-k: the cell's sequence and ``topk`` (a row or
    # two of random float32 scores tie at their threshold), a batch of
    # shorter rows, and scores of which every row ties
    out.append(_dsa_select_case(1, 8192, 2048, ties=False))
    out.append(_dsa_select_case(2, 1024, 256, ties=False))
    out.append(_dsa_select_case(1, 8192, 2048, ties=True))
    out.append(_dsa_select_case(1, 2048, 512, ties=True))
    # the state-space scan: a Nemotron-H mixer at the cell's length, and
    # a batch of float32 rows whose last chunk is padded
    out.append(_ssd_scan_case(1, 8192, 64, 64, 8, 128, bf16))
    out.append(_ssd_scan_case(2, 1000, 8, 64, 1, 128, f32))
    # the mixers' convolution: Nemotron-H's at the cell's length, and a
    # batch of float32 rows in three token blocks of 128
    out.append(_ssm_conv_case(1, 8192, 6144, 4, bf16))
    out.append(_ssm_conv_case(2, 384, 264, 4, f32))
    # the decode step's cached read: GPT-2 medium's heads at the serve
    # cells' cache length, and float32 rows of GPT-2 small's twelve
    out.append(_decode_attn_case(16, 1024, 16, 64, bf16))
    out.append(_decode_attn_case(4, 256, 12, 64, f32))
    for dtype in (bf16, f32):
        out.append(_ln_case(32 * 128, 768, dtype))       # BERT-base bs32
    out.append(_int8_case(1024, 3072, 768, "gelu"))      # GPT-2 FFN up
    out.append(_int8_case(1024, 768, 3072, None))        # GPT-2 FFN down
    out.append(_int8_case(32, 1000, 2048, None))         # ResNet-50 fc
    out.append(_int8_case(32 * 56 * 56, 64, 576, "relu"))  # 3x3 conv im2col
    out.append(_fp8_case(1024, 3072, 768, "e4m3"))
    out.append(_fp8_case(1024, 3072, 768, "e5m2"))
    out.append(_conv_case(8, 56, 56, 64, 64))            # ResNet stage 1
    out.append(_conv_case(8, 14, 14, 256, 256))          # ResNet stage 3
    return out


def main(argv=None):
    only = (argv if argv is not None else sys.argv[1:])
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    if dev.platform != "tpu":
        print("NOT A TPU — this check is meaningless on CPU", flush=True)
        sys.exit(2)
    table = {}
    for name, fn in cases():
        if only and not any(name.startswith(o) for o in only):
            continue
        try:
            res = fn()
            table[name] = {"compiles": True, "pass": True, **res}
            print(f"PASS {name}: {res}", flush=True)
        except Exception as e:  # noqa: BLE001 - report every case, exit 1
            # an AssertionError compiled and ran but missed its tolerance;
            # anything else is the compiler's (or the runtime's) refusal
            table[name] = {"compiles": isinstance(e, AssertionError),
                           "pass": False,
                           "error": f"{type(e).__name__}: {str(e)[:2000]}"}
            print(f"FAIL {name}:", flush=True)
            traceback.print_exc()
    out_dir = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_check.json"), "w") as f:
        json.dump({"device_kind": dev.device_kind, "cases": table}, f,
                  indent=1)
    ok = bool(table) and all(r["pass"] for r in table.values())
    print(f"{sum(r['pass'] for r in table.values())}/{len(table)} passed",
          flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
