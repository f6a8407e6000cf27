"""Grouped KV heads and a causal window in the three flash kernels
(ops/pallas/flash_attention.py) and in ``multi_head_attention``: parity
with the XLA composition, forward and all three gradients, in interpret
mode; the tile schedule with a window against a brute-force count over the
mask; skipped tiles left of the band not fetched; and the plain call (one
KV head a query head, no window) tracing as it did before either existed.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from family_harness import pallas_calls as _pallas_calls
from mxnet_tpu import telemetry
from mxnet_tpu.ops.attention import _reference_attention
from mxnet_tpu.ops.pallas import flash_attention as F


def _operands(batch, heads, kv_heads, seq, dim, dtype, seed=0):
    rs = onp.random.RandomState(seed)

    def draw(h):
        return jnp.asarray(rs.randn(batch, h, seq, dim), dtype)
    return draw(heads), draw(kv_heads), draw(kv_heads), \
        jnp.asarray(rs.randn(batch, heads, seq, dim), jnp.float32)


def _reference(q, k, v, window):
    """The XLA composition on (batch, heads, seq, dim) operands, fp32."""
    b, h, s, d = q.shape
    hk = k.shape[1]

    def merge(t):
        return t.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            b, s, t.shape[1] * d)
    out = _reference_attention(merge(q), merge(k), merge(v), h, causal=True,
                               kv_heads=hk, window=window)
    return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)


CASES = [
    # batch, heads, kv_heads, seq, dim, window, fwd blocks, bwd blocks
    (2, 4, 2, 96, 16, None, (32, 32), (32, 32)),
    (1, 8, 1, 128, 16, 40, (32, 16), (16, 32)),
    (2, 4, 4, 100, 16, 24, (32, 32), (32, 16)),
    (1, 4, 1, 64, 8, 64, (16, 16), (16, 16)),
    (1, 6, 2, 72, 8, 17, (24, 8), (8, 24)),
    (1, 8, 2, 256, 32, 128, (64, 64), (64, 64)),
    (1, 2, 2, 160, 8, 1, (32, 32), (32, 32)),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("case", CASES, ids=[
    f"h{c[1]}kv{c[2]}s{c[3]}w{c[5]}" for c in CASES])
def test_kernels_against_the_xla_composition(case, dtype):
    b, h, hk, s, d, window, fwd, bwd = case
    q, k, v, w = _operands(b, h, hk, s, d, dtype)

    def flash(q, k, v):
        return F.flash_attention(
            q, k, v, causal=True, window=window, interpret=True,
            block_q=fwd[0], block_k=fwd[1], bwd_block_q=bwd[0],
            bwd_block_k=bwd[1])

    out, grads = H.out_and_vjp(flash, w, q, k, v)
    ref, ref_grads = H.out_and_vjp(lambda *a: _reference(*a, window), w,
                                   q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert out.dtype == dtype and out.shape == q.shape
    onp.testing.assert_allclose(out.astype(jnp.float32), ref, atol=tol,
                                rtol=tol)
    for g, r, like in zip(grads, ref_grads, (q, k, v)):
        assert g.shape == like.shape and g.dtype == dtype
        scale = float(jnp.max(jnp.abs(r))) or 1.0   # window 1: dq = dk = 0
        onp.testing.assert_allclose(g.astype(jnp.float32) / scale,
                                    r / scale, atol=tol)


def test_bad_groups_and_a_window_without_causal_are_refused():
    q, k, v, _ = _operands(1, 6, 4, 32, 8, jnp.float32)
    with pytest.raises(ValueError, match="group"):
        F.flash_attention(q, k, v, causal=True, interpret=True)
    q, k, v, _ = _operands(1, 4, 2, 32, 8, jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        F.flash_attention(q, k, v, causal=False, window=8, interpret=True)


# -- the tile schedule with a window ------------------------------------------

def _brute_force(seq, bq, bk, window):
    bq, bk = min(bq, seq), min(bk, seq)
    nq, nk = -(-seq // bq), -(-seq // bk)
    i = onp.arange(nq * bq)[:, None]
    j = onp.arange(nk * bk)[None, :]
    valid = (j < seq) & (i >= j) & (i - j < window)
    tiles = valid.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    full, some = tiles.all(axis=(2, 3)), tiles.any(axis=(2, 3))
    return {"computed": int(full.sum()), "masked": int((some & ~full).sum()),
            "skipped": int((~some).sum())}, some


@pytest.mark.parametrize("window", [1, 100, 128, 256, 1000, 2048, 5000])
@pytest.mark.parametrize("seq", [1024, 2048, 1000, 200])
def test_tile_schedule_with_a_window_equals_a_brute_force_count(seq, window):
    for bq, bk in [(128, 128), (128, 256), (256, 128), (512, 512),
                   (1024, 512), (64, 192)]:
        want, some = _brute_force(seq, bq, bk, window)
        assert F.tile_counts(seq, seq, bq, bk, True, window) == want, (bq, bk)
        cbq, cbk = min(bq, seq), min(bk, seq)
        nk = -(-seq // cbk)
        for qi in range(some.shape[0]):
            first = F._first_k_block(qi * cbq, cbk, window)
            last = F._fwd_visits(qi, nk, cbq, cbk, True)
            assert list(range(first, last)) == list(
                onp.flatnonzero(some[qi])), (bq, bk, qi)


def test_tile_counts_at_the_trinity_cells_shape():
    """8192 tokens at the v5e's 512/512: 256 tiles a head.  Full causal:
    120 whole, 16 on the diagonal, 120 above.  A 2048 window: a row has
    the diagonal tile, three whole ones and the band's left edge."""
    assert F.tile_counts(8192, 8192, 512, 512, True) == {
        "computed": 120, "masked": 16, "skipped": 120}
    assert F.tile_counts(8192, 8192, 512, 512, True, 2048) == {
        "computed": 42, "masked": 28, "skipped": 186}


def test_flash_tiles_counter_counts_the_window():
    q, k, v, _ = _operands(1, 4, 2, 256, 8, jnp.float32)
    blocks = dict(block_q=64, block_k=64, bwd_block_q=64, bwd_block_k=64)
    telemetry.enable()
    telemetry.reset()
    try:
        jax.make_jaxpr(jax.grad(lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, window=100, interpret=True,
            **blocks).sum(), argnums=(0, 1, 2)))(q, k, v)
        got = telemetry.counters("kernel.flash_tiles_total")
    finally:
        telemetry.enable(False)
    want = F.tile_counts(256, 256, 64, 64, True, 100)
    assert want["skipped"] > 6      # more than the triangle's
    assert got == {
        f'kernel.flash_tiles_total{{kernel="{kern}",kind="{kind}"}}': n * 4
        for kern in ("fwd", "bwd_dkv", "bwd_dq") for kind, n in want.items()}


def _index_map(eqn, operand):
    """A pallas_call's block index map for one operand, as a function of
    the grid indices."""
    bm = eqn.params["grid_mapping"].block_mappings[operand]
    jaxpr = bm.index_map_jaxpr

    def at(*idx):
        return tuple(int(x) for x in jax.core.eval_jaxpr(
            jaxpr.jaxpr, jaxpr.consts, *(jnp.int32(i) for i in idx)))
    return at


def test_tiles_left_of_the_band_are_not_fetched_and_groups_share_kv():
    """A skipped grid step names the block of the nearest step that has
    work (so the pipeline fetches nothing); a running step names its own
    block; query head ``i`` names KV head ``i // group``."""
    seq, bq, bk, window, group = 1024, 128, 128, 256, 4
    q, k, v, _ = _operands(1, 8, 2, seq, 8, jnp.float32)
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(
        lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, window=window, interpret=True,
            block_q=bq, block_k=bk, bwd_block_q=bq, bwd_block_k=bk).sum(),
        argnums=(0, 1, 2)))(q, k, v).jaxpr)
    by_name = {e.params["name"]: e for e in calls}
    n = seq // bq
    # forward: whole K and V of the query head's KV head
    k_of = _index_map(by_name["mx_flash_fwd"], 1)
    assert [k_of(i, 0)[0] for i in range(8)] == [0] * 4 + [1] * 4
    # dQ: grid (heads, q-blocks, k-blocks); K clamps at both ends
    k_of = _index_map(by_name["mx_flash_bwd_dq"], 4)
    for a in range(n):
        for b in range(n):
            head, blk, _ = k_of(5, a, b)
            assert head == 5 // group
            runs = F._tile_runs(a, b, bq, bk, True, window)
            lo = max(0, a - 2)          # window 256 = two blocks back
            assert blk == (b if runs else min(max(b, lo), a))
    # dK/dV: grid (kv heads, k-blocks, group, q-blocks); q clamps
    dkv = by_name["mx_flash_bwd_dkv"]
    assert dkv.params["grid_mapping"].grid == (2, n, group, n)
    q_of = _index_map(dkv, 0)
    for b in range(n):
        for a in range(n):
            head, blk, _ = q_of(1, b, 3, a)
            assert head == 1 * group + 3
            runs = F._tile_runs(a, b, bq, bk, True, window)
            assert blk == (a if runs else min(max(a, b), min(b + 2, n - 1)))
    # dK and dV leave once a KV head
    assert [o.aval.shape[:2] for o in dkv.outvars] == [(2, seq)] * 2


def test_the_plain_call_traces_as_it_did():
    """One KV head a query head and no window: the GPT-2 cells' call.
    Its three kernels keep their 2-d / 3-d grids, their index maps name
    the grid's own row (no division by a group) and their bodies hold no
    window comparison — every new branch is taken in Python."""
    x = jax.ShapeDtypeStruct((2, 4, 1024, 64), jnp.bfloat16)

    def jaxpr_of(**kw):
        return jax.make_jaxpr(jax.grad(
            lambda q, k, v: F.flash_attention(
                q, k, v, causal=True, interpret=True, **kw)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)

    plain = jaxpr_of()
    calls = {e.params["name"]: e for e in _pallas_calls(plain.jaxpr)}
    assert [len(calls[n].params["grid_mapping"].grid) for n in (
        "mx_flash_fwd", "mx_flash_bwd_dkv", "mx_flash_bwd_dq")] == [2, 3, 3]
    for eqn in calls.values():
        for bm in eqn.params["grid_mapping"].block_mappings:
            row = bm.index_map_jaxpr.jaxpr
            assert row.outvars[0] is row.invars[0]
    # a window adds a comparison to every tile body; the plain bodies
    # hold exactly the causal ones
    def compares(jaxpr):
        return sum(str(e.params["jaxpr"]).count(" lt ")
                   for e in _pallas_calls(jaxpr.jaxpr))
    assert compares(jaxpr_of(window=512)) > compares(plain) == 4
    assert str(jaxpr_of(window=None)) == str(plain)


# -- multi_head_attention ---------------------------------------------------

@pytest.mark.parametrize("window", [None, 5])
def test_multi_head_attention_groups_and_windows(window):
    rs = onp.random.RandomState(1)
    q = mx.np.array(rs.randn(2, 12, 4 * 8), dtype="float32")
    k = mx.np.array(rs.randn(2, 12, 2 * 8), dtype="float32")
    v = mx.np.array(rs.randn(2, 12, 2 * 8), dtype="float32")
    from mxnet_tpu.ops.attention import multi_head_attention
    out = multi_head_attention(q, k, v, 4, causal=True, kv_heads=2,
                               window=window).asnumpy()
    # by hand: head h of the queries against KV head h // 2
    qh = q.asnumpy().reshape(2, 12, 4, 8)
    kh = k.asnumpy().reshape(2, 12, 2, 8)
    vh = v.asnumpy().reshape(2, 12, 2, 8)
    i, j = onp.arange(12)[:, None], onp.arange(12)[None, :]
    mask = (j <= i) & ((i - j < window) if window else True)
    want = onp.zeros_like(qh)
    for h in range(4):
        s = onp.einsum("bqd,bkd->bqk", qh[:, :, h], kh[:, :, h // 2]) \
            / onp.sqrt(8)
        s = onp.where(mask, s, -onp.inf)
        p = onp.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want[:, :, h] = onp.einsum("bqk,bkd->bqd", p, vh[:, :, h // 2])
    onp.testing.assert_allclose(out, want.reshape(2, 12, 32), atol=2e-5)


@pytest.mark.parametrize("kv_heads,per_device", [
    (4, ((2, 4, 8, 8), (2, 2, 8, 8))),      # tp divides the KV heads
    (1, ((2, 8, 8, 8), (2, 1, 8, 8))),      # it does not: heads replicated
], ids=["kv4", "kv1"])
def test_grouped_heads_through_the_shard_map(monkeypatch, kv_heads,
                                             per_device):
    """Under a mesh each device runs the kernel on its own block: batch
    over 'dp', and heads over 'tp' only where 'tp' divides the KV heads —
    every query head then stays beside its KV head."""
    from mxnet_tpu import runtime
    from mxnet_tpu.ops import attention
    from mxnet_tpu.parallel import MeshConfig
    from mxnet_tpu.parallel.mesh import activation_sharding
    seen, real = [], F.flash_attention

    def flash(q, k, v, causal=False, window=None):
        seen.append((q.shape, k.shape, window))
        return real(q, k, v, causal=causal, window=window, interpret=True,
                    block_q=8, block_k=8, bwd_block_q=8, bwd_block_k=8)

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ_CAUSAL", 8)
    monkeypatch.setattr(F, "flash_attention", flash, raising=True)
    rs = onp.random.RandomState(0)
    q = mx.np.array(rs.randn(4, 8, 8 * 8).astype("float32"))
    k = mx.np.array(rs.randn(4, 8, kv_heads * 8).astype("float32"))
    v = mx.np.array(rs.randn(4, 8, kv_heads * 8).astype("float32"))
    want = attention._reference_attention(
        q._data, k._data, v._data, 8, causal=True, kv_heads=kv_heads,
        window=3)
    mesh = MeshConfig(dp=2, tp=2).build(jax.devices()[:4])
    with activation_sharding(mesh):
        got = attention.multi_head_attention(
            q, k, v, heads=8, causal=True, kv_heads=kv_heads, window=3)
    assert seen[-1] == per_device + (3,)
    onp.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
