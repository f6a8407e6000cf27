"""The alignment loss ``L_I`` as one Pallas pass (ops/pallas/dsa_align.py)
against the XLA composition it replaces on the chip
(ops/sparse_index.py::_align_pass, its oracle): value and closed-form
gradient, the statistics the forward flash kernel hands it, the dispatch
in ``align_loss``, the ``shard_map`` under a mesh, and the tile counter.
Interpret mode on the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from mxnet_tpu import runtime
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import attention, sparse_index
from mxnet_tpu.ops.pallas import dsa_align
from mxnet_tpu.ops.pallas import flash_attention as F
from mxnet_tpu.parallel import MeshConfig
from mxnet_tpu.parallel.mesh import activation_sharding


def _operands(b, s, h, hk, d, topk, seed=0, dtype=jnp.float32):
    rs = onp.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, s, h * d), dtype)
    k = jnp.asarray(rs.randn(b, s, hk * d), dtype)
    scores = jnp.asarray(rs.randn(b, s, s), jnp.float32)
    return scores, jax.jit(
        lambda i: sparse_index.select_topk(i, topk))(scores), q, k


def _split(t, n):
    b, s, nd = t.shape
    return t.reshape(b, s, n, nd // n).transpose(0, 2, 1, 3)


def _align_pass(scores, sel, *rest, **kw):
    """``dsa_align.align_pass`` on query-major scores and selection: the
    kernel takes and returns them key-major."""
    kl, d = dsa_align.align_pass(jnp.swapaxes(scores, 1, 2),
                                 jnp.swapaxes(sel, 1, 2), *rest, **kw)
    return kl, jnp.swapaxes(d, 1, 2)


def _plain_lse(sel, q, k, h, hk):
    """Each head's log-sum-exp over the selected keys, written plainly."""
    d = q.shape[-1] // h
    att = jnp.einsum("bhqd,bhkd->bhqk", _split(q, h),
                     jnp.repeat(_split(k, hk), h // hk, 1),
                     preferred_element_type=jnp.float32) / d ** 0.5
    return jax.nn.logsumexp(jnp.where((sel != 0)[:, None], att, -1e30), -1)


def _both(scores, sel, q, k, h, hk, block, precise=True):
    """(the composition's loss and ``d_scores``, the kernel's row sums
    and ``d_scores`` given the plainly written statistics): one program
    a side."""
    b, s = scores.shape[:2]

    def kernel(scores, sel, q, k):
        return _align_pass(
            scores, sel, _split(q, h), _split(k, hk),
            _plain_lse(sel, q, k, h, hk), b * s, interpret=True, block=block)

    run = H.traced if precise else (lambda f, *a: jax.jit(f)(*a))
    return (run(lambda *a: sparse_index._align_pass(*a, h, hk),
                scores, sel, q, k), run(kernel, scores, sel, q, k))


# -- the kernel against the composition -------------------------------------

@pytest.mark.parametrize("b,s,h,hk,d,topk,block", [
    (2, 64, 8, 2, 16, 24, 16),      # grouped heads, batch 2, 4 x 4 tiles
    (1, 96, 4, 4, 8, 5, 32),        # a head a KV head, a tiny topk
    (2, 48, 8, 1, 32, 100, 16),     # one KV head, every causal pair chosen
    (1, 128, 2, 1, 128, 33, 128),   # one tile, a head as wide as the lanes
    (1, 64, 4, 2, 16, 24, 64),      # a block as long as the sequence
], ids=["grouped", "ungrouped", "dense", "one-tile", "whole-seq"])
def test_kernel_is_the_composition(b, s, h, hk, d, topk, block):
    """Value and ``d_scores`` to float32 rounding, rows with fewer than
    ``topk`` earlier keys and rows with exactly ``topk``, tiles that are
    skipped, crossed by the diagonal and wholly under it; ``d_scores``
    exactly 0 off the selection, above the diagonal included."""
    scores, sel, q, k = _operands(b, s, h, hk, d, topk, seed=s)
    chosen = onp.asarray(sel) != 0
    assert sorted(set(chosen.sum(-1).ravel())) == sorted(
        set(min(t + 1, topk) for t in range(s)))
    (want, want_d), (kl, got_d) = _both(scores, sel, q, k, h, hk, block)
    assert kl.shape == (b, 1, s) and got_d.shape == (b, s, s)
    onp.testing.assert_allclose(jnp.sum(kl) / (b * s), want, rtol=2e-6)
    onp.testing.assert_allclose(got_d, want_d, atol=2e-9, rtol=2e-5)
    assert not onp.asarray(got_d)[~chosen].any()
    assert onp.asarray(got_d)[chosen].any()


def test_bf16_operands_take_one_mxu_product_a_head():
    """Under AMP q and k arrive in bf16: the kernel multiplies them as
    they are, accumulates in float32, and agrees with the composition on
    the same operands."""
    scores, sel, q, k = _operands(2, 64, 8, 2, 16, 24, dtype=jnp.bfloat16)
    (want, want_d), (kl, got_d) = _both(scores, sel, q, k, 8, 2, 16,
                                        precise=False)
    onp.testing.assert_allclose(jnp.sum(kl) / 128, want, rtol=1e-5)
    onp.testing.assert_allclose(got_d, want_d, atol=1e-8, rtol=1e-4)


def test_a_sequence_the_blocks_do_not_divide_is_refused():
    scores, sel, q, k = _operands(1, 48, 2, 1, 8, 5)
    with pytest.raises(ValueError, match="no multiple of the block"):
        _align_pass(scores, sel, _split(q, 2), _split(k, 1),
                    _plain_lse(sel, q, k, 2, 1), 48, interpret=True,
                    block=32)


# -- the statistics the forward flash kernel hands out ----------------------

@pytest.mark.parametrize("s,d,block", [(64, 16, 16), (70, 16, 32),
                                       (128, 128, 64)])
def test_flash_forward_hands_out_its_lse(s, d, block):
    """``flash_attention(return_lse=True)``: the output it returns
    without, and each head's log-sum-exp over the selected keys; the
    gradients of the output are those of a call that does not ask, and
    the statistics carry none."""
    b, h, hk, topk = 2, 4, 2, 24
    scores, sel, q, k = _operands(b, s, h, hk, d, topk, seed=1)
    qh, kh = _split(q, h), _split(k, hk)
    vh = jnp.cos(kh)
    kw = dict(causal=True, selection=sel, interpret=True, block_q=block,
              block_k=block, bwd_block_q=block, bwd_block_k=block)

    def plain(q, k, v):
        return jnp.sum(jnp.sin(F.flash_attention(q, k, v, **kw)))

    def asking(q, k, v):
        out, lse = F.flash_attention(q, k, v, return_lse=True, **kw)
        return jnp.sum(jnp.sin(out)) + jnp.sum(lse), (out, lse)

    want, g_want = H.traced(lambda *a: (
        F.flash_attention(*a, **kw), jax.grad(plain, (0, 1, 2))(*a)),
        qh, kh, vh)
    (_, (out, lse)), g_got = H.traced(jax.value_and_grad(
        asking, (0, 1, 2), has_aux=True), qh, kh, vh)
    lse_want = H.traced(lambda *a: _plain_lse(*a, h, hk), sel, q, k)
    assert lse.shape == (b, h, s) and lse.dtype == jnp.float32
    onp.testing.assert_array_equal(out, want)
    onp.testing.assert_allclose(lse, lse_want, atol=2e-6)
    for got, ref in zip(g_got, g_want):
        onp.testing.assert_array_equal(got, ref)


def test_off_the_kernels_there_is_no_lse():
    """The composition keeps no such statistic: ``return_lse`` gives
    ``(out, None)`` and the output of a call that does not ask."""
    rs = onp.random.RandomState(0)
    q = mx.np.array(rs.randn(2, 12, 32).astype("float32"))
    k, v = (mx.np.array(rs.randn(2, 12, 16).astype("float32"))
            for _ in range(2))
    want = attention.multi_head_attention(q, k, v, 4, causal=True,
                                          kv_heads=2)
    out, lse = attention.multi_head_attention(q, k, v, 4, causal=True,
                                              kv_heads=2, return_lse=True)
    assert lse is None
    onp.testing.assert_array_equal(out.asnumpy(), want.asnumpy())


# -- align_loss: the dispatch, the gradient, the mesh -----------------------

def test_align_loss_with_the_statistics_is_the_kernel(monkeypatch):
    """``align_loss`` with ``lse`` at a sequence the blocks divide into
    runs ``mx_dsa_align`` — counted: tiles run + skipped = all tiles —
    and is the composition in value and in ``jax.grad`` by the scores; q,
    k and ``lse`` get no gradient.  Without ``lse``, or at a sequence the
    blocks do not divide, it is the composition itself."""
    monkeypatch.setattr(dsa_align, "BLOCK", 16)
    b, s, h, hk, d, topk = 2, 64, 8, 2, 16, 24
    scores, sel, q, k = _operands(b, s, h, hk, d, topk, seed=3)
    lse = jax.jit(lambda *a: _plain_lse(*a, h, hk))(sel, q, k)

    def composed(i, q, k):
        return sparse_index.align_loss(i, sel, q, k, h, hk)

    def kernel(i, q, k, lse):
        return sparse_index.align_loss(i, sel, q, k, h, hk, lse)

    def names(f, *args):
        return H.pallas_names(jax.grad(f), *args)

    def both():
        return (H.traced(jax.value_and_grad(composed), scores, q, k),
                H.traced(jax.value_and_grad(kernel, (0, 1, 2, 3)),
                         scores, q, k, lse))

    ((want, g_want), (got, grads)), tiles = H.kernel_tiles(both)
    tiles = tiles["dsa_align"]
    onp.testing.assert_allclose(got, want, rtol=2e-6)
    onp.testing.assert_allclose(grads[0], g_want, atol=2e-9, rtol=2e-5)
    assert not any(onp.asarray(g).any() for g in grads[1:])
    # 4 x 4 tiles a batch row: 6 under the diagonal, 4 on it, 6 above
    assert tiles == {"computed": 6 * b, "masked": 4 * b, "skipped": 6 * b}
    assert sum(tiles.values()) == b * (s // 16) ** 2
    assert names(kernel, scores, q, k, lse) == ["mx_dsa_align"]
    assert names(composed, scores, q, k) == []
    ragged = _operands(1, 40, h, hk, d, 9)
    assert names(lambda i, q, k, l: sparse_index.align_loss(
        i, ragged[1], q, k, h, hk, l), ragged[0], *ragged[2:],
        jax.ShapeDtypeStruct((1, h, 40), jnp.float32)) == []


def _on_the_kernels(monkeypatch, block):
    """A CPU that takes the TPU's routes, its kernels interpreted."""
    real = F.flash_attention

    def flash(q, k, v, **kw):
        return real(q, k, v, interpret=True, block_q=block, block_k=block,
                    bwd_block_q=block, bwd_block_k=block, **kw)

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ_CAUSAL", block)
    monkeypatch.setattr(F, "flash_attention", flash, raising=True)
    monkeypatch.setattr(dsa_align, "BLOCK", block)


def _indexed_attention(seed=0):
    net = nn.IndexedAttention(32, 4, 2, 8, index_heads=2, index_dim=8,
                              topk=6)
    net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    mx.random.seed(seed)
    return net


def test_indexed_attention_takes_the_kernel_where_the_core_took_its_own(
        monkeypatch):
    """``nn.IndexedAttention`` on the TPU's routes (kernels interpreted)
    against itself on the CPU's: output, loss, and every leaf's gradient
    — the indexer's from ``L_I`` through ``mx_dsa_align``'s
    ``d_scores`` and ``mx_dsa_scores_bwd``."""
    from mxnet_tpu import functional
    net = _indexed_attention()
    x = jnp.asarray(onp.random.RandomState(1).randn(2, 32, 32), jnp.float32)
    net(mx.np.array(x))
    params, aux = functional.split_params(net)

    def loss(p):
        (out, l_i), _ = functional.functional_call(
            net, {**p, **aux}, x, train=True)
        return jnp.sum(jnp.sin(out)) + l_i, l_i

    (want, want_li), g_want = H.traced(
        jax.value_and_grad(loss, has_aux=True), params)
    _on_the_kernels(monkeypatch, 16)
    with jax.default_matmul_precision("highest"):
        names = H.pallas_names(jax.grad(lambda p: loss(p)[0]), params)
    (got, got_li), g_got = H.traced(
        jax.value_and_grad(loss, has_aux=True), params)
    assert names == ["mx_dsa_align", "mx_dsa_scores",
                             "mx_dsa_scores_bwd", "mx_flash_bwd_dkv",
                             "mx_flash_bwd_dq", "mx_flash_fwd"]
    assert float(want_li) > 0
    onp.testing.assert_allclose(got_li, want_li, rtol=1e-5)
    onp.testing.assert_allclose(got, want, rtol=1e-5)
    for name in g_want:
        onp.testing.assert_allclose(g_got[name], g_want[name], atol=1e-5,
                                    rtol=1e-5, err_msg=name)


def test_under_a_mesh_the_kernel_sits_in_a_shard_map(monkeypatch):
    """On a dp x tp mesh each device runs ``mx_dsa_align`` on its rows of
    the batch with every head (a query's heads are summed in the
    kernel), the loss is the whole batch's mean, and the step lowers for
    the TPU with the kernel inside (GSPMD partitions no Mosaic call)."""
    _on_the_kernels(monkeypatch, 16)
    seen, real = [], dsa_align.align_pass

    def align_pass(scores, selection, q, k, lse, tokens, **kw):
        seen.append((scores.shape, q.shape, k.shape, lse.shape, tokens))
        return real(scores, selection, q, k, lse, tokens, **kw)

    monkeypatch.setattr(dsa_align, "align_pass", align_pass)
    b, s, h, hk, d, topk = 4, 32, 4, 2, 8, 6
    scores, sel, q, k = _operands(b, s, h, hk, d, topk, seed=5)
    lse = jax.jit(lambda *a: _plain_lse(*a, h, hk))(sel, q, k)

    def loss(i, q, k, lse):
        return sparse_index.align_loss(i, sel, q, k, h, hk, lse)

    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(jax.value_and_grad(
            lambda i: sparse_index.align_loss(i, sel, q, k, h, hk)))(scores)
        mesh = MeshConfig(dp=2, tp=2).build(jax.devices()[:4])
        with activation_sharding(mesh):
            got, g_got = jax.jit(jax.value_and_grad(loss))(scores, q, k, lse)
    assert seen[0] == ((2, s, s), (2, h, s, d), (2, hk, s, d), (2, h, s),
                       b * s)
    onp.testing.assert_allclose(got, want, rtol=2e-6)
    onp.testing.assert_allclose(g_got, g_want, atol=2e-9, rtol=2e-5)
    # for Mosaic: blocks of 128 lanes, nothing interpreted
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: False)
    monkeypatch.setattr(dsa_align, "BLOCK", 128)
    s = 256
    specs = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
        (b, s, s), (b, s, h * d), (b, s, hk * d), (b, h, s))]
    sel = jnp.zeros((b, s, s), jnp.int8)
    with activation_sharding(mesh):
        text = jax.jit(jax.grad(loss)).trace(*specs).lower(
            lowering_platforms=("tpu",)).as_text()
    assert seen[-1][:2] == ((2, s, s), (2, h, s, d))
    assert text.count("tpu_custom_call") == 1 and "mx_dsa_align" in text
