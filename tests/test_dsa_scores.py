"""The indexer's scores as Pallas passes (ops/pallas/dsa_scores.py)
against the XLA composition they replace on the chip
(ops/sparse_index.py::_composed_scores, their oracle): the scores under
the diagonal and the three gradients, what meets the MXU, the dispatch in
``index_scores``, the ``shard_map`` under a mesh, the scope the backward
kernel is traced under, the tile counter, and ``nn.SparseIndexer`` end
to end.  Interpret mode on the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from mxnet_tpu import runtime
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import sparse_index
from mxnet_tpu.ops.pallas import dsa_align, dsa_scores
from mxnet_tpu.parallel import MeshConfig
from mxnet_tpu.parallel.mesh import activation_sharding
from family_harness import eqns as _eqns, kernel_tiles as _counted, \
    pallas_calls as _pallas_calls, pallas_names as _names


def _operands(b, s, heads, d, dtype=jnp.float32, seed=0):
    """q (b, s, heads, d), k (b, s, d), weights (b, s, heads) with the
    scale in them, and a cotangent that is nonzero on some causal pairs
    only (as ``mx_dsa_align``'s ``d_scores`` is)."""
    rs = onp.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, s, heads, d), dtype)
    k = jnp.asarray(rs.randn(b, s, d), dtype)
    w = jnp.asarray(rs.randn(b, s, heads) / d ** 0.5, jnp.float32)
    g = onp.tril(rs.randn(b, s, s)) * (rs.rand(b, s, s) < 0.5)
    return q, k, w, jnp.asarray(g, jnp.float32)


def _causal(s):
    return onp.tril(onp.ones((s, s), bool))


def _kernels(q, k, w, g, block):
    """The two passes on ``index_scores``' own operand layouts."""
    first = sparse_index._heads_first
    scores = jnp.swapaxes(dsa_scores.scores_pass(
        first(q), k, first(w), interpret=True, block=block), 1, 2)
    dq, dk, dw = dsa_scores.scores_bwd_pass(
        first(q), k, first(w), jnp.swapaxes(g, 1, 2), interpret=True,
        block=block)
    return scores, (first(dq), dk, first(dw))


# -- the kernels against the composition ------------------------------------

SHAPES = [
    (1, 16, 4, 64, 16),      # one tile, the cell's head width
    (2, 64, 4, 64, 16),      # 4 x 4 tiles, a batch of two
    (1, 48, 16, 8, 16),      # the cell's head count, narrow heads
    (2, 96, 2, 24, 32),      # a width that is no power of two
    (1, 64, 2, 128, 64),     # heads as wide as the lanes, whole seq
    (3, 32, 3, 16, 8),       # odd batch and head count
]
IDS = ["one-tile", "batch-2", "16-heads", "d-24", "wide", "odd"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,s,heads,d,block", SHAPES, ids=IDS)
def test_scores_under_the_diagonal_are_the_composition(b, s, heads, d, block,
                                                       dtype):
    """Every causal pair's score; the tiles wholly above the diagonal
    are zeros (the composition computes them, nobody reads them)."""
    q, k, w, g = _operands(b, s, heads, d, dtype, seed=s + heads)
    want = onp.asarray(H.traced(sparse_index._composed_scores, q, k, w))
    got = onp.asarray(H.traced(
        lambda *a: _kernels(*a, block)[0], q, k, w, g))
    assert got.shape == (b, s, s) and got.dtype == onp.float32
    causal = _causal(s)
    onp.testing.assert_allclose(got[:, causal], want[:, causal],
                                atol=2e-5 * d ** 0.5, rtol=2e-5)
    tiles = s // block
    for a in range(tiles):
        for c in range(a + 1, tiles):
            assert not got[:, a * block:(a + 1) * block,
                           c * block:(c + 1) * block].any()


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("b,s,heads,d,block", SHAPES, ids=IDS)
def test_gradients_are_the_compositions(b, s, heads, d, block, dtype, tol):
    """``dqI``, ``dkI`` and ``dw`` for a cotangent on causal pairs.  In
    float32 to rounding; in bf16 the kernel rounds ``dP`` to the
    operands' type before its two products, as the chip's MXU does to
    the composition's float32 ``dP`` (one bf16 pass at default
    precision) and the CPU does not."""
    q, k, w, g = _operands(b, s, heads, d, dtype, seed=s + d)
    want = H.out_and_vjp(sparse_index._composed_scores, g, q, k, w)[1]
    got = H.traced(lambda *a: _kernels(*a, block)[1], q, k, w, g)
    for name, a, r in zip(("dq", "dk", "dw"), got, want):
        a, r = (onp.asarray(t, onp.float32) for t in (a, r))
        assert a.shape == r.shape, name
        onp.testing.assert_allclose(a, r, atol=tol * onp.abs(r).max(),
                                    rtol=tol, err_msg=name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_operands_meet_the_mxu_as_they_come(dtype):
    """Read the kernels: a head's products take q and k in their own
    type and accumulate in float32 — one product a head forward, three
    backward (the product again, ``dP.T @ kI``, ``dP @ qI``), ``dP``
    cast to the operands' type — and the head sum is float32."""
    q, k, w, g = _operands(1, 32, 4, 16, dtype)
    first = sparse_index._heads_first

    def dots(f, *args):
        call, = _pallas_calls(jax.make_jaxpr(f)(*args).jaxpr)
        return [e for e in _eqns(call.params["jaxpr"])
                if e.primitive.name == "dot_general"]

    fwd = dots(lambda q, k, w: dsa_scores.scores_pass(
        first(q), k, first(w), interpret=True, block=16), q, k, w)
    bwd = dots(lambda q, k, w, g: dsa_scores.scores_bwd_pass(
        first(q), k, first(w), g, interpret=True, block=16), q, k, w, g)
    # forward: the first head's, which writes the tile, and the loop's;
    # backward: the loop's three
    assert len(fwd) == 2 and len(bwd) == 3
    for e in fwd + bwd:
        assert [str(v.aval.dtype) for v in e.invars] == [dtype, dtype]
        assert e.params["preferred_element_type"] == jnp.float32
        assert e.outvars[0].aval.dtype == jnp.float32


def test_a_sequence_the_blocks_do_not_divide_is_refused():
    q, k, w, g = _operands(1, 48, 2, 8)
    first = sparse_index._heads_first
    with pytest.raises(ValueError, match="no multiple of the block"):
        dsa_scores.scores_pass(first(q), k, first(w), interpret=True,
                               block=32)
    with pytest.raises(ValueError, match="no multiple of the block"):
        dsa_scores.scores_bwd_pass(first(q), k, first(w), g, interpret=True,
                                   block=32)


# -- index_scores: the dispatch ---------------------------------------------

def _on_the_kernels(monkeypatch, block):
    """A CPU that takes the TPU's route, its kernels interpreted."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    monkeypatch.setattr(dsa_align, "BLOCK", block)


def _loss(g):
    return lambda q, k, w: jnp.sum(sparse_index.index_scores(q, k, w) * g)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_off_the_tpu_index_scores_is_the_composition(dtype):
    """On the CPU no kernel is traced, forward or backward, and no tile
    is counted, whatever the sequence."""
    q, k, w, g = _operands(2, 64, 4, 16, dtype)
    (_, grads), tiles = _counted(
        jax.jit(jax.value_and_grad(_loss(g), (0, 1, 2))), q, k, w)
    assert tiles == {}
    assert _names(jax.grad(_loss(g), (0, 1, 2)), q, k, w) == []
    assert [t.dtype for t in grads] == [q.dtype, k.dtype, w.dtype]


@pytest.mark.parametrize("s", [40, 72, 100])
def test_a_ragged_sequence_falls_to_the_composition(monkeypatch, s):
    """On the TPU's route a sequence the block does not divide takes the
    composition: no kernel, no tile, the composition's values."""
    _on_the_kernels(monkeypatch, 16)
    q, k, w, g = _operands(1, s, 2, 8)
    want = jax.jit(lambda q, k, w: sparse_index._composed_scores(
        q, k, w * (1.0 / 8 ** 0.5)))(q, k, w)
    got, tiles = _counted(jax.jit(
        lambda *a: sparse_index.index_scores(*a)), q, k, w)
    assert tiles == {}
    assert _names(jax.grad(_loss(g), (0, 1, 2)), q, k, w) == []
    onp.testing.assert_array_equal(got, want)


def test_a_q_block_vmem_cannot_hold_falls_to_the_composition(monkeypatch):
    """``fits`` reckons what the backward call keeps resident: a head
    count, a width or a sequence beyond the limit takes the composition;
    the cell's shape is far inside."""
    assert dsa_scores.fits(8192, 16, 64, 2)
    assert dsa_scores.fits(32768, 16, 128, 2)
    assert not dsa_scores.fits(8192 + 256, 16, 64, 2)       # ragged
    assert not dsa_scores.fits(8192, 512, 128, 4)           # q-block
    assert not dsa_scores.fits(1 << 17, 16, 64, 2)          # dkI whole
    _on_the_kernels(monkeypatch, 16)
    monkeypatch.setattr(dsa_scores, "_VMEM_MAX", dsa_scores._VMEM_ROOM)
    q, k, w, g = _operands(1, 32, 2, 8)
    assert _names(jax.grad(_loss(g), (0, 1, 2)), q, k, w) == []


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-5), ("bfloat16", 2e-2)])
def test_on_the_tpu_index_scores_is_the_kernels(monkeypatch, dtype, tol):
    """At a sequence the blocks divide, ``index_scores`` and its
    ``jax.grad`` trace ``mx_dsa_scores`` and ``mx_dsa_scores_bwd`` —
    counted: tiles run + skipped = all tiles, a call — and agree with
    the composition; gradients come back in the operands' types."""
    b, s, heads, d = 2, 64, 4, 16
    q, k, w, g = _operands(b, s, heads, d, dtype, seed=7)
    causal = jnp.asarray(_causal(s))

    def loss(q, k, w):      # under the diagonal: the rest means nothing
        return jnp.sum(jnp.where(causal, sparse_index.index_scores(q, k, w),
                                 0.0) * g)

    want, g_want = H.traced(jax.value_and_grad(loss, (0, 1, 2)), q, k, w)
    _on_the_kernels(monkeypatch, 16)
    (got, g_got), tiles = _counted(
        H.traced, jax.value_and_grad(loss, (0, 1, 2)), q, k, w)
    # 4 x 4 tiles a batch row: 6 under the diagonal, 4 on it, 6 above
    one = {"computed": 6 * b, "masked": 4 * b, "skipped": 6 * b}
    assert tiles == {"dsa_scores": one, "dsa_scores_bwd": one}
    assert _names(jax.grad(loss, (0, 1, 2)), q, k, w) == [
        "mx_dsa_scores", "mx_dsa_scores_bwd"]
    assert _names(sparse_index.index_scores, q, k, w) == ["mx_dsa_scores"]
    onp.testing.assert_allclose(got, want, rtol=tol)
    for a, r in zip(g_got, g_want):
        assert a.dtype == r.dtype and a.shape == r.shape
        r = onp.asarray(r, onp.float32)
        onp.testing.assert_allclose(onp.asarray(a, onp.float32), r,
                                    atol=tol * onp.abs(r).max(), rtol=tol)


def test_the_cells_tiles(monkeypatch):
    """16 x 16 blocks of 512 at batch 1: 136 tiles run, 120 skipped, in
    either kernel (counted where the call is traced; nothing runs)."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    spec = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((1, 8192, 16, 64), jnp.bfloat16), ((1, 8192, 64), jnp.bfloat16),
        ((1, 8192, 16), jnp.float32))]
    _, tiles = _counted(jax.eval_shape, jax.grad(
        lambda q, k, w: jnp.sum(sparse_index.index_scores(q, k, w)),
        (0, 1, 2)), *spec)
    for kernel in ("dsa_scores", "dsa_scores_bwd"):
        kinds = tiles[kernel]
        assert kinds["computed"] + kinds["masked"] == 136
        assert kinds["skipped"] == 120


def test_the_backward_kernel_keeps_the_callers_scope(monkeypatch):
    """``dsa_index_ms.train`` reads operations whose name holds
    ``mx.dsa.index``: the backward rule of a ``custom_vjp`` is traced
    after the forward's ``named_scope`` has closed, and JAX hands it the
    scope round ``transpose(jvp(...))``."""
    _on_the_kernels(monkeypatch, 16)
    q, k, w, g = _operands(1, 32, 2, 8)

    def loss(q, k, w):
        with jax.named_scope("mx.dsa.index"):
            return jnp.sum(sparse_index.index_scores(q, k, w) * g)

    calls = {e.params["name"]: str(e.source_info.name_stack)
             for e in _pallas_calls(jax.make_jaxpr(
                 jax.grad(loss, (0, 1, 2)))(q, k, w).jaxpr)}
    assert sorted(calls) == ["mx_dsa_scores", "mx_dsa_scores_bwd"]
    for name, stack in calls.items():
        assert "mx.dsa.index" in stack, (name, stack)
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, w).as_text(
        debug_info=True)
    assert "transpose(jvp(mx.dsa.index))" in text


# -- under a mesh -----------------------------------------------------------

def test_under_a_mesh_the_kernels_sit_in_a_shard_map(monkeypatch):
    """On a dp x tp mesh each device runs both kernels on its rows of the
    batch with every head (they are summed in the kernel); values and
    gradients are the composition's, and the step lowers for the TPU
    with the kernels inside (GSPMD partitions no Mosaic call)."""
    _on_the_kernels(monkeypatch, 16)
    seen = []
    real, real_bwd = dsa_scores.scores_pass, dsa_scores.scores_bwd_pass

    def scores_pass(q, k, w, **kw):
        seen.append(("fwd", q.shape, k.shape, w.shape))
        return real(q, k, w, **kw)

    def scores_bwd_pass(q, k, w, g, **kw):
        seen.append(("bwd", q.shape, k.shape, w.shape, g.shape))
        return real_bwd(q, k, w, g, **kw)

    monkeypatch.setattr(dsa_scores, "scores_pass", scores_pass)
    monkeypatch.setattr(dsa_scores, "scores_bwd_pass", scores_bwd_pass)
    b, s, heads, d = 4, 32, 4, 8
    q, k, w, g = _operands(b, s, heads, d, seed=5)
    causal = jnp.asarray(_causal(s))

    def loss(q, k, w):
        return jnp.sum(jnp.where(causal, sparse_index.index_scores(q, k, w),
                                 0.0) * g)

    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(jax.value_and_grad(
            lambda q, k, w: jnp.sum(sparse_index._composed_scores(
                q, k, w / d ** 0.5) * g), (0, 1, 2)))(q, k, w)
        mesh = MeshConfig(dp=2, tp=2).build(jax.devices()[:4])
        with activation_sharding(mesh):
            got, g_got = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(q, k, w)
    assert seen[0] == ("fwd", (2, heads, s, d), (2, s, d), (2, heads, s))
    assert seen[-1] == ("bwd", (2, heads, s, d), (2, s, d), (2, heads, s),
                        (2, s, s))
    onp.testing.assert_allclose(got, want, rtol=2e-5)
    for a, r in zip(g_got, g_want):
        onp.testing.assert_allclose(a, r, atol=2e-5 * onp.abs(r).max(),
                                    rtol=2e-5)
    # for Mosaic: blocks of 128 lanes, nothing interpreted
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: False)
    monkeypatch.setattr(dsa_align, "BLOCK", 128)
    s = 256
    specs = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
        (b, s, heads, d), (b, s, d), (b, s, heads))]
    with activation_sharding(mesh):
        text = jax.jit(jax.value_and_grad(
            lambda q, k, w: jnp.sum(sparse_index.index_scores(q, k, w)),
            (0, 1, 2))).trace(*specs).lower(
                lowering_platforms=("tpu",)).as_text()
    assert seen[-1][1] == (2, heads, s, d)
    assert text.count("tpu_custom_call") == 2
    assert "mx_dsa_scores_bwd" in text


# -- nn.SparseIndexer end to end --------------------------------------------

def _indexer(seed=0):
    net = nn.SparseIndexer(32, num_heads=4, head_dim=16, topk=6)
    net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    mx.random.seed(seed)
    return net


def _indexed(net, x):
    """The indexer's inference forward (scores, selection) as one
    program."""
    from mxnet_tpu import functional
    return H.traced(lambda p, x_: functional.functional_call(
        net, p, x_)[0], functional.param_arrays(net), x._data)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_indexer_selects_the_same_either_way(monkeypatch, seed):
    """``nn.SparseIndexer`` on a seeded input: the TPU's route (kernels
    interpreted) scores every causal pair as the CPU's does and picks
    the same keys, with the same counts."""
    net = _indexer(seed)
    x = mx.np.array(onp.random.RandomState(seed).randn(2, 48, 32).astype(
        "float32"))
    net.infer_shape(x[:1, :1])
    want_i, want_sel = _indexed(net, x)
    _on_the_kernels(monkeypatch, 16)
    got_i, got_sel = _indexed(net, x)
    causal = _causal(48)
    onp.testing.assert_allclose(onp.asarray(got_i)[:, causal],
                                onp.asarray(want_i)[:, causal], atol=1e-5,
                                rtol=1e-5)
    onp.testing.assert_array_equal(got_sel, want_sel)
    assert onp.asarray(got_sel).sum() == 2 * sum(min(t + 1, 6)
                                                 for t in range(48))


def test_sparse_indexer_learns_the_same_either_way(monkeypatch):
    """Every leaf of the indexer gets the composition's gradient from a
    loss on the scores through ``mx_dsa_scores_bwd``."""
    from mxnet_tpu import functional
    net = _indexer()
    x = jnp.asarray(onp.random.RandomState(3).randn(2, 32, 32), jnp.float32)
    net(mx.np.array(x))
    params, aux = functional.split_params(net)
    ct = jnp.asarray(onp.tril(onp.random.RandomState(4).randn(2, 32, 32)),
                     jnp.float32)

    def loss(p):
        (scores, _), _ = functional.functional_call(
            net, {**p, **aux}, x, train=True)
        return jnp.sum(scores * ct)

    want, g_want = H.traced(jax.value_and_grad(loss), params)
    _on_the_kernels(monkeypatch, 16)
    with jax.default_matmul_precision("highest"):
        names = _names(jax.grad(loss), params)
    got, g_got = H.traced(jax.value_and_grad(loss), params)
    assert names == ["mx_dsa_scores", "mx_dsa_scores_bwd"]
    onp.testing.assert_allclose(got, want, rtol=1e-5)
    assert set(g_got) == set(g_want) and len(g_want) >= 4
    for name in g_want:
        r = onp.asarray(g_want[name])
        onp.testing.assert_allclose(g_got[name], r, rtol=1e-4,
                                    atol=1e-5 * max(onp.abs(r).max(), 1.0),
                                    err_msg=name)
