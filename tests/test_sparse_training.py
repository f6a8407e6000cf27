"""Sparse training end-to-end (reference: row_sparse gradients from
Embedding(sparse_grad=True) -> lazy_update optimizers
(python/mxnet/optimizer/sgd.py lazy_update over
src/operator/optimizer_op.cc SGDUpdateRspImpl) -> kvstore row_sparse
push/pull).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, optimizer as opt
from mxnet_tpu.ndarray.sparse import (RowSparseNDArray, dedupe_coo,
                                      row_sparse_array)

VOCAB, DIM = 50, 4


def _embed_net(sparse_grad):
    net = gluon.nn.Embedding(VOCAB, DIM, sparse_grad=sparse_grad)
    net.initialize()
    return net


def test_dedupe_coo_sums_duplicates():
    idx = jnp.array([3, 1, 3, 7, 1, 3])
    vals = jnp.arange(6.0).reshape(6, 1)
    uidx, uvals = dedupe_coo(idx, vals, 10)
    assert uidx.shape == (6,)
    dense = jnp.zeros((10, 1)).at[uidx].add(uvals, mode="drop")
    ref = jnp.zeros((10, 1)).at[idx].add(vals)
    onp.testing.assert_allclose(onp.asarray(dense), onp.asarray(ref))
    # padding slots carry the sentinel index and zero values
    assert int(uidx[3]) == 10 and float(jnp.abs(uvals[3:]).sum()) == 0


def test_embedding_sparse_grad_is_row_sparse():
    net = _embed_net(sparse_grad=True)
    x = mx.np.array(onp.array([[1, 3], [3, 7]]), dtype="int32")
    with autograd.record():
        out = net(x)
        loss = (out * out).sum()
    loss.backward()
    g = net.weight.grad()
    assert isinstance(g, RowSparseNDArray)
    assert g.shape == (VOCAB, DIM)
    # matches the dense-path gradient when densified
    dense_net = _embed_net(sparse_grad=False)
    dense_net.weight.set_data(net.weight.data())
    with autograd.record():
        out2 = dense_net(x)
        loss2 = (out2 * out2).sum()
    loss2.backward()
    onp.testing.assert_allclose(g.tostype("default").asnumpy(),
                                dense_net.weight.grad().asnumpy(),
                                rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("optname,kw", [
    ("sgd", dict(learning_rate=0.1, momentum=0.0)),
    ("sgd", dict(learning_rate=0.1, momentum=0.9)),
    ("adam", dict(learning_rate=0.05)),
])
def test_sparse_vs_dense_training_converges_identically(optname, kw):
    """A tiny embedding classifier trained with sparse lazy updates must
    track the dense path exactly.  Every batch touches the same row set:
    on that set lazy and standard stateful updates coincide, and rows
    never touched keep zero state in both (wd=0) — the regime where the
    reference documents bitwise-equal results (sgd.py lazy_update note).
    """
    # each 5x3 batch covers ids 0..9 (some twice); repeated 4 times
    batch = onp.array([[0, 1, 0], [2, 3, 1], [4, 5, 2],
                       [6, 7, 3], [8, 9, 4]], dtype="int32")
    xs = onp.concatenate([batch] * 4, axis=0)
    ys = (xs.sum(-1) % 2).astype("float32")

    def train(sparse):
        net = _embed_net(sparse_grad=sparse)
        onp.random.seed(7)
        net.weight.set_data(mx.np.array(
            onp.random.RandomState(7).randn(VOCAB, DIM).astype("float32")))
        o = opt.create(optname, lazy_update=sparse, wd=0.0, **kw)
        trainer = gluon.Trainer(net.collect_params(), o)
        for i in range(0, 20, 5):
            x = mx.np.array(xs[i:i + 5])
            y = mx.np.array(ys[i:i + 5])
            with autograd.record():
                emb = net(x)
                score = emb.sum(axis=(1, 2))
                loss = ((score - y) ** 2).mean()
            loss.backward()
            trainer.step(1)
        return net.weight.data().asnumpy(), float(loss.asnumpy())

    w_sparse, l_sparse = train(True)
    w_dense, l_dense = train(False)
    onp.testing.assert_allclose(w_sparse, w_dense, rtol=1e-4, atol=1e-5)
    assert l_sparse == pytest.approx(l_dense, rel=1e-4)


def test_a_sparse_step_with_telemetry_on_notes_its_gradient_norm():
    """``Trainer.step`` with telemetry on takes the global gradient norm:
    of a row-sparse gradient, the norm of the rows it holds (it raised
    on the sparse array's missing ``_data`` whenever an earlier test in
    the process had left telemetry on)."""
    from mxnet_tpu import telemetry
    net = _embed_net(sparse_grad=True)
    trainer = gluon.Trainer(net.collect_params(), opt.create(
        "sgd", learning_rate=0.1, lazy_update=True, wd=0.0))
    x = mx.np.array(onp.array([[0, 1, 0], [2, 3, 1]], dtype="int32"))
    telemetry.reset()
    telemetry.enable()
    try:
        with autograd.record():
            loss = (net(x).sum(axis=(1, 2)) ** 2).mean()
        loss.backward()
        want = float(onp.sqrt((net.weight.grad().data.asnumpy() ** 2).sum()))
        assert trainer._grad_norm() == pytest.approx(want, rel=1e-6)
        trainer.step(1)
    finally:
        telemetry.enable(False)
        telemetry.reset()


def test_lazy_update_touches_only_nnz_rows():
    """O(nnz) assertion: jaxpr of the lazy SGD step must contain no
    elementwise math over the full (VOCAB, DIM) table — only gather,
    row-block math and scatter."""
    big_vocab = 10_000
    sgd = opt.create("sgd", learning_rate=0.1, momentum=0.9, lazy_update=True)
    w = jnp.zeros((big_vocab, DIM))
    from mxnet_tpu.numpy.multiarray import _wrap
    state = _wrap(jnp.zeros((big_vocab, DIM)))
    idx = jnp.array([5, 17, 123], dtype=jnp.int32)
    vals = jnp.ones((3, DIM))
    rsp = RowSparseNDArray(_wrap(vals), _wrap(idx), (big_vocab, DIM))

    jaxpr = jax.make_jaxpr(
        lambda w_, g_, m_: sgd._lazy_update_impl(
            w_, RowSparseNDArray(_wrap(g_), _wrap(idx), (big_vocab, DIM)),
            _wrap(m_), 0.1, 0.0)[0])(w, vals, state._data)
    full_size = big_vocab * DIM
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name in ("scatter", "scatter-set", "gather"):
            continue  # the O(nnz)-indexed table accesses themselves
        for v in eqn.outvars:
            size = 1
            for s in getattr(v.aval, "shape", ()):
                size *= s
            assert size < full_size, (
                f"{eqn.primitive.name} materializes a full-table temp "
                f"{v.aval.shape} — lazy update must be O(nnz)")

    # and the weight values behave: only idx rows change
    new_w, _ = sgd._lazy_update_impl(w + 1.0, rsp, state, 0.1, 0.0)
    changed = onp.nonzero(onp.abs(onp.asarray(new_w) - 1.0).sum(-1))[0]
    onp.testing.assert_array_equal(changed, [5, 17, 123])


def test_kvstore_row_sparse_training_loop():
    """update_on_kvstore-style loop: push row_sparse grads, optimizer runs
    on the store (lazy), row_sparse_pull fetches only needed rows."""
    kv = mx.kv.create("local")
    weight = mx.np.array(onp.random.RandomState(3).randn(VOCAB, DIM)
                         .astype("float32"))
    kv.init("emb", weight)
    kv.set_optimizer(opt.create("sgd", learning_rate=0.5, momentum=0.9,
                                lazy_update=True))
    w_ref = weight.asnumpy().copy()

    for step in range(3):
        ids = onp.array([2, 9, 2, 31])
        vals = onp.random.RandomState(step).randn(4, DIM).astype("float32")
        uidx, uvals = dedupe_coo(jnp.asarray(ids), jnp.asarray(vals), VOCAB)
        from mxnet_tpu.numpy.multiarray import _wrap
        g = RowSparseNDArray(_wrap(uvals), _wrap(uidx), (VOCAB, DIM))
        kv.push("emb", g)

    out = mx.np.zeros((VOCAB, DIM))
    kv.pull("emb", out=out)
    new_w = out.asnumpy()
    untouched = [i for i in range(VOCAB) if i not in (2, 9, 31)]
    onp.testing.assert_allclose(new_w[untouched], w_ref[untouched])
    assert onp.abs(new_w[[2, 9, 31]] - w_ref[[2, 9, 31]]).sum() > 0

    rows = kv.row_sparse_pull("emb", row_ids=mx.np.array([2, 31]))
    assert isinstance(rows, RowSparseNDArray)
    onp.testing.assert_allclose(rows.tostype("default").asnumpy()[[2, 31]],
                                new_w[[2, 31]], rtol=1e-6)
