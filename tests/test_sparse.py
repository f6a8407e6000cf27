"""Sparse NDArray (row_sparse/CSR) tests.

Reference layout: tests/python/unittest/test_sparse_ndarray.py +
test_sparse_operator.py — construction, tostype round-trips, retain,
sparse dot vs dense oracle, kvstore row_sparse_pull.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray import sparse


def _rand_dense_rows(rows=8, cols=5, density=0.4, seed=0):
    rng = onp.random.RandomState(seed)
    d = rng.randn(rows, cols).astype("float32")
    mask = rng.rand(rows) < (1 - density)
    d[mask] = 0
    return d


def test_row_sparse_from_dense_roundtrip():
    d = _rand_dense_rows()
    rsp = sparse.row_sparse_array(d)
    assert rsp.stype == "row_sparse"
    onp.testing.assert_array_equal(rsp.asnumpy(), d)
    # indices are exactly the non-zero rows, sorted
    nz = onp.where(d.any(axis=1))[0]
    onp.testing.assert_array_equal(onp.asarray(rsp.indices._data), nz)


def test_row_sparse_from_components():
    data = onp.ones((2, 3), "float32")
    rsp = sparse.row_sparse_array((data, [1, 4]), shape=(6, 3))
    dense = rsp.tostype("default").asnumpy()
    expect = onp.zeros((6, 3), "float32")
    expect[[1, 4]] = 1
    onp.testing.assert_array_equal(dense, expect)


def test_ndarray_tostype():
    d = mx.np.array(_rand_dense_rows())
    rsp = d.tostype("row_sparse")
    assert rsp.stype == "row_sparse"
    onp.testing.assert_array_equal(rsp.asnumpy(), d.asnumpy())
    csr = d.tostype("csr")
    assert csr.stype == "csr"
    onp.testing.assert_array_equal(csr.asnumpy(), d.asnumpy())
    assert d.tostype("default") is d


def test_retain():
    data = onp.arange(9, dtype="float32").reshape(3, 3)
    rsp = sparse.row_sparse_array((data, [0, 2, 5]), shape=(6, 3))
    kept = sparse.retain(rsp, [2, 5])
    onp.testing.assert_array_equal(onp.asarray(kept.indices._data), [2, 5])
    dense = kept.asnumpy()
    assert (dense[0] == 0).all()
    onp.testing.assert_array_equal(dense[2], data[1])
    onp.testing.assert_array_equal(dense[5], data[2])


def test_csr_from_dense_and_dot_oracle():
    rng = onp.random.RandomState(3)
    d = rng.randn(6, 7).astype("float32")
    d[rng.rand(6, 7) < 0.6] = 0
    csr = sparse.csr_matrix(d)
    rhs = rng.randn(7, 4).astype("float32")
    out = sparse.dot(csr, mx.np.array(rhs))
    onp.testing.assert_allclose(out.asnumpy(), d @ rhs, rtol=1e-5, atol=1e-5)
    # transpose_a
    outT = sparse.dot(csr, mx.np.array(rng.randn(6, 2).astype("float32")),
                      transpose_a=True)
    assert outT.shape == (7, 2)


def test_csr_transpose_dot_oracle():
    rng = onp.random.RandomState(4)
    d = rng.randn(5, 6).astype("float32")
    d[rng.rand(5, 6) < 0.5] = 0
    rhs = rng.randn(5, 3).astype("float32")
    csr = sparse.csr_matrix(d)
    out = sparse.dot(csr, mx.np.array(rhs), transpose_a=True)
    onp.testing.assert_allclose(out.asnumpy(), d.T @ rhs, rtol=1e-5,
                                atol=1e-5)


def test_sparse_zeros():
    z = sparse.zeros("row_sparse", (4, 3))
    assert z.asnumpy().sum() == 0 and z.shape == (4, 3)
    zc = sparse.zeros("csr", (4, 3))
    assert zc.asnumpy().sum() == 0


def test_row_sparse_add():
    a = sparse.row_sparse_array((onp.ones((1, 2), "float32"), [1]), shape=(4, 2))
    b = sparse.row_sparse_array((2 * onp.ones((2, 2), "float32"), [1, 3]),
                                shape=(4, 2))
    c = sparse.add(a, b)
    assert c.stype == "row_sparse"
    expect = onp.zeros((4, 2), "float32")
    expect[1] = 3.0
    expect[3] = 2.0
    onp.testing.assert_array_equal(c.asnumpy(), expect)
    # sparse + dense falls back to dense
    dense = sparse.add(a, mx.np.ones((4, 2)))
    assert not isinstance(dense, sparse.BaseSparseNDArray)


def test_kvstore_row_sparse_pull():
    kv = mx.kv.create("device")
    w = onp.arange(12, dtype="float32").reshape(6, 2)
    kv.init("emb", mx.np.array(w))
    rsp = kv.row_sparse_pull("emb", row_ids=mx.np.array([4, 1, 1]))
    onp.testing.assert_array_equal(onp.asarray(rsp.indices._data), [1, 4])
    onp.testing.assert_array_equal(onp.asarray(rsp.data._data),
                                   w[[1, 4]])
    dense = rsp.tostype("default").asnumpy()
    assert (dense[[0, 2, 3, 5]] == 0).all()


def test_parameter_row_sparse_data():
    from mxnet_tpu.gluon import nn
    emb = nn.Embedding(10, 4, sparse_grad=True)
    emb.initialize()
    emb(mx.np.array([[1, 2]], dtype="int32"))
    rsp = emb.weight.row_sparse_data(mx.np.array([2, 7], dtype="int64"))
    assert rsp.stype == "row_sparse"
    onp.testing.assert_array_equal(onp.asarray(rsp.indices._data), [2, 7])
    onp.testing.assert_allclose(
        onp.asarray(rsp.data._data),
        emb.weight.data().asnumpy()[[2, 7]])


def test_sparse_embedding_training_smoke():
    """End-to-end: sparse-marked embedding trains (dense-grad fallback)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import nn, Trainer
    emb = nn.Embedding(20, 4, sparse_grad=True)
    emb.initialize()
    tr = Trainer(emb.collect_params(), "sgd", {"learning_rate": 0.5},
                 kvstore=None)
    ids = mx.np.array([[1, 3, 1]], dtype="int32")
    before = emb.weight.data().asnumpy().copy()
    with autograd.record():
        loss = (emb(ids) ** 2).sum()
    loss.backward()
    tr.step(1)
    after = emb.weight.data().asnumpy()
    assert not onp.allclose(before[[1, 3]], after[[1, 3]])
    onp.testing.assert_array_equal(before[[0, 2, 4]], after[[0, 2, 4]])


def test_sparse_module_binary_tail():
    """subtract/multiply/divide/empty/array (reference sparse.py
    :1282-1596; ops densify via the storage-fallback dispatch)."""
    import numpy as onp

    from mxnet_tpu.ndarray import sparse

    a = sparse.row_sparse_array(
        (mx.np.ones((2, 3)), mx.np.array([0, 2], dtype="int64")),
        shape=(4, 3))
    b = sparse.row_sparse_array(
        (mx.np.ones((1, 3)) * 2, mx.np.array([2], dtype="int64")),
        shape=(4, 3))
    onp.testing.assert_allclose(sparse.subtract(a, b).asnumpy()[2],
                                [-1, -1, -1])
    onp.testing.assert_allclose(sparse.multiply(a, b).asnumpy()[2],
                                [2, 2, 2])
    d = sparse.divide(b, sparse.row_sparse_array(
        (mx.np.ones((4, 3)) * 4, mx.np.arange(4, dtype="int64")),
        shape=(4, 3)))
    onp.testing.assert_allclose(d.asnumpy()[2], [0.5, 0.5, 0.5])
    e = sparse.empty("row_sparse", (3, 2))
    assert e.asnumpy().sum() == 0 and e.stype == "row_sparse"
    c = sparse.array(a)
    assert c is not a
    onp.testing.assert_allclose(c.asnumpy(), a.asnumpy())
    # dtype override works for both stypes
    assert sparse.array(a, dtype="float16").dtype == onp.float16
    csr = sparse.csr_matrix(onp.eye(3, dtype="float32"))
    assert sparse.array(csr, dtype="float16").dtype == onp.float16
    # dense input is rejected like the reference
    import pytest
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="tostype"):
        sparse.array(onp.ones((2, 2), "float32"))
    assert sparse.divide.__name__ == "divide"
