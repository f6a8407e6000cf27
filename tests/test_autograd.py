"""Autograd tape (reference: tests/python/unittest/test_autograd.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, np
from mxnet_tpu.test_utils import assert_almost_equal


def test_simple_backward():
    x = np.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    y.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy())


def test_chain():
    x = np.array([0.5, 1.0])
    x.attach_grad()
    with autograd.record():
        y = np.exp(x) * x
        z = y.sum()
    z.backward()
    expected = onp.exp(x.asnumpy()) * (1 + x.asnumpy())
    assert_almost_equal(x.grad, expected, rtol=1e-5)


def test_multi_input():
    a = np.array([1.0, 2.0])
    b = np.array([3.0, 4.0])
    a.attach_grad()
    b.attach_grad()
    with autograd.record():
        c = (a * b + a).sum()
    c.backward()
    assert_almost_equal(a.grad, b.asnumpy() + 1)
    assert_almost_equal(b.grad, a.asnumpy())


def test_no_grad_outside_record():
    x = np.array([1.0])
    x.attach_grad()
    y = x * 2  # not recorded
    assert y._entry is None


def test_head_grad():
    x = np.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
    y.backward(np.array([1.0, 10.0]))
    assert_almost_equal(x.grad, onp.array([3.0, 30.0]))


def test_grad_req_add():
    x = np.array([1.0])
    x.attach_grad("add")
    for _ in range(3):
        with autograd.record():
            y = x * 2
        y.backward()
    assert float(x.grad) == 6.0


def test_grad_function():
    x = np.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * x * x
    g = autograd.grad(y, x)
    assert_almost_equal(g, onp.array([12.0]))


def test_detach():
    x = np.array([1.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        z = y.detach() * x
    z.backward()
    assert_almost_equal(x.grad, onp.array([2.0]))  # only through 2nd factor


def test_pause():
    x = np.array([1.0])
    x.attach_grad()
    with autograd.record():
        with autograd.pause():
            y = x * 2
        z = x * 3
    assert y._entry is None
    z.backward()
    assert float(x.grad) == 3.0


def test_training_modes():
    assert not autograd.is_training()
    with autograd.record():
        assert autograd.is_training()
        assert autograd.is_recording()
        with autograd.predict_mode():
            assert not autograd.is_training()
            assert autograd.is_recording()
    with autograd.train_mode():
        assert autograd.is_training()
        assert not autograd.is_recording()


def test_retain_graph():
    x = np.array([1.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    y.backward(retain_graph=True)
    g1 = float(x.grad)
    y.backward()
    assert float(x.grad) == g1  # write req overwrites


def test_double_backward_error_without_retain():
    x = np.array([1.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    y.backward()
    with pytest.raises(mx.MXNetError):
        y.backward()


def test_mark_variables():
    x = np.array([1.0, 1.0])
    g = np.zeros(2)
    autograd.mark_variables([x], [g])
    with autograd.record():
        y = (x * 4).sum()
    y.backward()
    assert_almost_equal(x.grad, onp.array([4.0, 4.0]))


def test_custom_function():
    class Square(autograd.Function):
        def forward(self, x):
            self.save_for_backward(x)
            return x * x

        def backward(self, dy):
            (x,) = self.saved_tensors
            return dy * 2 * x

    x = np.array([3.0])
    x.attach_grad()
    sq = Square()
    with autograd.record():
        y = sq(x)
    y.backward()
    assert_almost_equal(x.grad, onp.array([6.0]))


def test_through_reductions_and_reshape():
    x = np.arange(6, dtype="float32").reshape(2, 3)
    x.attach_grad()
    with autograd.record():
        y = (x.reshape(3, 2).T * 2).mean()
    y.backward()
    assert_almost_equal(x.grad, onp.full((2, 3), 2.0 / 6.0))


def test_nondiff_path_int():
    x = np.array([1.0, 5.0, 3.0])
    x.attach_grad()
    with autograd.record():
        idx = np.argmax(x)  # int output
        y = (x * 2).sum()
    y.backward()
    assert_almost_equal(x.grad, onp.full(3, 2.0))
    assert int(idx) == 1


def test_finite_difference_utility():
    from mxnet_tpu.test_utils import check_numeric_gradient

    def f(inputs):
        (x,) = inputs
        return (np.tanh(x) * x).sum()

    x = np.array([0.3, -0.7, 1.2])
    check_numeric_gradient(f, [x])


# ---------------------------------------------------------------------------
# higher-order (create_graph) — reference layout:
# python/mxnet/autograd.py:303 grad(create_graph=True) over
# src/imperative/imperative.cc:438; tests/python/unittest/test_higher_order_grad.py
# ---------------------------------------------------------------------------

def test_create_graph_sin_chain():
    # sin -> cos -> -sin -> -cos through repeated create_graph
    xs = onp.array([0.3, 1.1, -0.7], onp.float32)
    x = np.array(xs)
    x.attach_grad()
    with autograd.record():
        y = np.sin(x)
        g1 = autograd.grad(y, x, create_graph=True)
        g2 = autograd.grad(g1, x, create_graph=True)
        g3 = autograd.grad(g2, x)
    assert_almost_equal(g1, onp.cos(xs), rtol=1e-5)
    assert_almost_equal(g2, -onp.sin(xs), rtol=1e-5)
    assert_almost_equal(g3, -onp.cos(xs), rtol=1e-5)


def test_create_graph_then_backward():
    # reference pattern: grad(create_graph=True) then .backward() accumulates
    # the second-order gradient into x.grad
    x = np.array([2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x ** 3).sum()
        g = autograd.grad(y, x, create_graph=True)
        gs = g.sum()
    gs.backward()
    assert_almost_equal(x.grad, onp.array([12.0, 18.0]), rtol=1e-5)


def test_create_graph_mixed_partial():
    # f = x*y^2: d/dy(df/dx) = 2y
    x = np.array([2.0])
    y = np.array([3.0])
    x.attach_grad()
    y.attach_grad()
    with autograd.record():
        f = x * y * y
        gx = autograd.grad(f, x, create_graph=True)
        gxy = autograd.grad(gx, y)
    assert_almost_equal(gxy, onp.array([6.0]), rtol=1e-5)


def test_create_graph_gradient_penalty():
    # WGAN-GP style: penalty on the gradient norm, differentiated wrt weights
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, activation='tanh')
    net.initialize()
    x = np.ones((2, 3)) * 0.5
    x.attach_grad()
    with autograd.record():
        out = net(x).sum()
        g = autograd.grad(out, x, create_graph=True)
        penalty = (g * g).sum()
    penalty.backward()
    w = list(net.collect_params().values())[0]
    assert onp.isfinite(w.grad().asnumpy()).all()
    assert onp.abs(w.grad().asnumpy()).sum() > 0


def test_create_graph_through_hybridized():
    # the CachedOp tape node re-linearizes through the jitted forward
    from mxnet_tpu.gluon import nn
    net = nn.Dense(3, activation='tanh')
    net.initialize()
    net.hybridize()
    x = np.array([[0.1, 0.2], [0.3, -0.4]])
    x.attach_grad()
    with autograd.record():
        y = net(x).sum()
        g = autograd.grad(y, x, create_graph=True)
        gn = (g * g).sum()
    gn.backward()
    # oracle: same computation fully eager (non-hybridized fresh net with
    # identical params)
    net2 = nn.Dense(3, activation='tanh')
    net2.initialize()
    for (n1, p1), (n2, p2) in zip(net.collect_params().items(),
                                  net2.collect_params().items()):
        p2.set_data(p1.data())
    x2 = np.array([[0.1, 0.2], [0.3, -0.4]])
    x2.attach_grad()
    with autograd.record():
        y2 = net2(x2).sum()
        g2 = autograd.grad(y2, x2, create_graph=True)
        gn2 = (g2 * g2).sum()
    gn2.backward()
    assert_almost_equal(x.grad, x2.grad.asnumpy(), rtol=1e-4, atol=1e-5)


def test_create_graph_function_fails_fast():
    # custom Function has only a user backward — no pure fn to re-linearize;
    # must raise, not silently return un-taped grads
    class Double(autograd.Function):
        def forward(self, x):
            return x * 2
        def backward(self, dy):
            return dy * 2

    f = Double()
    x = np.array([1.0])
    x.attach_grad()
    with pytest.raises(mx.base.MXNetError, match="create_graph"):
        with autograd.record():
            y = f(x)
            autograd.grad(y, x, create_graph=True)
