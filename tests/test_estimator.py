"""gluon.contrib.estimator (reference: tests/python/unittest/
test_gluon_estimator.py + test_gluon_event_handler.py layout)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.contrib import estimator as est


def _toy_data(n=64, d=8, classes=3, bs=16, seed=0):
    rng = onp.random.RandomState(seed)
    x = rng.randn(n, d).astype("float32")
    w = rng.randn(d, classes).astype("float32")
    y = (x @ w).argmax(-1).astype("float32")
    return [(mx.np.array(x[i:i + bs]), mx.np.array(y[i:i + bs]))
            for i in range(0, n, bs)]


def _make_estimator(lr=0.1, **kwargs):
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": lr})
    return est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         trainer=trainer, **kwargs)


def test_fit_learns_and_updates_metrics():
    e = _make_estimator()
    data = _toy_data()
    e.fit(data, epochs=20)
    names = dict(nv for m in e.train_metrics for nv in m.get_name_value())
    assert names["accuracy"] > 0.9, names
    assert 0 < names["train_loss"] < 1.0


def test_gradient_update_handler_is_the_stepper():
    """Removing GradientUpdateHandler must freeze the weights."""
    e = _make_estimator()
    data = _toy_data()
    e.net(data[0][0])  # materialize deferred shapes
    w0 = e.net.collect_params()["0.weight"].data().asnumpy().copy()

    class NoStep(est.GradientUpdateHandler):
        def batch_end(self, estimator, *args, **kwargs):
            pass  # swallow the step

    e.fit(data, epochs=2, event_handlers=[NoStep()])
    w1 = e.net.collect_params()["0.weight"].data().asnumpy()
    assert onp.allclose(w0, w1), "weights moved without an update handler"
    # while the default handler does move them
    e2 = _make_estimator()
    e2.net(data[0][0])  # materialize deferred shapes
    v0 = e2.net.collect_params()["0.weight"].data().asnumpy().copy()
    e2.fit(data, epochs=1)
    assert not onp.allclose(
        v0, e2.net.collect_params()["0.weight"].data().asnumpy())


def test_custom_batch_processor():
    calls = []

    class Recorder(est.BatchProcessor):
        def fit_batch(self, estimator, batch, batch_axis=0):
            calls.append("fit")
            return super().fit_batch(estimator, batch, batch_axis)

    e = _make_estimator(batch_processor=Recorder())
    data = _toy_data(n=32)
    e.fit(data, epochs=1)
    assert len(calls) == len(data)


def test_checkpoint_handler(tmp_path):
    e = _make_estimator()
    data = _toy_data(n=32)
    ckpt = est.CheckpointHandler(str(tmp_path), model_prefix="toy",
                                 epoch_period=1, max_checkpoints=2)
    e.fit(data, epochs=3, event_handlers=[ckpt])
    files = sorted(os.listdir(tmp_path))
    assert any("epoch3" in f for f in files)
    # max_checkpoints evicts the oldest
    assert not any("epoch1" in f for f in files)
    # reload round-trip
    net2 = nn.Sequential()
    net2.add(nn.Dense(16, activation="relu"), nn.Dense(3))
    saved = [f for f in files if f.endswith((".params", ".params.npz"))][-1]
    net2.load_parameters(str(tmp_path / saved))
    x = data[0][0]
    onp.testing.assert_allclose(net2(x).asnumpy(), e.net(x).asnumpy(),
                                atol=1e-6)


def test_early_stopping_handler():
    loss_metric = mx.gluon.metric.Loss("train_loss")

    class Plateau(est.EpochEnd):
        """Force the monitored metric flat so patience triggers."""

        def epoch_end(self, estimator, *args, **kwargs):
            loss_metric.reset()
            loss_metric.update(None, [mx.np.array([1.0])])

    e = _make_estimator(lr=0.0)
    stopper = est.EarlyStoppingHandler(monitor=loss_metric, patience=2,
                                       mode="min")
    e.fit(_toy_data(n=32), epochs=50,
          event_handlers=[Plateau(), stopper])
    assert stopper.stop_training
    assert stopper.wait >= 2


def test_validation_handler_runs_eval():
    seen = []
    e = _make_estimator()
    val = _toy_data(n=16, seed=1)
    vh = est.ValidationHandler(val, eval_fn=lambda d: seen.append(len(d)),
                               epoch_period=1)
    e.fit(_toy_data(n=32), epochs=2, event_handlers=[vh])
    assert seen == [1, 1]


def test_evaluate_reports_accuracy():
    e = _make_estimator()
    data = _toy_data()
    e.fit(data, epochs=20)
    metrics = e.evaluate(data)
    acc = dict(nv for m in metrics for nv in m.get_name_value())["accuracy"]
    assert acc > 0.9


def test_priority_ordering():
    order = []

    class A(est.BatchEnd):
        priority = 10

        def batch_end(self, estimator, *args, **kwargs):
            order.append("late")

    class B(est.BatchEnd):
        priority = -5000

        def batch_end(self, estimator, *args, **kwargs):
            order.append("early")

    e = _make_estimator()
    e.fit(_toy_data(n=16), epochs=1, event_handlers=[A(), B()])
    assert order[0] == "early" and order[1] == "late"


def test_val_metrics_and_loss_reported():
    """val_metrics is honored and evaluate() feeds LossMetric; the
    training metrics are left untouched."""
    vm = [mx.gluon.metric.Accuracy(), mx.gluon.metric.Loss("val_loss")]
    e = _make_estimator(val_metrics=vm)
    data = _toy_data()
    e.fit(data, epochs=15)
    train_vals = dict(nv for m in e.train_metrics
                      for nv in m.get_name_value())
    out = e.evaluate(data)
    got = dict(nv for m in out for nv in m.get_name_value())
    assert got["accuracy"] > 0.8 and got["val_loss"] > 0
    # train metrics unchanged by evaluate
    after = dict(nv for m in e.train_metrics for nv in m.get_name_value())
    assert after == train_vals


def test_scalar_loss_step_normalization():
    """A mean-reduced (scalar) loss must still normalize by the DATA
    batch size, not by loss.shape."""
    class ScalarLossProcessor(est.BatchProcessor):
        def fit_batch(self, estimator, batch, batch_axis=0):
            from mxnet_tpu import autograd
            data, label = batch[0], batch[1]
            with autograd.record():
                pred = estimator.net(data)
                loss = estimator.loss(pred, label).mean()  # scalar
            loss.backward()
            return [data], [label], [pred], [loss]

    seen = []

    class SpyStep(est.GradientUpdateHandler):
        def batch_end(self, estimator, *args, **kwargs):
            super().batch_end(estimator, *args, **kwargs)
            seen.append(kwargs.get("num_samples"))

    e = _make_estimator(batch_processor=ScalarLossProcessor())
    e.fit(_toy_data(n=32, bs=16), epochs=1, event_handlers=[SpyStep()])
    assert seen == [16, 16]
