"""mx.registry generic factory + mx.log + contrib facade tail.

Reference layout: python/mxnet/registry.py is exercised in the
reference through initializer/optimizer create-from-json paths;
contrib/io.py DataLoaderIter has doctest-style usage in its docstring.
"""
import logging

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import registry
from mxnet_tpu.base import MXNetError


class Fruit:
    def __init__(self, n=1):
        self.n = n


register = registry.get_register_func(Fruit, "fruit")
alias = registry.get_alias_func(Fruit, "fruit")
create = registry.get_create_func(Fruit, "fruit")


@alias("pomme", "manzana")
class Apple(Fruit):
    pass


register(Apple)


def test_register_and_create_by_name():
    a = create("apple", 3)
    assert isinstance(a, Apple) and a.n == 3
    assert isinstance(create("POMME"), Apple)  # case-insensitive
    assert isinstance(create("manzana"), Apple)


def test_create_config_forms():
    assert create(Apple(5)).n == 5                      # instance passthrough
    assert create({"fruit": "apple", "n": 7}).n == 7    # dict
    assert create('["apple", {"n": 9}]').n == 9         # json list
    assert create('{"fruit": "apple", "n": 2}').n == 2  # json dict
    assert isinstance(create(fruit="apple"), Apple)     # kwarg nickname


def test_create_errors():
    with pytest.raises(MXNetError):
        create("durian")
    with pytest.raises(MXNetError):
        create(Apple(), 1)  # instance + extra args
    with pytest.raises(MXNetError):
        register(int)  # not a subclass


def test_get_registry_copy():
    reg = registry.get_registry(Fruit)
    assert reg["apple"] is Apple
    reg["apple"] = int  # mutating the copy must not touch the registry
    assert registry.get_registry(Fruit)["apple"] is Apple


def test_reregister_warns():
    class Apple2(Fruit):
        pass
    with pytest.warns(UserWarning, match="overriding"):
        register(Apple2, "apple")
    register(Apple, "apple")  # restore (also warns)


def test_initializer_create_json_and_alias():
    init = mx.init.create('["uniform", {"scale": 0.5}]')
    assert isinstance(init, mx.init.Uniform)
    init2 = mx.init.create('{"initializer": "zero"}')
    arr = mx.np.ones((3,))
    init2("w", arr)


def test_log_get_logger(tmp_path):
    log_file = tmp_path / "t.log"
    logger = mx.log.get_logger("mxtpu-test", filename=str(log_file),
                               level=mx.log.INFO)
    logger.info("hello %d", 42)
    for h in logger.handlers:
        h.flush()
    text = log_file.read_text()
    assert "hello 42" in text and "I " in text
    # idempotent: second call does not duplicate handlers
    again = mx.log.get_logger("mxtpu-test")
    assert again is logger and len(logger.handlers) == 1
    with pytest.warns(DeprecationWarning):
        mx.log.getLogger("mxtpu-test")
    logging.getLogger("mxtpu-test").handlers.clear()


def test_contrib_namespace_aliases():
    assert mx.contrib.ndarray.foreach is mx.nd.contrib.foreach
    # symbolic contrib ops resolve through the shared CamelCase table
    s = mx.contrib.symbol.Variable("x")
    assert isinstance(s, mx.sym.Symbol)
    with pytest.raises(MXNetError):
        mx.contrib.tensorrt.get_use_fp16()


def test_contrib_onnx_forwarding():
    with pytest.warns(DeprecationWarning):
        try:
            mx.contrib.onnx.export_model(None, None)
        except Exception:
            pass  # only the forwarding + deprecation is under test


def test_contrib_dataloader_iter():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    x = onp.arange(50, dtype="float32").reshape(10, 5)
    y = onp.arange(10, dtype="float32")
    loader = DataLoader(ArrayDataset(x, y), batch_size=4)
    it = mx.contrib.io.DataLoaderIter(loader, dtype="float32")
    assert it.batch_size == 4
    batches = list(it)
    assert len(batches) == 3
    # last batch zero-padded from 2 -> 4 rows with pad recorded
    assert batches[-1].pad == 2
    assert batches[-1].data[0].shape == (4, 5)
    assert onp.allclose(onp.asarray(batches[-1].data[0])[2:], 0)
    # reset() rewinds
    it.reset()
    assert next(it).data[0].shape == (4, 5)
