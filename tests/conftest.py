"""Test configuration.

Mirrors the reference's tests/python/unittest/conftest.py (seed control +
repro logging) plus the TPU-CI trick from SURVEY §4: tests run on a virtual
8-device CPU mesh (xla_force_host_platform_device_count) so sharding/
collective paths are exercised without TPU hardware.
"""
import os

# must be set before jax import. MXNET_TEST_DEVICE=tpu opts into running the
# suite on real hardware (the reference's test_operator_gpu.py pattern);
# default is the 8-virtual-device CPU mesh for determinism + sharding tests.
if os.environ.get("MXNET_TEST_DEVICE", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"

import numpy as onp  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402

if os.environ.get("MXNET_TEST_DEVICE", "cpu") == "cpu":
    # tests run on the virtual CPU mesh whatever JAX_PLATFORMS says: the
    # config update (pre-backend-init) outranks the environment
    jax.config.update("jax_platforms", "cpu")
# numpy-oracle tests need true-f32 matmuls (TPU MXU defaults to bf16 passes)
jax.config.update("jax_default_matmul_precision", "float32")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: nightly-bucket test (set MXNET_TEST_SLOW=1 to "
        "run; analog of the reference's tests/nightly split)")


def pytest_collection_modifyitems(config, items):
    if os.environ.get("MXNET_TEST_SLOW", "0") == "1":
        return
    skip = pytest.mark.skip(
        reason="nightly bucket: set MXNET_TEST_SLOW=1 to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed_everything(request):
    seed = int(os.environ.get("MXNET_TEST_SEED", 17))
    onp.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    yield


def retry(n):
    """Retry up to n times for stochastic/load-sensitive tests
    (reference: tests/python/unittest/common.py:218)."""
    import functools

    assert n > 0

    def deco(orig_test):
        @functools.wraps(orig_test)
        def wrapped(*args, **kwargs):
            for i in range(n):
                try:
                    return orig_test(*args, **kwargs)
                except AssertionError:
                    if i == n - 1:
                        raise
                    import mxnet_tpu as mx
                    mx.nd.waitall()
        return wrapped
    return deco
