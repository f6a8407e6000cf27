"""mx.util — numpy-mode scopes and misc helpers.

Reference parity: python/mxnet/util.py (np_shape/np_array scopes, use_np
decorators, getenv wrappers). The new framework always has numpy semantics,
so the scopes are identity context managers kept for API compatibility.
"""
from __future__ import annotations

import contextlib
import functools

from .base import get_env  # noqa: F401


def is_np_shape():
    return True


def is_np_array():
    return True


def is_np_default_dtype():
    return False


@contextlib.contextmanager
def np_shape(active=True):
    yield active


@contextlib.contextmanager
def np_array(active=True):
    yield active


def use_np_shape(func):
    return func


def use_np_array(func):
    return func


def use_np(func):
    return func


def use_np_default_dtype(func):
    return func


def set_np(shape=True, array=True, dtype=False):
    pass


def reset_np():
    pass


def wrap_np_unary_func(func):
    return func


def wrap_np_binary_func(func):
    return func


def default_array(source_array, ctx=None, dtype=None):
    from .numpy import array
    return array(source_array, dtype=dtype, ctx=ctx)


def get_gpu_count():
    from .context import num_gpus
    return num_gpus()


def get_gpu_memory(gpu_dev_id=0):
    import jax
    try:
        stats = jax.devices()[gpu_dev_id].memory_stats()
        return stats.get("bytes_in_use", 0), stats.get("bytes_limit", 0)
    except Exception:
        return (0, 0)


def int64_enabled():
    """Whether 64-bit tensor sizes/dtypes are active.

    Analog of the reference's MXNET_USE_INT64_TENSOR_SIZE build flag
    (docs env_var.md; tests/nightly/test_large_array.py relies on it).
    Here it maps to JAX's x64 mode.
    """
    import jax
    return bool(jax.config.jax_enable_x64)


@contextlib.contextmanager
def int64_tensor_size(active=True):
    """Scope enabling true int64 dtypes/indices (jax x64 mode).

    Arrays created inside the scope keep 64-bit dtypes; outside it JAX's
    default 32-bit truncation applies (a startup-time choice in the
    reference, a scope here).
    """
    from jax import enable_x64
    with enable_x64(active):
        yield


def getenv(name):
    """Read an MXNET_* environment variable (reference util.py getenv
    over MXGetEnv); returns None when unset. Alias of base.get_env."""
    return get_env(name)


def setenv(name, value):
    """Set an MXNET_* environment variable for THIS process (reference
    util.py setenv over MXSetEnv). Config knobs read env at use time via
    mx.config, so changes take effect on the next read."""
    import os
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = str(value)


def set_np_shape(active=True):
    """1.x toggle for numpy shape semantics (reference util.py:set_np_shape).
    This build is numpy-semantics-only; disabling raises like MXNet 2.0
    does once npx.set_np has been called."""
    if not active:
        from .base import MXNetError
        raise MXNetError(
            "legacy (non-numpy) shape semantics are not supported; "
            "this framework is numpy-first (reference: deprecation in 2.0)")
    return True


def np_default_dtype():
    """Default float dtype for mx.np creation funcs (reference
    util.py np_default_dtype): float32 here (TPU-native), float64 when
    is_np_default_dtype() — kept False permanently; use explicit
    dtype= or util.int64_tensor_size for 64-bit work."""
    return "float32"


def set_np_default_dtype(is_np_default_dtype=False):  # noqa: ARG001
    """1.x toggle for float64 creation defaults (reference
    util.py set_np_default_dtype). This build is float32-default
    permanently (TPU-native); requesting float64 defaults raises, the
    matching False state is a no-op."""
    if is_np_default_dtype:
        from .base import MXNetError
        raise MXNetError(
            "float64 creation defaults are not supported on the TPU "
            "path; pass dtype='float64' explicitly where needed "
            "(runs under a scoped x64 mode)")
    return False


def np_ufunc_legal_option(key, value):
    """Whether a ufunc kwarg is supported (reference util.py:550 — the
    dispatch protocol uses it to reject unsupported options)."""
    import numpy as _onp
    if key == "where":
        return True
    if key == "casting":
        return value in ("no", "equiv", "safe", "same_kind", "unsafe")
    if key == "order":
        return isinstance(value, str)
    if key == "dtype":
        return value in (_onp.int8, _onp.uint8, _onp.int32, _onp.int64,
                         _onp.float16, _onp.float32, _onp.float64,
                         "int8", "uint8", "int32", "int64",
                         "float16", "float32", "float64")
    if key == "subok":
        return isinstance(value, bool)
    return False


def set_module(module):
    """Decorator overriding __module__ for doc rendering (reference
    util.py set_module)."""
    def decorator(obj):
        if module is not None:
            obj.__module__ = module
        return obj
    return decorator


def set_flush_denorms(value=True):  # noqa: ARG001 — parity signature
    """Reference util.py set_flush_denorms sets CPU FTZ via SSE; XLA/TPU
    flushes denormals by hardware design, so this is a documented no-op
    returning False (the reference also returns False on unsupported
    hardware)."""
    return False
