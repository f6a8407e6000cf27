"""mxnet_tpu — a TPU-native deep learning framework with Apache MXNet 2.0's
capabilities.

This is NOT a port of MXNet: the compute path is JAX/XLA (eager dispatch +
``hybridize()``-to-``jax.jit`` tracing), parallelism is ``jax.sharding`` meshes
with XLA collectives over ICI/DCN, and hot kernels are Pallas. The *API surface*
mirrors MXNet (reference: ``python/mxnet/__init__.py`` of apache/incubator-mxnet
2.0) so that Gluon user code carries over:

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, autograd, np, npx

Layer map vs the reference (see SURVEY.md):
  - MXNet ThreadedEngine (src/engine/)      -> PJRT async dispatch (jax arrays
    are futures; ``wait_to_read`` = block_until_ready)
  - NDArray/Chunk/Storage (src/ndarray/)    -> ndarray over jax.Array (+sharding)
  - deferred-compute trace -> CachedOp      -> trace -> jax.jit executable cache
  - KVStore (src/kvstore/)                  -> XLA collectives on a device mesh
  - src/operator/** kernels                 -> jnp/lax lowering + Pallas kernels
"""
import time as _time
_T_IMPORT = _time.perf_counter_ns()  # first, so the span holds every module

__version__ = "2.0.0a1"

# must run before anything touches the JAX backend (see _dist_init docstring)
from ._dist_init import ensure_distributed as _ensure_distributed
_ensure_distributed()

from . import base
from .base import MXNetError
from . import config
from . import telemetry
from . import fault
from . import trace
from . import insight
from . import blackbox
from . import goodput
from . import context
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, device, num_gpus, num_tpus
from . import engine
from . import pipeline
from . import _compile_cache
from . import numpy as np  # noqa: F401
from . import numpy_extension as npx  # noqa: F401
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import kvstore
from .kvstore import KVStore
from . import gluon
from . import parallel
from . import amp
from . import profiler
from . import util
from . import runtime
from . import library
from . import log
from . import registry
from . import test_utils
from . import symbol
from . import symbol as sym
from . import recordio
from . import io
from . import image
from . import contrib
from . import serialization
from . import resilience
from . import stream
from . import fleet
from . import serve
from . import servefleet
from . import autotune
from . import storage
from . import callback
from . import model
from . import operator
from . import name
from . import attribute
from . import error
from . import dlpack
from . import libinfo
from . import rtc
from . import executor
from . import visualization

viz = visualization
try:
    from . import onnx
except ImportError:  # protobuf missing: degrade the feature, not the package
    import types as _types

    class _OnnxUnavailable(_types.ModuleType):
        def __getattr__(self, name):
            raise ImportError(
                "mx.onnx requires the 'protobuf' package (pip install "
                "protobuf)")
    onnx = _OnnxUnavailable("mxnet_tpu.onnx")

kv = kvstore

if config.get("profiler.autostart"):
    profiler.set_state("run")

if config.get("compilation_cache_dir"):
    _compile_cache.configure()


def waitall():
    """Block until all pending device computation is done.

    Reference parity: ``mx.nd.waitall`` / ``Engine::WaitForAll``
    (include/mxnet/engine.h:255). On TPU, pending work is the set of
    undelivered jax.Arrays; the engine module tracks live arrays.
    """
    engine.wait_all()


# the package's import as the first span of mx.trace.startup()
import sys as _sys
trace.emit("import", _T_IMPORT // 1000,
           (_time.perf_counter_ns() - _T_IMPORT) // 1000, category="startup",
           modules=sum(1 for _m in _sys.modules
                       if _m == __name__ or _m.startswith(__name__ + ".")))
