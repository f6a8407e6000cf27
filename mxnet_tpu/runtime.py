"""mx.runtime — feature detection.

Reference parity: python/mxnet/runtime.py over src/libinfo.cc:37-90 (compiled
feature flags like CUDA/CUDNN/MKLDNN/DIST_KVSTORE surfaced at runtime). Here
the features describe the JAX/XLA backend actually present in the process.
"""
from __future__ import annotations

import jax


def on_tpu():
    """Is the default backend a TPU.  The one spelling of the question:
    kernel dispatch, block tables and feature flags all ask it here."""
    return jax.default_backend() == "tpu"


def pallas_interpret():
    """``interpret=`` for a ``pallas_call``: the Pallas interpreter
    everywhere but on a TPU, where kernels go to Mosaic — never True
    there."""
    return not on_tpu()


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self._enabled = enabled

    @property
    def enabled(self):
        return self._enabled

    def __repr__(self):
        return f"[{'✔' if self._enabled else '✖'} {self.name}]"


def feature_list():
    devs = jax.devices()
    accel = bool(devs) and devs[0].platform != "cpu"
    feats = {
        "TPU": on_tpu(),
        "XLA": True,
        "PALLAS": accel,
        "CPU": True,
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "MKLDNN": False,
        "OPENMP": False,
        "DIST_KVSTORE": True,        # mesh collectives over ICI/DCN
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": False,
        "F16C": True,
        "BF16": True,
    }
    return [Feature(k, v) for k, v in feats.items()]


class Features(dict):
    instance = None

    def __init__(self):
        super().__init__([(f.name, f) for f in feature_list()])

    def is_enabled(self, name):
        return self[name.upper()].enabled


def libinfo_features():
    return feature_list()


def compiled_with_gcc_cxx11_abi():
    """Whether the native helper libraries use the GCC cxx11 ABI
    (reference runtime.py over MXLibInfoCompiledWithCXX11ABI). The
    on-demand g++ builds here (native/*.cc via storage/io loaders) use
    the toolchain default, which is the cxx11 ABI on every supported
    image; returns False only if no native library is loadable at all."""
    import os
    import shutil

    from . import native
    # consult already-built libs first; otherwise answer from toolchain +
    # source presence WITHOUT triggering an on-demand g++ build (an
    # introspection query must not shell out for seconds)
    if any(lib is not None for lib in native._libs.values()):
        return True
    return (shutil.which("g++") is not None
            and os.path.isdir(native._SRC_DIR))
