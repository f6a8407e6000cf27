"""sha256 of a one-chip cell's train step lowered for the TPU at its real
size, on the CPU, traceback locations at their default: a Mosaic kernel's
serialized body — part of the step's compile-cache key — holds the path
and line of every frame of its Python call stack, and is compared here as
the chip would see it.  To show that a change leaves a cell's program (and
so its cache entry) alone, unpack parent and change IN TURN AT ONE PATH
(``git archive``; copy this file into the parent's tree) and compare what

    python chipbench/dev/same_program.py [cell ...]

prints in each (~1 min a cell; default: the three cells of PR 35)."""
import hashlib
import os
import sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "chipbench"),
          os.path.join(ROOT, "chipbench", "tests")):
    sys.path.insert(0, p)
os.chdir(ROOT)
import jax
from jax.experimental import topologies
from mxnet_tpu import runtime
from mxnet_tpu.autotune import kernels
import test_compile_v5e as t

runtime.on_tpu = lambda: True
kernels._device_family = lambda kind=None: "v5e"
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


class Stop(Exception):
    pass


def fake(self, *a, **k):
    raise Stop(hashlib.sha256(self.as_text().encode()).hexdigest())


jax.stages.Lowered.compile = fake
for cell in sys.argv[1:] or ["trinity-train-8k", "keye-train-8k",
                             "gpt2m-train-8k"]:
    try:
        t._train_compile(cell, topo)
    except Stop as e:
        print(cell, e, flush=True)
