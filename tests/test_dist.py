"""Multi-process distributed tests (reference layout: tests/nightly/
dist_sync_kvstore.py launched via tools/launch.py local mode, SURVEY §4
'distributed tests are real multi-process on one box') and the gradient-
compression bitwise oracle (reference: src/kvstore/gradient_compression.h).
"""
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_launch_two_process_dist_sync():
    """Spawn 2 real processes; workers assert exact reduced values."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # workers force cpu via MXTPU_DIST_DEVICE
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"), "-n", "2",
         sys.executable, os.path.join(REPO, "tests", "dist_worker.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "DIST_OK 0" in r.stdout and "DIST_OK 1" in r.stdout, r.stdout


def test_gradient_compression_2bit_oracle():
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    gc = GradientCompression(type="2bit", threshold=0.5)
    g = onp.array([0.3, -0.3, 0.7, -0.9, 0.0, 2.0], dtype="float32")
    q1 = onp.asarray(gc.quantize("k", g))
    # oracle: elementwise threshold quantization
    onp.testing.assert_array_equal(
        q1, onp.array([0.0, 0.0, 0.5, -0.5, 0.0, 0.5], dtype="float32"))
    res = onp.asarray(gc._residual["k"])
    onp.testing.assert_allclose(res, g - q1, rtol=1e-6)
    # error feedback: second quantize of zeros flushes accumulated residual
    q2 = onp.asarray(gc.quantize("k", onp.zeros_like(g)))
    onp.testing.assert_array_equal(
        q2, onp.array([0.0, 0.0, 0.0, 0.0, 0.0, 0.5], dtype="float32"))


def test_gradient_compression_1bit_oracle():
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    gc = GradientCompression(type="1bit", threshold=0.5)
    g = onp.array([0.1, -0.1, 3.0], dtype="float32")
    q = onp.asarray(gc.quantize("k", g))
    onp.testing.assert_array_equal(
        q, onp.array([0.5, -0.5, 0.5], dtype="float32"))
    onp.testing.assert_allclose(onp.asarray(gc._residual["k"]), g - q,
                                rtol=1e-6)


@pytest.mark.parametrize("mode,per_byte", [("2bit", 4), ("1bit", 8)])
def test_pack_unpack_codes_bitwise(mode, per_byte):
    """Wire format: n values fit in ceil(n/per_byte) bytes, exact roundtrip."""
    from mxnet_tpu.kvstore.gradient_compression import (
        GradientCompression, pack_codes, unpack_codes)
    t = 0.5
    gc = GradientCompression(type=mode, threshold=t)
    rng = onp.random.RandomState(0)
    g = rng.uniform(-2, 2, size=(37,)).astype("float32")  # non-multiple of 8
    q = onp.asarray(gc.quantize("k", g))
    packed, n = pack_codes(q, t, mode=mode)
    assert packed.dtype == onp.uint8
    assert len(packed) == -(-37 // per_byte)  # ceil: the compression claim
    back = unpack_codes(packed, n, t, mode=mode)
    onp.testing.assert_array_equal(back, q)


def test_compression_rejects_bad_params():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    with pytest.raises(MXNetError):
        GradientCompression(type="4bit")
    with pytest.raises(MXNetError):
        GradientCompression(threshold=0)


def test_local_kvstore_rejects_compression():
    from mxnet_tpu.base import MXNetError
    kv = mx.kv.create("device")
    with pytest.raises(MXNetError):
        kv.set_gradient_compression({"type": "2bit"})


def test_single_process_dist_kvstore_degenerates():
    """dist_sync with no peer env vars = world of 1; exact local behavior."""
    kv = mx.kv.create("dist_sync")
    assert kv.num_workers == 1 and kv.rank == 0
    kv.init("a", mx.np.zeros((3,)))
    kv.push("a", mx.np.full((3,), 2.0))
    out = mx.np.empty((3,))
    kv.pull("a", out=out)
    onp.testing.assert_array_equal(out.asnumpy(), onp.full((3,), 2.0))


def test_dist_async_watchdog_times_out():
    """A hung reconciling collective must raise with a schedule diagnostic
    (the documented dist_async divergence, kvstore/dist.py:121) instead of
    freezing. The hang is simulated: a real mismatched pull schedule
    blocks inside XLA exactly like this stand-in."""
    import time

    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu import np
    from mxnet_tpu.kvstore.dist import DistAsyncKVStore

    kv = DistAsyncKVStore.__new__(DistAsyncKVStore)
    kv._store = {"w": np.zeros((4,))}
    kv._nprocs = 2
    kv._rank = 0

    def hang(merged):
        time.sleep(60)
        return merged

    kv._allreduce = hang
    old = mx.config.get("kvstore.async_timeout")
    mx.config.set("kvstore.async_timeout", 0.5)
    # a deterministic schedule mismatch must fail fast, not be retried:
    # pin the elastic retry layer off for the raw-diagnostic assertion
    mx.config.set("kvstore.retry_max", 0)
    try:
        t0 = time.time()
        with pytest.raises(mx.base.MXNetError, match="pull schedule"):
            kv._reconcile("w")
        assert time.time() - t0 < 5
    finally:
        mx.config.set("kvstore.async_timeout", old)
        mx.config.reset("kvstore.retry_max")


@pytest.mark.slow
def test_multiprocess_overhead_table_two_procs():
    """Real 2-process collective probe (reference:
    tests/nightly/dist_sync_kvstore.py launch scheme)."""
    from mxnet_tpu.parallel.scaling import multiprocess_overhead_table

    rows = multiprocess_overhead_table(ns=(2,), timeout=240)
    assert len(rows) == 1
    row = rows[0]
    assert "error" not in row, row
    assert row["n"] == 2
    assert row["compute_ms"] > 0
    assert len(row["allreduce"]) == 2
    for r in row["allreduce"]:
        assert r["allreduce_ms"] > 0 and r["bytes"] in (1 << 20, 1 << 24)
