"""Multiprocess DataLoader workers (reference: gluon/data/dataloader.py
worker_loop + shared-memory transport, tests/python/unittest/
test_gluon_data.py test_multi_worker)."""
import glob
import os
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.data import DataLoader, ArrayDataset, SimpleDataset


def _slow_transform(x):
    # CPU-bound pure-python work: the GIL wall threads cannot cross
    s = 0.0
    for v in x[:64]:
        s += float(v) * 1.000001
    return x + onp.float32(s * 0)


class _PyTransformDataset:
    """Picklable dataset with a python transform."""

    def __init__(self, n=32, dim=128):
        rs = onp.random.RandomState(0)
        self.x = rs.rand(n, dim).astype(onp.float32)
        self.y = onp.arange(n).astype(onp.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return _slow_transform(self.x[i]), self.y[i]


@pytest.mark.parametrize("workers,threads", [(0, True), (2, True),
                                             (2, False)])
def test_dataloader_paths_agree(workers, threads):
    ds = _PyTransformDataset()
    dl = DataLoader(ds, batch_size=8, num_workers=workers,
                    thread_pool=threads)
    batches = list(dl)
    assert len(batches) == 4
    ref = _PyTransformDataset()
    for bi, (bx, by) in enumerate(batches):
        want_x = onp.stack([ref[bi * 8 + i][0] for i in range(8)])
        want_y = onp.stack([ref[bi * 8 + i][1] for i in range(8)])
        onp.testing.assert_allclose(bx.asnumpy(), want_x, rtol=1e-6)
        onp.testing.assert_allclose(by.asnumpy(), want_y, rtol=1e-6)


def test_mp_loader_multiple_epochs_reuse_pool():
    ds = _PyTransformDataset(n=16)
    dl = DataLoader(ds, batch_size=8, num_workers=2, thread_pool=False)
    e1 = [b[0].asnumpy() for b in dl]
    pool = dl._proc_pool
    e2 = [b[0].asnumpy() for b in dl]
    assert dl._proc_pool is pool  # persistent workers across epochs
    for a, b in zip(e1, e2):
        onp.testing.assert_allclose(a, b)


def test_mp_loader_shm_cleanup():
    # the segment ring holds pooled blocks while the loader is alive;
    # close() must unlink every one (pool-internal semaphores die with
    # the worker processes)
    before = set(glob.glob("/dev/shm/psm_*"))
    ds = _PyTransformDataset(n=16)
    dl = DataLoader(ds, batch_size=4, num_workers=2, thread_pool=False)
    _ = [b[0].asnumpy() for b in dl]
    dl.close()
    time.sleep(0.2)
    after = set(glob.glob("/dev/shm/psm_*"))
    assert not (after - before), after - before


def test_mp_loader_shm_ring_reuse():
    """Epoch 2+ serves most batches from pooled segments: bounded creates,
    growing reuse counter (segment churn is what slows process workers).  All
    leaves of a batch ride ONE packed segment, so the counters tick once
    per batch, not once per leaf."""
    from mxnet_tpu import telemetry
    telemetry.enable()
    try:
        ds = _PyTransformDataset(n=32)
        dl = DataLoader(ds, batch_size=8, num_workers=2, thread_pool=False)
        for _ in range(3):
            assert len(list(dl)) == 4
        agg = telemetry.counters(aggregate=True)
        created = agg.get("dataloader.shm_created_total", 0)
        reused = agg.get("dataloader.shm_reused_total", 0)
        # 3 epochs x 4 batches = 12 packed-segment transfers
        assert created + reused == 12
        assert reused > created, (created, reused)
        dl.close()
    finally:
        telemetry.disable()


def test_mp_loader_shm_ring_off_knob():
    """dataloader.shm_ring=False restores the one-shot create/unlink
    protocol (and still leaks nothing)."""
    before = set(glob.glob("/dev/shm/psm_*"))
    mx.config.set("dataloader.shm_ring", False)
    try:
        ds = _PyTransformDataset(n=16)
        dl = DataLoader(ds, batch_size=4, num_workers=2, thread_pool=False)
        batches = [b[0].asnumpy() for b in dl]
        assert len(batches) == 4
        dl.close()
    finally:
        mx.config.reset("dataloader.shm_ring")
    time.sleep(0.2)
    after = set(glob.glob("/dev/shm/psm_*"))
    assert not (after - before), after - before
