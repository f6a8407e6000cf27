"""mx.pipeline — async host<->device overlap engine.

Covers the acceptance contract of the overlap engine: prefetch ordering
and bounded depth, clean shutdown, stall recovery under fault injection,
a sync-FREE step loop proven by the transfer-guard (zero host syncs in
three full fwd/bwd/step iterations), deferred metric/grad-norm windows,
sharded skip-reput, mid-epoch resume with buffered-but-unserved batches,
shm segment-ring reuse, and the persistent compilation-cache knob.
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, pipeline, telemetry
from mxnet_tpu.gluon import metric, nn
from mxnet_tpu.gluon.data import DataLoader
from mxnet_tpu.gluon.data.dataset import ArrayDataset
from mxnet_tpu.gluon.data.sampler import RandomSampler


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    mx.fault.clear()
    mx.fault.reset_stats()
    mx.config.reset()
    telemetry.disable()


# ---------------------------------------------------------------------------
# DevicePrefetcher basics
# ---------------------------------------------------------------------------

def _arrays(n, shape=(4, 8)):
    rs = onp.random.RandomState(0)
    return [rs.rand(*shape).astype("float32") for _ in range(n)]


def test_prefetcher_preserves_order_and_values():
    src = _arrays(6)
    out = list(pipeline.DevicePrefetcher(iter(src)))
    assert len(out) == 6
    for a, b in zip(out, src):
        onp.testing.assert_array_equal(onp.asarray(a), b)


def test_prefetcher_preserves_leaf_type():
    """Raw numpy/jax leaves come back as device-placed jax.Arrays; mx
    ndarray leaves come back as mx ndarrays — no silent type change."""
    import jax
    raw_out = next(iter(pipeline.DevicePrefetcher(iter(_arrays(1)))))
    assert isinstance(raw_out, jax.Array)
    nd_src = [mx.np.array(a) for a in _arrays(2)]
    for got, want in zip(pipeline.DevicePrefetcher(iter(nd_src)), nd_src):
        assert isinstance(got, mx.np.ndarray)
        onp.testing.assert_array_equal(got.asnumpy(), want.asnumpy())


def test_prefetcher_tuple_batches_and_passthrough_payloads():
    def gen():
        for i in range(3):
            yield (onp.full((2, 2), i, dtype="float32"), {"meta": i})
    out = list(pipeline.DevicePrefetcher(gen()))
    for i, (arr, meta) in enumerate(out):
        onp.testing.assert_array_equal(onp.asarray(arr),
                                       onp.full((2, 2), i))
        assert meta == {"meta": i}  # non-array payloads ride along


def test_prefetcher_bounded_depth():
    """The background thread never runs more than depth batches ahead of
    the consumer — the window is the memory bound."""
    pulled = []

    def gen():
        for i in range(50):
            pulled.append(i)
            yield onp.zeros((2,), dtype="float32")

    pf = pipeline.DevicePrefetcher(iter(gen()), depth=2)
    it = iter(pf)
    consumed = 0
    for _ in range(3):
        next(it)
        consumed += 1
        time.sleep(0.05)  # give the thread every chance to overrun
        # +1 for the batch being put right now, +1 queue slack
        assert len(pulled) <= consumed + 2 + 2, (len(pulled), consumed)
    pf.close()


def test_prefetcher_clean_shutdown_releases_source():
    """close() mid-stream unblocks the producer thread and runs the
    source generator's cleanup (shm bookkeeping relies on this)."""
    closed = threading.Event()

    def gen():
        try:
            for _ in range(1000):
                yield onp.zeros((2,), dtype="float32")
        finally:
            closed.set()

    pf = pipeline.DevicePrefetcher(gen(), depth=2)
    next(iter(pf))
    pf.close()
    assert closed.wait(3.0), "source generator finalizer never ran"


def test_prefetcher_propagates_source_exception():
    def gen():
        yield onp.zeros((2,), dtype="float32")
        raise RuntimeError("boom in producer")

    pf = pipeline.DevicePrefetcher(gen())
    it = iter(pf)
    next(it)
    with pytest.raises(RuntimeError, match="boom in producer"):
        next(it)


def test_prefetcher_stall_recovery_preserves_order():
    """A wedged prefetch thread (fault point pipeline.prefetch_stall) is
    detected by the stall deadline and replaced; the batch sequence the
    consumer sees is unchanged and the recovery is accounted."""
    telemetry.enable()
    mx.fault.configure("pipeline.prefetch_stall:at=2,times=1")
    src = _arrays(5)
    pf = pipeline.DevicePrefetcher(iter(src), depth=2, stall_timeout=0.4)
    out = [onp.asarray(b) for b in pf]
    assert len(out) == 5
    for a, b in zip(out, src):
        onp.testing.assert_array_equal(a, b)
    assert mx.fault.stats().get("pipeline.stall_recovered", 0) >= 1
    snap = telemetry.counters(aggregate=True)
    assert snap.get("pipeline.stall_recovered_total", 0) >= 1


def test_prefetcher_slow_producer_loses_no_batches():
    """A producer slower than stall_timeout (cold start, heavy
    augmentation, network FS) triggers stall recovery, but its in-flight
    batch is handed over under the source lock — not dropped — so the
    consumer still sees every batch in order."""
    src = _arrays(5)

    def gen():
        for i, a in enumerate(src):
            if i == 2:
                time.sleep(0.9)  # > stall_timeout: slow, not wedged
            yield a

    pf = pipeline.DevicePrefetcher(gen(), depth=2, stall_timeout=0.3)
    out = [onp.asarray(b) for b in pf]
    assert len(out) == 5
    for a, b in zip(out, src):
        onp.testing.assert_array_equal(a, b)
    # recovery DID fire (the deadline passed) and yet nothing was lost
    assert mx.fault.stats().get("pipeline.stall_recovered", 0) >= 1


def test_prefetch_to_device_disabled_is_identity():
    """target=None/False must return the source object untouched — the
    off switch costs nothing, not even a wrapper frame."""
    it = iter(_arrays(2))
    assert pipeline.prefetch_to_device(it, target=None) is it
    assert pipeline.prefetch_to_device(it, target=False) is it


def test_maybe_device_put_skips_already_placed():
    import jax
    dev = jax.devices()[0]
    raw = jax.device_put(onp.zeros((2, 2), dtype="float32"), dev)
    out, moved = pipeline.maybe_device_put(raw, dev)
    assert out is raw and not moved
    out2, moved2 = pipeline.maybe_device_put(
        onp.zeros((2, 2), dtype="float32"), dev)
    assert moved2 and out2.devices() == {dev}


# ---------------------------------------------------------------------------
# sync guard + sync-free step loop
# ---------------------------------------------------------------------------

def test_sync_guard_counts_host_syncs():
    x = mx.np.array(onp.ones((2, 2), dtype="float32"))
    with pipeline.sync_guard() as g:
        x.asnumpy()
        x.sum().item()
    assert g.count >= 2
    assert "ndarray.asnumpy" in g.sites
    assert "ndarray.item" in g.sites
    # guard is scoped: outside the with-block nothing counts
    before = g.count
    x.asnumpy()
    assert g.count == before


def test_sync_guard_ignores_other_threads():
    """Transfers on a background (prefetch) thread must not count against
    a guarded main-thread step loop."""
    x = mx.np.array(onp.ones((4,), dtype="float32"))
    done = threading.Event()

    def worker():
        x.asnumpy()
        done.set()

    with pipeline.sync_guard() as g:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.is_set()
    assert g.count == 0, g.sites


def test_trainer_step_loop_is_sync_free():
    """Three full fwd/bwd/step iterations with telemetry ON perform ZERO
    host syncs — grad-norm accounting is deferred to the drain."""
    telemetry.enable()
    net = nn.Dense(4, in_units=8)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.L2Loss()
    x = mx.np.array(onp.random.RandomState(0).rand(16, 8).astype("float32"))
    y = mx.np.array(onp.random.RandomState(1).rand(16, 4).astype("float32"))
    with pipeline.sync_guard() as g:
        for _ in range(3):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(16)
    assert g.count == 0, f"hot path synced: {g.sites}"
    trainer.drain_telemetry()
    snap = telemetry.snapshot()
    assert snap["histograms"]["trainer.grad_norm"]["count"] == 3


def test_deferred_window_bounds_and_eviction():
    telemetry.enable()
    seen = []
    w = pipeline.DeferredWindow(window=3)
    for i in range(7):
        w.push(float(i), seen.append)
    assert len(w) == 3
    assert seen == [0.0, 1.0, 2.0, 3.0]  # oldest evicted in order
    w.drain()
    assert seen == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert len(w) == 0
    snap = telemetry.counters(aggregate=True)
    assert snap.get("pipeline.deferred_evictions_total", 0) >= 4
    w2 = pipeline.DeferredWindow(window=3)
    w2.push(1.0, seen.append)
    w2.clear()
    w2.drain()
    assert seen[-1] == 6.0  # clear() drops without fetching


# ---------------------------------------------------------------------------
# deferred metrics
# ---------------------------------------------------------------------------

def test_deferred_metrics_match_eager():
    rs = onp.random.RandomState(2)
    labels = rs.randint(0, 4, size=(32,))
    preds = rs.rand(32, 4).astype("float32")
    reg_lab = rs.rand(32, 4).astype("float32")
    cases = [
        (metric.Accuracy(), metric.Accuracy(), labels, preds),
        (metric.MSE(), metric.MSE(), reg_lab, preds),
        (metric.MAE(), metric.MAE(), reg_lab, preds),
        (metric.RMSE(), metric.RMSE(), reg_lab, preds),
    ]
    for eager, base, lab, pred in cases:
        deferred = base.defer()
        eager.update(mx.np.array(lab), mx.np.array(pred))
        with pipeline.sync_guard() as g:
            deferred.update(mx.np.array(lab), mx.np.array(pred))
        assert g.count == 0, (type(base).__name__, g.sites)
        (_, v1), (_, v2) = eager.get(), deferred.get()
        assert v1 == pytest.approx(v2, rel=1e-5), type(base).__name__


def test_deferred_loss_metric_and_reset():
    preds = onp.random.RandomState(3).rand(16, 4).astype("float32")
    eager, base = metric.Loss(), metric.Loss()
    deferred = base.defer()
    eager.update(None, mx.np.array(preds))
    with pipeline.sync_guard() as g:
        deferred.update(None, mx.np.array(preds))
    assert g.count == 0, g.sites
    (_, v1), (_, v2) = eager.get(), deferred.get()
    assert v1 == pytest.approx(v2, rel=1e-5)
    # reset drops buffered batches without a host fetch
    deferred.update(None, mx.np.array(preds))
    with pipeline.sync_guard() as g:
        deferred.reset()
    assert g.count == 0
    assert deferred.num_inst == 0


def test_deferred_metric_without_device_stats_falls_back():
    base = metric.F1()
    deferred = base.defer()
    deferred.update(mx.np.array(onp.array([1, 0, 1, 1])),
                    mx.np.array(onp.array([1, 0, 0, 1])))
    name, val = deferred.get()
    ref = metric.F1()
    ref.update(mx.np.array(onp.array([1, 0, 1, 1])),
               mx.np.array(onp.array([1, 0, 0, 1])))
    assert val == pytest.approx(ref.get()[1])


# ---------------------------------------------------------------------------
# DataLoader integration: device prefetch + resume + shm ring
# ---------------------------------------------------------------------------

def test_dataloader_prefetch_to_device_equivalence():
    x = onp.arange(80, dtype="float32").reshape(20, 4)
    ds = ArrayDataset(x)
    plain = [b.asnumpy() for b in DataLoader(ds, batch_size=4)]
    for workers in (0, 2):
        dl = DataLoader(ds, batch_size=4, num_workers=workers,
                        thread_pool=True if workers else None,
                        prefetch_to_device=True)
        got = [b.asnumpy() for b in dl]
        assert len(got) == len(plain)
        for a, b in zip(got, plain):
            onp.testing.assert_array_equal(a, b)
        dl.close()


def test_dataloader_resume_with_buffered_unserved_batches():
    """The prefetcher buffers batches ahead of the loop; the resume cursor
    must track batches YIELDED, so buffered-but-unserved batches replay
    bitwise after restore."""
    x = onp.random.RandomState(5).rand(32, 3).astype("float32")
    ds = ArrayDataset(x)

    def make():
        return DataLoader(ds, batch_size=4,
                          sampler=RandomSampler(32, seed=9),
                          prefetch_to_device=True, device_prefetch_depth=3)

    loader = make()
    it = iter(loader)
    seen = [next(it).asnumpy() for _ in range(3)]
    time.sleep(0.2)  # let the prefetcher buffer batches past the cursor
    state = loader.state_dict()
    assert state["cursor"] == 3
    rest_truth = [b.asnumpy() for b in it]

    loader2 = make()
    loader2.load_state_dict(state)
    rest = [b.asnumpy() for b in loader2]
    assert len(rest) == len(rest_truth) == 8 - 3
    for a, b in zip(rest, rest_truth):
        onp.testing.assert_array_equal(a, b)
    assert seen


def test_shm_ring_grant_return_protocol():
    """Unit-level ring invariants: granted names leave the pool, returned
    names re-enter it, overflow unlinks, close() unlinks everything."""
    from multiprocessing import shared_memory
    from mxnet_tpu.gluon.data.dataloader import _ShmRing
    ring = _ShmRing(max_segments=2)
    segs = [shared_memory.SharedMemory(create=True, size=1024)
            for _ in range(3)]
    names = [s.name for s in segs]
    for s in segs:
        s.close()
    for n in names:
        ring.give_back(n, 1024)
    # max 2: the oldest was retired (unlinked)
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name=names[0])
    ring.last_sizes = [512]
    grants = ring.grant()
    assert grants == [(names[1], 1024)]  # best-fit pop, FIFO preference
    assert len(ring._free) == 1
    ring.give_back(names[1], 1024)
    ring.close()
    for n in names[1:]:
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=n)


# ---------------------------------------------------------------------------
# persistent compilation cache knob
# ---------------------------------------------------------------------------

def test_compile_cache_knob_configures_jax(tmp_path, monkeypatch):
    import jax
    from mxnet_tpu import _compile_cache
    # a cache placed from outside would win (tests/test_bring_up.py)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = str(tmp_path / "xla-cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        mx.config.set("compilation_cache_dir", cache_dir)
        applied = _compile_cache.configure()
        assert applied == cache_dir
        assert jax.config.jax_compilation_cache_dir == cache_dir
        import os
        assert os.path.isdir(cache_dir)
    finally:
        mx.config.set("compilation_cache_dir", "")
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_event_listeners_feed_telemetry():
    telemetry.enable()
    from mxnet_tpu import _compile_cache
    _compile_cache._install_listeners()
    from jax import monitoring
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.01)
    snap = telemetry.counters(aggregate=True)
    assert snap.get("compile.persistent_cache_requests_total", 0) >= 1
    assert snap.get("compile.persistent_cache_hits_total", 0) >= 1
    hist = telemetry.snapshot()["histograms"].get(
        "compile.persistent_cache_retrieval_seconds")
    assert hist and hist["count"] >= 1


# ---------------------------------------------------------------------------
# sharded training integration
# ---------------------------------------------------------------------------

def test_sharded_prefetch_skips_reput_and_stays_sync_free():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.train import ShardedTrainStep
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    telemetry.enable()
    mesh = make_mesh({"dp": 8})
    net = nn.Dense(4, in_units=8)
    net.initialize()

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    step = ShardedTrainStep(net, loss_fn, "sgd", mesh,
                            batch_specs=(P("dp"), P("dp")), n_labels=1)

    def batches():
        rs = onp.random.RandomState(3)
        for _ in range(4):
            yield (rs.rand(16, 8).astype("float32"),
                   rs.randint(0, 4, (16,)).astype("int32"))

    losses = []
    with pipeline.sync_guard() as g:
        for b in step.prefetch(batches()):
            # the prefetch thread already laid the batch out on the step's
            # shardings: ensure_sharded must be an identity (no device_put,
            # no sync) on the consumer thread
            losses.append(step(*b))
    assert g.count == 0, g.sites
    assert len(losses) == 4
    assert all(onp.isfinite(float(l.asnumpy())) for l in losses)
    snap = telemetry.counters(aggregate=True)
    assert snap.get("pipeline.batches_total", 0) >= 4
    assert snap.get("pipeline.h2d_bytes_total", 0) > 0
