"""Fault-injection framework + resilience layer (docs/FAULT_TOLERANCE.md).

Every recovery path ships with the chaos test that proves it: worker
crash/hang -> bounded respawn -> threaded fallback; NaN gradients -> step
skipped and counted; torn checkpoint -> checksum rejection + auto-resume
from the previous valid one; hung collective -> structured timeout. The
CI `chaos` stage additionally runs the env_spec test under a small
MXNET_FAULT_SPEC matrix (ci/run.sh chaos).
"""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import DataLoader


class _SynthDataset:
    """Picklable (spawn workers) linearly-separable classification set."""

    def __init__(self, n=128, dim=16, classes=3):
        rs = onp.random.RandomState(0)
        self.x = rs.rand(n, dim).astype(onp.float32)
        w = rs.rand(dim, classes).astype(onp.float32)
        self.y = (self.x @ w).argmax(axis=1).astype(onp.int32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    mx.fault.clear()
    mx.fault.reset_stats()
    mx.config.reset()


def _mlp(classes=3):
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(classes))
    net.initialize(mx.init.Xavier())
    return net


# ---------------------------------------------------------------------------
# framework basics
# ---------------------------------------------------------------------------

def test_spec_parse_and_api():
    armed = mx.fault.configure(
        "invoke.nan_output:at=3,times=1;serialization.torn_write:prob=0.5")
    assert armed == ["invoke.nan_output", "serialization.torn_write"]
    assert mx.fault.active()
    assert mx.fault.armed("invoke.nan_output")
    assert not mx.fault.armed("dataloader.worker_crash")
    assert "invoke.nan_output [at=3,times=1" in mx.fault.describe()
    mx.fault.clear()
    assert not mx.fault.active()

    with pytest.raises(MXNetError, match="unknown fault injection point"):
        mx.fault.configure("no.such.point:at=1")
    with pytest.raises(MXNetError, match="unknown key"):
        mx.fault.configure("invoke.nan_output:bogus=1")
    with pytest.raises(MXNetError, match="needs a trigger"):
        mx.fault.configure("invoke.nan_output")


def test_at_fires_exactly_once():
    mx.fault.configure("invoke.nan_output:at=3")
    fires = [mx.fault.fire("invoke.nan_output") for _ in range(6)]
    assert fires == [False, False, True, False, False, False]
    assert mx.fault.stats()["injected.invoke.nan_output"] == 1


def test_prob_stream_is_seeded_and_reproducible():
    mx.fault.configure("invoke.nan_output:prob=0.5,seed=7")
    first = [mx.fault.fire("invoke.nan_output") for _ in range(32)]
    mx.fault.configure("invoke.nan_output:prob=0.5,seed=7")
    again = [mx.fault.fire("invoke.nan_output") for _ in range(32)]
    assert first == again
    assert any(first) and not all(first)


def test_disabled_hooks_are_noops(tmp_path):
    assert not mx.fault.active()
    assert not mx.fault.fire("invoke.nan_output")
    # eager math unaffected
    out = (mx.np.ones((2, 2)) * 3).asnumpy()
    assert onp.isfinite(out).all()
    # serialization writes full bytes
    p = str(tmp_path / "x.bin")
    mx.serialization.atomic_write_bytes(p, b"abcdef" * 100)
    assert os.path.getsize(p) == 600
    assert mx.fault.stats() == {}


# ---------------------------------------------------------------------------
# DataLoader: crash -> bounded respawn -> threaded fallback; hang heartbeat
# ---------------------------------------------------------------------------

def _epoch_rows(loader):
    """Concatenate every batch's data rows, preserving batch order."""
    xs = [x.asnumpy() for x, _ in loader]
    return onp.concatenate(xs), len(xs)


def test_worker_crash_respawns_and_preserves_epoch(monkeypatch):
    monkeypatch.setenv("MXNET_FAULT_SPEC", "dataloader.worker_crash:at=2")
    ds = _SynthDataset(64)
    loader = DataLoader(ds, batch_size=8, num_workers=2, thread_pool=False,
                        timeout=60)
    rows, nbatches = _epoch_rows(loader)
    assert nbatches == 8
    # recovery re-queued the in-flight batches in order: identical epoch
    onp.testing.assert_array_equal(rows, ds.x)
    assert mx.fault.stats().get("dataloader.worker_respawn") == 1
    assert "dataloader.fallback_threaded" not in mx.fault.stats()


def test_worker_crash_storm_falls_back_to_threads(monkeypatch):
    monkeypatch.setenv("MXNET_FAULT_SPEC", "dataloader.worker_crash:prob=1.0")
    monkeypatch.setenv("MXNET_DATALOADER_MAX_RESPAWNS", "1")
    ds = _SynthDataset(16)
    loader = DataLoader(ds, batch_size=8, num_workers=2, thread_pool=False,
                        timeout=60)
    rows, nbatches = _epoch_rows(loader)
    assert nbatches == 2
    onp.testing.assert_array_equal(rows, ds.x)
    stats = mx.fault.stats()
    assert stats.get("dataloader.worker_respawn") == 1  # bounded
    assert stats.get("dataloader.fallback_threaded") == 1
    assert loader._force_threads
    # the degradation is permanent: the next epoch goes straight to threads
    rows2, _ = _epoch_rows(loader)
    onp.testing.assert_array_equal(rows2, ds.x)
    assert stats == mx.fault.stats()


def test_worker_hang_caught_by_heartbeat_deadline(monkeypatch):
    monkeypatch.setenv("MXNET_FAULT_SPEC", "dataloader.worker_hang:at=1")
    ds = _SynthDataset(16)
    loader = DataLoader(ds, batch_size=8, num_workers=2, thread_pool=False,
                        timeout=3)
    rows, nbatches = _epoch_rows(loader)
    assert nbatches == 2
    onp.testing.assert_array_equal(rows, ds.x)
    # at least one heartbeat miss was detected and recovered from; a loaded
    # host can miss the deadline again on the respawned pool (extra respawn
    # or even the threaded fallback) — the epoch contract above is what
    # matters
    assert mx.fault.stats().get("dataloader.worker_respawn", 0) >= 1


def test_worker_mode_auto_and_override(monkeypatch):
    ds = _SynthDataset(32)
    # cheap samples -> threads (the shm transport costs more than it buys)
    assert DataLoader(ds, batch_size=8,
                      num_workers=2)._resolve_worker_mode() == "threads"
    # a zero threshold makes any sample "expensive" -> processes
    mx.config.set("dataloader.mp_threshold_ms", 0.0)
    assert DataLoader(ds, batch_size=8,
                      num_workers=2)._resolve_worker_mode() == "processes"
    mx.config.reset("dataloader.mp_threshold_ms")
    # env override beats the probe
    monkeypatch.setenv("MXNET_DATALOADER_WORKER_MODE", "processes")
    assert DataLoader(ds, batch_size=8,
                      num_workers=2)._resolve_worker_mode() == "processes"
    monkeypatch.setenv("MXNET_DATALOADER_WORKER_MODE", "threads")
    assert DataLoader(ds, batch_size=8,
                      num_workers=2)._resolve_worker_mode() == "threads"
    # explicit constructor arg keeps its historical meaning
    monkeypatch.delenv("MXNET_DATALOADER_WORKER_MODE")
    assert DataLoader(ds, batch_size=8, num_workers=2,
                      thread_pool=True)._resolve_worker_mode() == "threads"
    assert DataLoader(ds, batch_size=8, num_workers=2,
                      thread_pool=False)._resolve_worker_mode() == "processes"


# ---------------------------------------------------------------------------
# Trainer: non-finite gradient guard
# ---------------------------------------------------------------------------

def test_nonfinite_grad_step_skipped_and_counted():
    mx.config.set("trainer.skip_nonfinite", True)
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.np.array(onp.random.RandomState(0).rand(4, 16).astype("float32"))
    y = mx.np.array(onp.array([0, 1, 2, 0], dtype="int32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # one clean step to settle initialization
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(4)
    assert trainer.nonfinite_steps == 0
    before = {k: v.data().asnumpy().copy()
              for k, v in net.collect_params().items()}

    # corrupt the first eager op of the next forward -> NaN gradients
    mx.fault.configure("invoke.nan_output:at=1,times=1")
    with autograd.record():
        loss = loss_fn(net(x), y)
    mx.fault.clear()
    loss.backward()
    trainer.step(4)

    assert trainer.nonfinite_steps == 1
    assert mx.fault.stats()["trainer.nonfinite_skip"] == 1
    for k, v in net.collect_params().items():
        onp.testing.assert_array_equal(v.data().asnumpy(), before[k],
                                       err_msg=f"{k} moved on skipped step")

    # a following clean step still updates
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(4)
    assert trainer.nonfinite_steps == 1
    moved = any(not onp.array_equal(v.data().asnumpy(), before[k])
                for k, v in net.collect_params().items())
    assert moved


def test_nonfinite_guard_backs_off_amp_scaler():
    from mxnet_tpu.amp.loss_scaler import LossScaler
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    trainer._amp_loss_scaler = scaler = LossScaler()
    assert trainer._guard_active()
    scale0 = scaler.loss_scale
    x = mx.np.array(onp.random.RandomState(1).rand(4, 16).astype("float32"))
    mx.fault.configure("invoke.nan_output:at=1,times=1")
    with autograd.record():
        loss = net(x).square().sum()
    mx.fault.clear()
    loss.backward()
    trainer.step(4)
    assert trainer.nonfinite_steps == 1
    assert scaler.loss_scale < scale0


# ---------------------------------------------------------------------------
# checkpoints: crash-atomicity, checksums, auto-resume
# ---------------------------------------------------------------------------

def test_atomic_write_cleans_stale_temps(tmp_path):
    p = str(tmp_path / "ckpt.bin")
    stale = p + ".tmp-12345"
    with open(stale, "wb") as f:
        f.write(b"leftover from a crashed save")
    mx.serialization.atomic_write_bytes(p, b"payload")
    assert not os.path.exists(stale)
    with open(p, "rb") as f:
        assert f.read() == b"payload"
    assert not [fn for fn in os.listdir(tmp_path) if ".tmp-" in fn]


def test_torn_write_rejected_by_checksum(tmp_path):
    p = str(tmp_path / "w.params")
    net = _mlp()
    net(mx.np.ones((1, 16)))
    net.save_parameters(p)
    mx.serialization.write_checksum(p)
    assert mx.serialization.verify_checksum(p) is True

    # silent truncation on the next save: the sidecar no longer matches
    mx.fault.configure("serialization.torn_write:at=1,times=1")
    net.save_parameters(p)
    mx.fault.clear()
    assert mx.fault.stats()["injected.serialization.torn_write"] == 1
    with pytest.raises(MXNetError, match="checksum mismatch"):
        mx.serialization.verify_checksum(p)
    with pytest.raises(MXNetError, match="checksum mismatch"):
        net.load_parameters(p)


class _EstimatorStub:
    def __init__(self, net, trainer):
        self.net = net
        self.trainer = trainer


def test_checkpoint_handler_auto_resume_skips_torn(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator.event_handler import \
        CheckpointHandler
    net = _mlp()
    net(mx.np.ones((1, 16)))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    est = _EstimatorStub(net, trainer)

    h = CheckpointHandler(str(tmp_path), epoch_period=1)
    for _ in range(3):
        h.epoch_end(est)
    for suffix in (".params", ".params.sha256", ".states", ".states.sha256"):
        assert os.path.exists(str(tmp_path / f"model-epoch3{suffix}"))

    # tear the newest checkpoint behind the checksum's back
    newest = str(tmp_path / "model-epoch3.params")
    with open(newest, "rb") as f:
        blob = f.read()
    with open(newest, "wb") as f:
        f.write(blob[:len(blob) // 2])

    h2 = CheckpointHandler(str(tmp_path), resume_from_checkpoint=True)
    h2.train_begin(est)
    assert h2.current_epoch == 2  # newest valid, not newest on disk
    stats = mx.fault.stats()
    assert stats["checkpoint.rejected"] == 1
    assert stats["checkpoint.resume"] == 1


# ---------------------------------------------------------------------------
# dist collectives: watchdog raises a structured diagnostic, never hangs
# ---------------------------------------------------------------------------

def test_collective_watchdog_structured_timeout():
    from mxnet_tpu.kvstore import CollectiveTimeout, DistKVStore
    kv = DistKVStore()
    kv.init("weight", mx.np.array([1.0, 2.0]))
    mx.config.set("kvstore.async_timeout", 0.3)
    # this test asserts the RAW watchdog contract; disable the elastic
    # retry layer (tests/test_resilience.py covers it)
    mx.config.set("kvstore.retry_max", 0)
    mx.fault.configure("kvstore.collective_timeout:at=1")
    with pytest.raises(CollectiveTimeout) as ei:
        kv.push("weight", mx.np.array([0.5, 0.5]))
    e = ei.value
    assert (e.op, e.key, e.rank, e.nprocs) == ("allreduce", "weight", 0, 1)
    assert e.elapsed >= 0.3
    assert "kvstore.async_timeout" in str(e)
    assert mx.fault.stats()["kvstore.collective_timeout_raised"] == 1
    mx.fault.clear()
    mx.config.reset("kvstore.retry_max")
    # disarmed single-process store goes back to the wait-free fast path
    kv.push("weight", mx.np.array([0.5, 0.5]))


def test_dist_async_watchdog_diagnostic_names_key_rank_and_knob():
    from mxnet_tpu.kvstore import CollectiveTimeout, DistAsyncKVStore
    kv = DistAsyncKVStore()
    kv.init("emb", mx.np.array([3.0]))
    mx.config.set("kvstore.async_timeout", 0.3)
    mx.config.set("kvstore.retry_max", 0)  # raw watchdog contract
    mx.fault.configure("kvstore.collective_timeout:at=1")
    out = mx.np.zeros(1)
    with pytest.raises(CollectiveTimeout) as ei:
        kv.pull("emb", out=out)
    msg = str(ei.value)
    assert "'emb'" in msg                      # names the key
    assert "rank 0/1" in msg                   # names the rank
    assert "kvstore.async_timeout" in msg      # points at the knob
    assert "pull schedule" in msg              # reconcile-specific hint
    assert ei.value.op.startswith("reconcile#")
    mx.fault.clear()
    mx.config.reset("kvstore.retry_max")
    # the reconciling pull works once disarmed (nprocs=1: identity)
    kv.pull("emb", out=out)
    assert out.asnumpy()[0] == 3.0


# ---------------------------------------------------------------------------
# end-to-end chaos: train through crashes, one NaN step, and a mid-run
# checkpoint restart — final metrics must come out correct anyway
# ---------------------------------------------------------------------------

def test_chaos_train_completes_with_correct_metrics(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_FAULT_SPEC", "dataloader.worker_crash:at=2")
    mx.config.set("trainer.skip_nonfinite", True)
    mx.random.seed(0)

    ds = _SynthDataset(256)
    loader = DataLoader(ds, batch_size=32, num_workers=2, thread_pool=False,
                        timeout=60)
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-2})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = gluon.metric.Accuracy()

    ckpt = str(tmp_path / "chaos")
    seen = 0
    for epoch in range(10):
        if epoch == 5:
            # simulate a restart: fresh model resumed from the checkpoint
            net = _mlp()
            net(mx.np.ones((1, 16)))
            net.load_parameters(ckpt + ".params")
            trainer = gluon.Trainer(net.collect_params(), "adam",
                                    {"learning_rate": 3e-2})
            trainer.load_states(ckpt + ".states")
        metric.reset()
        for i, (data, label) in enumerate(loader):
            if epoch == 1 and i == 2:
                # one poisoned forward; the guard must absorb it
                mx.fault.configure("invoke.nan_output:at=1,times=1")
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            mx.fault.clear()
            loss.backward()
            trainer.step(data.shape[0])
            metric.update([label], [out])
            seen += 1
        if epoch == 4:
            net.save_parameters(ckpt + ".params")
            trainer.save_states(ckpt + ".states")
            mx.serialization.write_checksum(ckpt + ".params")
            mx.serialization.write_checksum(ckpt + ".states")

    stats = mx.fault.stats()
    assert seen == 10 * len(loader)             # no batch lost to the chaos
    assert trainer.nonfinite_steps + stats.get(
        "trainer.nonfinite_skip", 0) >= 1      # the NaN step was skipped
    assert stats.get("dataloader.worker_respawn", 0) >= 1
    acc = metric.get()[1]
    assert acc > 0.9, f"chaos training diverged: accuracy {acc}"


# ---------------------------------------------------------------------------
# CI chaos matrix entrypoint: runs under whatever MXNET_FAULT_SPEC the
# stage exports (ci/run.sh chaos); skipped without one
# ---------------------------------------------------------------------------

def test_env_spec_chaos_smoke(tmp_path):
    spec = os.environ.get("MXNET_FAULT_SPEC", "")
    if not spec:
        pytest.skip("MXNET_FAULT_SPEC not set (CI chaos matrix only)")
    from mxnet_tpu.gluon.contrib.estimator.event_handler import \
        CheckpointHandler
    assert mx.fault.active()  # armed from the env at import
    mx.config.set("trainer.skip_nonfinite", True)

    ds = _SynthDataset(128)
    loader = DataLoader(ds, batch_size=32, num_workers=2, thread_pool=False,
                        timeout=60)
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1e-2})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    est = _EstimatorStub(net, trainer)
    handler = CheckpointHandler(str(tmp_path), epoch_period=1)

    seen = 0
    for _ in range(2):
        for data, label in loader:
            with autograd.record():
                loss = loss_fn(net(data), label)
            loss.backward()
            trainer.step(data.shape[0])
            seen += 1
        handler.epoch_end(est)
    assert seen == 2 * len(loader)

    resumer = CheckpointHandler(str(tmp_path), resume_from_checkpoint=True)
    resumer.train_begin(est)
    assert resumer.current_epoch >= 1  # some checkpoint validated

    stats = mx.fault.stats()
    recovery = ("dataloader.worker_respawn", "dataloader.fallback_threaded",
                "trainer.nonfinite_skip", "checkpoint.rejected")
    assert any(k.startswith("injected.") for k in stats) or \
        any(k in stats for k in recovery), f"no chaos observed: {stats}"
    mx.fault.log_stats()
