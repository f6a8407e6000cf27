"""gluon.data.DataLoader.

Reference parity: python/mxnet/gluon/data/dataloader.py (multiprocessing
workers + shared-memory NDArray pickling + prefetch queue; C++ alternative
src/io/dataloader.cc ThreadedDataLoader).

TPU-native design: worker processes/threads produce host numpy batches
(the shared-memory NDArray trick doesn't apply to device memory — SURVEY §7
hard parts); the main process converts the final batch to a device array, so
the host->HBM transfer is one contiguous copy per batch and can overlap with
compute thanks to async dispatch. num_workers>0 uses a thread pool (numpy
decode releases the GIL); a process pool is used when spawn-safe.
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import os
import pickle
import time

import numpy as onp

from ... import config as _config
from ... import fault as _fault
from ... import numpy as _np
from ... import pipeline as _pipeline
from ... import telemetry as _telemetry
from ... import trace as _trace
from ...numpy.multiarray import ndarray
from .sampler import BatchSampler, RandomSampler, SequentialSampler


def default_batchify_fn(data):
    """Stack samples (reference: dataloader.py default_batchify_fn).

    numpy samples assemble into a pooled host staging buffer
    (mx.storage, the cpu_pinned/CommCPU-merge-buffer analog) so repeated
    batches recycle one aligned block instead of re-mallocing."""
    if isinstance(data[0], ndarray):
        return _np.stack(data)
    if isinstance(data[0], (tuple, list)):
        return type(data[0])(default_batchify_fn(list(x)) for x in zip(*data))
    first = onp.asarray(data[0])
    if first.size and all(isinstance(d, onp.ndarray)
                          and d.shape == first.shape
                          and d.dtype == first.dtype for d in data):
        from ... import storage
        from ...numpy.multiarray import _wrap
        import jax
        import jax.numpy as jnp
        out = storage.pinned_array((len(data),) + first.shape, first.dtype)
        for i, d in enumerate(data):
            out[i] = d
        # on a CPU backend jnp.asarray zero-copies the aligned pooled
        # block; the pool then recycles it under the live device array and
        # later batches overwrite earlier ones. Force a real copy there.
        # On an accelerator the host->HBM transfer already copies, and the
        # pooled staging block is exactly what we want to hand it.
        if jax.default_backend() == "cpu":
            return _wrap(jnp.array(out, copy=True))
        return _np.array(out)
    return _np.array(onp.asarray(data))


def default_mp_batchify_fn(data):
    """Worker-process batchify: stacks to HOST numpy (reference:
    dataloader.py:55 builds NDArrays in shared memory; device buffers
    cannot cross a process boundary, so workers stay numpy and the main
    process does the one host->HBM copy per batch)."""
    if isinstance(data[0], ndarray):
        data = [d.asnumpy() for d in data]
    if isinstance(data[0], (tuple, list)):
        return type(data[0])(
            default_mp_batchify_fn(list(x)) for x in zip(*data))
    return onp.stack([onp.asarray(d) for d in data])


# ---------------------------------------------------------------------------
# multiprocess workers (reference: dataloader.py:28-187 worker_loop +
# ConnectionWrapper + shared-memory NDArray rebuild over
# src/storage/cpu_shared_storage_manager.h). Transport here is
# multiprocessing.shared_memory: the worker packs ALL leaves of a batch
# into ONE shm segment at 64-byte-aligned offsets and ships a single
# ("pack", name, tree, alloc, created) spec whose tree leaves carry
# (shape, dtype, offset); the main process copies each leaf out into a
# device array.  One grant/attach/give_back per BATCH instead of per
# leaf — the per-leaf segment churn (and its per-leaf pool round trips)
# is what made process workers slower than threads.  With the
# dataloader.shm_ring knob (default on) segments are pooled and reused
# across batches; otherwise each segment is unlinked after its one batch
# (the historical protocol).
# ---------------------------------------------------------------------------

_worker_state = {}


def _mp_worker_init(payload):
    # a chip belongs to one process and the parent holds it: pin the
    # worker to the CPU platform before anything here can start a
    # back-end.  ``payload`` arrives pickled so that this runs first —
    # unpickling a dataset that holds mx arrays creates device buffers.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    dataset, batchify = pickle.loads(payload)
    _worker_state["dataset"] = dataset
    _worker_state["batchify"] = batchify
    _worker_state["segs"] = {}  # name -> SharedMemory (attached handles)


def _grant_segment(nbytes, grants):
    """Pick a segment for one packed batch: best-fit from the parent's grant list
    (mutated: used grants are popped), else create a fresh power-of-2
    sized block — round sizes recur, so the parent's pool converges on a
    small set of reusable segments.  Attached handles are cached in
    ``_worker_state['segs']`` (LRU, bounded) so reuse costs zero
    open/mmap."""
    from multiprocessing import shared_memory
    segs = _worker_state.setdefault("segs", {})
    best = None
    for i, (name, size) in enumerate(grants):
        if size >= nbytes and (best is None or size < grants[best][1]):
            best = i
    if best is not None:
        name, size = grants.pop(best)
        shm = segs.get(name)
        if shm is None:
            try:
                shm = shared_memory.SharedMemory(name=name)
                segs[name] = shm
            except FileNotFoundError:  # parent retired it meanwhile
                shm = None
        if shm is not None:
            segs[name] = segs.pop(name)  # LRU touch
            return shm, name, size, False
    size = 1 << (max(nbytes, 1) - 1).bit_length()
    shm = shared_memory.SharedMemory(create=True, size=size)
    segs[shm.name] = shm
    while len(segs) > 64:  # stale handles accumulate only via retires
        segs.pop(next(iter(segs))).close()
    return shm, shm.name, size, True


#: leaf offsets inside a packed segment are cache-line aligned so the
#: consumer-side views copy at full memcpy speed
_PACK_ALIGN = 64


def _pack_layout(batch, leaves, offset):
    """Flatten ``batch`` into ``leaves`` ([(array, offset)], appended in
    tree order, offsets :data:`_PACK_ALIGN`-aligned) and return
    ``(tree, end)`` where the tree's leaves are ("leaf", shape, dtype,
    offset) and ``end`` is the packed payload size so far."""
    if isinstance(batch, (tuple, list)):
        parts = []
        for b in batch:
            sub, offset = _pack_layout(b, leaves, offset)
            parts.append(sub)
        return (type(batch).__name__, parts), offset
    a = onp.ascontiguousarray(onp.asarray(batch))
    offset = -(-offset // _PACK_ALIGN) * _PACK_ALIGN
    leaves.append((a, offset))
    return ("leaf", a.shape, str(a.dtype), offset), offset + a.nbytes


def _to_shm(batch, grants=None):
    """Serialize one batch into a SINGLE packed shm segment (all leaves
    at aligned offsets behind one header) so the whole batch costs one
    grant/attach/give_back round trip.  ``grants`` is the mutable list of
    (name, size) segments the parent loaned this task (ring mode; used
    grants are popped); None means a one-shot segment the parent will
    unlink after copying."""
    from multiprocessing import shared_memory
    leaves = []
    tree, total = _pack_layout(batch, leaves, 0)
    total = max(total, 1)
    if grants is None:
        shm = shared_memory.SharedMemory(create=True, size=total)
        name, size, created = shm.name, total, True
    else:
        shm, name, size, created = _grant_segment(total, grants)
    for a, off in leaves:
        onp.ndarray(a.shape, a.dtype, buffer=shm.buf, offset=off)[...] = a
    if grants is None:
        shm.close()
    return ("pack", name, tree, size, created)


def _mp_worker_task(indices, fault_step=0, grants=None, trace_ctx=None):
    # fault hooks (armed via MXNET_FAULT_SPEC, inherited by the spawned
    # worker's environment): crash = hard death with no cleanup, the
    # failure a preempted/OOM-killed worker produces; hang = the worker
    # stops producing, which the parent's heartbeat deadline must catch.
    # fault_step is the parent's global task sequence, so at=N fires
    # deterministically regardless of which worker runs the task.
    if _fault._active:
        if _fault.fire("dataloader.worker_crash", step=fault_step):
            os._exit(117)
        if _fault.fire("dataloader.worker_hang", step=fault_step):
            time.sleep(3600)
    # trace_ctx is the consumer's (trace_id, span_id): spans built here
    # ride the result tuple back and land on the parent's timeline (the
    # trace clock is CLOCK_MONOTONIC, system-wide on Linux)
    t0u = _trace.clock_us() if trace_ctx is not None else 0
    ds, bf = _worker_state["dataset"], _worker_state["batchify"]
    grants = list(grants) if grants is not None else None
    fetch = getattr(ds, "sample_batch", None)
    samples = (fetch(indices) if fetch is not None
               else [ds[i] for i in indices])
    spec = _to_shm(bf(samples), grants)
    spans = []
    if trace_ctx is not None:
        spans.append(_trace.make_span(
            "dataloader.worker_batch", t0u, _trace.clock_us() - t0u,
            tuple(trace_ctx), category="dataloader",
            samples=len(indices), task_seq=fault_step,
            worker_pid=os.getpid()))
    # leftover grants ride back so the parent can return them to the pool
    return (grants or [], spec, spans)


class _ShmRing:
    """Parent-side pool of reusable SharedMemory segments.

    Ownership protocol (overwrite-safe by construction): a segment name
    lives in exactly one place at any time — the free pool, the grant
    list of one in-flight task, or one unconsumed result spec.
    ``grant()`` moves names out best-fit against the previous batch's
    packed-segment size; ``give_back()`` returns them after the device
    copy;
    pool overflow unlinks oldest-first (``dataloader.shm_ring_max``).
    Attached parent mappings are cached so a reused segment costs zero
    open/mmap on the copy side too.
    """

    def __init__(self, max_segments):
        self._free = []       # [(size, name)] insertion order
        self._attached = {}   # name -> SharedMemory
        self._max = max(1, int(max_segments))
        self.last_sizes = []  # packed segment bytes of the latest batch

    def grant(self):
        grants = []
        for want in self.last_sizes:
            best = None
            for i, (size, _name) in enumerate(self._free):
                if size >= want and (best is None
                                     or size < self._free[best][0]):
                    best = i
            if best is not None:
                size, name = self._free.pop(best)
                grants.append((name, size))
        return grants

    def attach(self, name):
        shm = self._attached.get(name)
        if shm is None:
            from multiprocessing import shared_memory
            shm = shared_memory.SharedMemory(name=name)
            self._attached[name] = shm
        return shm

    def give_back(self, name, size):
        self._free.append((size, name))
        while len(self._free) > self._max:
            self._retire(self._free.pop(0)[1])

    def _retire(self, name):
        from multiprocessing import shared_memory
        shm = self._attached.pop(name, None)
        if shm is None:
            try:
                shm = shared_memory.SharedMemory(name=name)
            except FileNotFoundError:
                return
        shm.close()
        try:
            shm.unlink()
        except FileNotFoundError:
            pass

    def close(self):
        """Unlink every pooled segment (DataLoader.close / __del__)."""
        while self._free:
            self._retire(self._free.pop()[1])
        for name in list(self._attached):
            self._retire(name)


def _free_shm(spec, ring=None):
    """Return a batch's packed shm segment without copying (abandoned
    iterator): back into the ring, or unlinked in one-shot mode."""
    from multiprocessing import shared_memory
    _, name, _tree, alloc, _created = spec
    if ring is not None:
        ring.give_back(name, alloc)
        return
    try:
        shm = shared_memory.SharedMemory(name=name)
        shm.close()
        shm.unlink()
    except FileNotFoundError:
        pass


def _unpack_tree(tree, buf):
    """Copy every leaf of a packed segment out of ``buf`` into device
    arrays, rebuilding the original tuple/list nesting."""
    if tree[0] == "leaf":
        _, shape, dtype, off = tree
        import jax.numpy as jnp
        from ...numpy.multiarray import _wrap
        view = onp.ndarray(shape, dtype, buffer=buf, offset=off)
        # copy=True is load-bearing: a CPU backend would otherwise
        # zero-copy the mapping, which the ring reuses underneath
        out = _wrap(jnp.array(view, copy=True))
        out._data.block_until_ready()  # transfer done before reuse
        return out
    kind, parts = tree
    seq = [_unpack_tree(p, buf) for p in parts]
    return tuple(seq) if kind == "tuple" else seq


def _from_shm(spec, ring=None, sizes=None):
    from multiprocessing import shared_memory
    _, name, tree, alloc, created = spec
    if ring is not None:
        shm = ring.attach(name)
        out = _unpack_tree(tree, shm.buf)
        if sizes is not None:
            sizes.append(alloc)
        ring.give_back(name, alloc)
        if _telemetry._active:
            _telemetry.inc("dataloader.shm_created_total" if created
                           else "dataloader.shm_reused_total")
    else:
        shm = shared_memory.SharedMemory(name=name)
        try:
            out = _unpack_tree(tree, shm.buf)
        finally:
            # ... the one-shot mapping instead dies right here
            shm.close()
            shm.unlink()
    return out


class DataLoader:
    """Reference: dataloader.py DataLoader."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=None, timeout=120,
                 try_nopython=None, prefetch_to_device=None,
                 device_prefetch_depth=None):
        # prefetch_to_device: None/False = off (the historical behavior);
        # True = overlap host->device transfer with compute via
        # mx.pipeline.DevicePrefetcher against the default device; a
        # jax Device / Sharding (or per-leaf sequence) targets that
        # placement (sharded training passes the step's batch shardings).
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._num_workers = max(0, num_workers)
        self._timeout = timeout
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size required when no batch_sampler")
            if sampler is None:
                sampler = (RandomSampler(len(dataset)) if shuffle
                           else SequentialSampler(len(dataset)))
            elif shuffle:
                raise ValueError("shuffle and sampler are mutually exclusive")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        self._batch_sampler = batch_sampler
        # thread_pool=None -> mode from mx.config dataloader.worker_mode
        # ('auto' probes the per-sample cost, see _resolve_worker_mode);
        # explicit True/False keeps the historical meaning
        self._thread_pool = thread_pool
        self._user_batchify = batchify_fn
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._proc_pool = None
        self._worker_mode_cache = None
        self._force_threads = False   # set after repeated worker crashes
        self._task_seq = 0            # global task counter (fault at=N)
        self._served = 0              # batches handed to the training loop
        self._prefetch_to_device = prefetch_to_device
        self._device_prefetch_depth = device_prefetch_depth
        self._ring = None             # _ShmRing, built lazily by _mp_pump

    def _batchify(self, mp_mode):
        if self._user_batchify is not None:
            return self._user_batchify
        return default_mp_batchify_fn if mp_mode else default_batchify_fn

    # kept as an attribute for callers/tests that introspect the loader
    @property
    def _batchify_fn(self):
        return self._batchify(self._resolve_worker_mode() == "processes"
                              and self._num_workers > 0)

    def _resolve_worker_mode(self):
        """'threads' or 'processes' for num_workers>0.

        The shm transport makes process workers slower per batch than
        threads for anything that releases the GIL (numpy decode), while GIL-bound pure-python transforms only scale
        in processes.  'auto' (the default) probes the cost of one sample
        eagerly and picks processes only above
        mx.config dataloader.mp_threshold_ms; MXNET_DATALOADER_WORKER_MODE
        overrides.  Crash fallback: after dataloader.max_respawns worker
        pool deaths the loader degrades to threads permanently.
        """
        if self._force_threads:
            return "threads"
        if self._thread_pool is not None:
            return "threads" if self._thread_pool else "processes"
        mode = _config.get("dataloader.worker_mode")
        if mode in ("threads", "processes"):
            return mode
        if mode != "auto":
            raise ValueError(f"dataloader.worker_mode {mode!r} not in "
                             "('auto', 'threads', 'processes')")
        if self._worker_mode_cache is None:
            n = min(len(self._dataset), 3)
            if n == 0:
                self._worker_mode_cache = "threads"
            else:
                t0 = time.perf_counter()
                for i in range(n):
                    self._dataset[i]
                per_ms = (time.perf_counter() - t0) * 1000.0 / n
                self._worker_mode_cache = (
                    "processes"
                    if per_ms >= _config.get("dataloader.mp_threshold_ms")
                    else "threads")
        return self._worker_mode_cache

    def _make_batch(self, indices):
        # streaming sources (mx.stream.StreamDataset) fetch whole
        # batches: the corrupt-record skip policy must be able to shrink
        # a batch, which per-item __getitem__ cannot express
        fetch = getattr(self._dataset, "sample_batch", None)
        samples = (fetch(indices) if fetch is not None
                   else [self._dataset[i] for i in indices])
        return self._batchify(False)(samples)

    def _get_proc_pool(self):
        # persistent spawn pool (reference keeps its worker pool for the
        # loader lifetime, dataloader.py:520); spawn not fork — the parent
        # holds live PJRT/XLA state that must not be forked
        if self._proc_pool is None:
            import multiprocessing as mp
            from multiprocessing.reduction import ForkingPickler
            payload = bytes(ForkingPickler.dumps(
                (self._dataset, self._batchify(True))))
            self._proc_pool = cf.ProcessPoolExecutor(
                self._num_workers,
                mp_context=mp.get_context("spawn"),
                initializer=_mp_worker_init,
                initargs=(payload,))
        return self._proc_pool

    def _kill_pool(self):
        """Tear down the worker pool hard: hung workers never exit on
        their own, so terminate before shutdown."""
        pool, self._proc_pool = self._proc_pool, None
        if pool is None:
            return
        for p in list(getattr(pool, "_processes", {}).values()):
            try:
                p.terminate()
            except Exception:  # noqa: BLE001 - already-dead workers
                pass
        pool.shutdown(wait=False, cancel_futures=True)

    def __iter__(self):
        # the served-batch cursor is what TrainState bundles record: with
        # prefetching workers, batches *generated* run ahead of batches the
        # training loop has actually consumed, and resume must continue at
        # the consumed position
        self._served = (self._batch_sampler.resume_cursor()
                        if hasattr(self._batch_sampler, "resume_cursor")
                        else 0)
        src = self._iter_impl()
        pf = None
        if self._prefetch_to_device not in (None, False):
            # the served counter stays on the *consumer* side of the
            # prefetcher: batches it has buffered but not yet handed out
            # are replayed after a resume, not skipped
            target = self._prefetch_to_device
            pf = src = _pipeline.DevicePrefetcher(
                src, shardings=None if target is True else target,
                depth=self._device_prefetch_depth)
        try:
            for batch in src:
                self._served += 1
                yield batch
        finally:
            if pf is not None:
                pf.close()

    def _iter_impl(self):
        if self._num_workers == 0:
            for indices in self._batch_sampler:
                yield self._make_batch(indices)
            return
        if self._resolve_worker_mode() == "threads":
            # thread-pool pipeline with bounded prefetch (the analog of
            # iter_prefetcher.h's threaded prefetch chain)
            with cf.ThreadPoolExecutor(self._num_workers) as pool:
                yield from self._pump(pool, self._make_batch, lambda r: r,
                                      iter(self._batch_sampler))
            return
        yield from self._mp_pump()

    # -- elastic resume (docs/FAULT_TOLERANCE.md "Preemption & elastic
    # resume"): the loader's position is {epoch replay state, batches
    # served}; restoring it makes the next iteration continue at the exact
    # next batch of the interrupted epoch ------------------------------------
    def state_dict(self):
        from ...base import MXNetError
        if not hasattr(self._batch_sampler, "state_dict"):
            raise MXNetError(
                f"batch_sampler {type(self._batch_sampler).__name__} has no "
                "state_dict; implement state_dict/load_state_dict to make "
                "this DataLoader resumable")
        return self._batch_sampler.state_dict(cursor=self._served)

    def load_state_dict(self, state):
        from ...base import MXNetError
        if not hasattr(self._batch_sampler, "load_state_dict"):
            raise MXNetError(
                f"batch_sampler {type(self._batch_sampler).__name__} has no "
                "load_state_dict; cannot resume this DataLoader")
        self._batch_sampler.load_state_dict(state)

    def publish_cursor(self, **kwargs):
        """Streaming passthrough: publish the sampler's cursor at the
        CONSUMED position (``self._served``) to the shared fleet dir —
        what a surviving host resumes a dead peer's shards from.  No-op
        for non-streaming samplers."""
        publish = getattr(self._batch_sampler, "publish_cursor", None)
        if publish is None:
            return None
        kwargs.setdefault("cursor", self._served)
        return publish(**kwargs)

    def take_over_host(self, dead_rank, **kwargs):
        """Streaming passthrough: adopt this host's share of a dead
        peer's unfinished shards (see StreamSampler.take_over_host)."""
        take = getattr(self._batch_sampler, "take_over_host", None)
        return take(dead_rank, **kwargs) if take is not None else 0

    def _pump(self, pool, task, unwrap, batches, dispose=None):
        pending = []
        it = iter(batches)
        try:
            try:
                for _ in range(self._prefetch or self._num_workers):
                    pending.append(pool.submit(task, next(it)))
            except StopIteration:
                pass
            while pending:
                fut = pending.pop(0)
                try:
                    pending.append(pool.submit(task, next(it)))
                except StopIteration:
                    pass
                if _telemetry._active:
                    # batch wait = how long the training loop starves on
                    # input; queue depth = prefetch headroom at that moment
                    _telemetry.set_gauge("dataloader.queue_depth",
                                         len(pending) + 1)
                    _t0 = time.perf_counter()
                    result = fut.result(timeout=self._timeout)
                    _telemetry.observe("dataloader.wait_seconds",
                                       time.perf_counter() - _t0)
                    _telemetry.inc("dataloader.batches_total")
                    yield unwrap(result)
                else:
                    yield unwrap(fut.result(timeout=self._timeout))
        finally:
            # abandoned mid-epoch (break / islice / GC): in-flight batches
            # carry shm blocks only _from_shm would unlink — drain them
            if dispose is not None:
                for fut in pending:
                    try:
                        dispose(fut.result(timeout=self._timeout))
                    except Exception:  # noqa: BLE001 - best-effort cleanup
                        pass

    def _mp_pump(self):
        """Process-worker pipeline with crash/hang recovery.

        Worker death (BrokenProcessPool) or a missed per-batch heartbeat
        deadline (``timeout``) tears the pool down and respawns it with
        exponential backoff, re-queueing every in-flight batch in order;
        after ``mx.config dataloader.max_respawns`` pool losses the loader
        degrades to threaded workers for the rest of its life (graceful
        degradation beats an unusable input pipeline).  Every recovery
        action is counted in ``mx.fault.stats()``.
        """
        from concurrent.futures.process import BrokenProcessPool
        max_respawns = _config.get("dataloader.max_respawns")
        backoff = _config.get("dataloader.respawn_backoff")
        depth = max(1, self._prefetch or self._num_workers)
        if self._ring is None and _config.get("dataloader.shm_ring"):
            self._ring = _ShmRing(_config.get("dataloader.shm_ring_max"))
        ring = self._ring
        todo = collections.deque(self._batch_sampler)
        inflight = collections.deque()  # (future, indices, grants), oldest 1st
        crashes = 0
        try:
            while todo or inflight:
                try:
                    pool = self._get_proc_pool()
                    while todo and len(inflight) < depth:
                        indices = todo.popleft()
                        self._task_seq += 1
                        grants = ring.grant() if ring is not None else None
                        try:
                            inflight.append(
                                (pool.submit(_mp_worker_task, indices,
                                             self._task_seq, grants,
                                             (_trace.current_context()
                                              if _trace._active
                                              else None)),
                                 indices, grants))
                        except BaseException:
                            todo.appendleft(indices)
                            if ring is not None:
                                for name, size in grants:
                                    ring.give_back(name, size)
                            raise
                    fut, _, _ = inflight[0]
                    if _telemetry._active:
                        _telemetry.set_gauge("dataloader.queue_depth",
                                             len(inflight))
                        _t0 = time.perf_counter()
                        leftover, spec, wspans = \
                            fut.result(timeout=self._timeout)
                        _telemetry.observe("dataloader.wait_seconds",
                                           time.perf_counter() - _t0)
                        _telemetry.inc("dataloader.batches_total")
                    else:
                        leftover, spec, wspans = \
                            fut.result(timeout=self._timeout)
                    if wspans and _trace._active:
                        _trace.ingest(wspans)
                    inflight.popleft()
                except (BrokenProcessPool, cf.BrokenExecutor,
                        cf.TimeoutError, TimeoutError):
                    crashes += 1
                    # kill BEFORE reclaiming grants: a hung-but-alive
                    # worker could otherwise write into a segment the
                    # ring has already re-granted to a new task
                    self._kill_pool()
                    self._requeue(todo, inflight, ring)
                    if crashes > max_respawns:
                        _fault.record("dataloader.fallback_threaded")
                        self._force_threads = True
                        yield from self._threaded_remainder(todo)
                        return
                    _fault.record("dataloader.worker_respawn")
                    if _telemetry._active:
                        _telemetry.inc("dataloader.respawn_total")
                    time.sleep(backoff * (2 ** (crashes - 1)))
                    continue
                if ring is not None:
                    for name, size in leftover:
                        ring.give_back(name, size)
                    sizes = []
                    batch = _from_shm(spec, ring, sizes)
                    ring.last_sizes = sizes
                else:
                    batch = _from_shm(spec)
                yield batch
        finally:
            for fut, _, grants in inflight:
                try:
                    leftover, spec, _wspans = \
                        fut.result(timeout=self._timeout)
                    if ring is not None:
                        for name, size in leftover:
                            ring.give_back(name, size)
                    _free_shm(spec, ring)
                # CancelledError: futures we killed the pool under on a
                # previous loop pass (it subclasses BaseException)
                except (Exception, cf.CancelledError):  # noqa: BLE001
                    # a timed-out worker may still be alive and writing
                    # into its granted segments: kill the pool first so
                    # the ring never re-grants a segment under a live
                    # writer (mirrors the crash path above)
                    self._kill_pool()
                    if ring is not None and grants:
                        for name, size in grants:
                            ring.give_back(name, size)

    @staticmethod
    def _requeue(todo, inflight, ring=None):
        """Move every in-flight batch back onto the queue in order; shm
        blocks of tasks that did complete go back to the ring / are
        unlinked (their results are recomputed — a failure-path-only
        cost), and unused grants of tasks that didn't are reclaimed.
        Caller must have torn the pool down first (see _mp_pump)."""
        for fut, _, grants in inflight:
            if fut.done() and not fut.cancelled() and \
                    fut.exception() is None:
                try:
                    leftover, spec, _wspans = fut.result()
                    if ring is not None:
                        for name, size in leftover:
                            ring.give_back(name, size)
                    _free_shm(spec, ring)
                    continue
                except Exception:  # noqa: BLE001 - best-effort cleanup
                    pass
            if ring is not None and grants:
                for name, size in grants:
                    ring.give_back(name, size)
        todo.extendleft(indices for _, indices, _ in reversed(inflight))
        inflight.clear()

    def _threaded_remainder(self, todo):
        """Finish the epoch on threads after the process pool was given
        up on; the host-numpy batchify keeps batch values identical."""
        with cf.ThreadPoolExecutor(self._num_workers) as pool:
            yield from self._pump(pool, self._make_batch, lambda r: r,
                                  todo)

    def close(self):
        """Release worker pool and pooled shm segments.  Idempotent; also
        run from __del__, but deterministic teardown (tests, epoch-bounded
        scripts) should call it explicitly — unlinking pooled segments at
        GC time races interpreter shutdown."""
        self._kill_pool()
        ring, self._ring = self._ring, None
        if ring is not None:
            ring.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter-shutdown races
            pass

    def __len__(self):
        return len(self._batch_sampler)


class _PyBenchDataset:
    """Picklable synthetic dataset with a deliberately GIL-bound python
    transform (bench: dataloader_pytransform row)."""

    def __init__(self, n=256, dim=2048):
        rs = onp.random.RandomState(0)
        self.x = rs.rand(n, dim).astype(onp.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        row = self.x[i]
        acc = 0.0
        for _ in range(5):           # ~1 ms of pure-python GIL-bound work
            for v in row[:2048:1]:
                acc += float(v) * 1.0000001
        return row * onp.float32(1.0 + 0.0 * acc)
