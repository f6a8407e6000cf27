#!/usr/bin/env python
"""Per-process scaling probe, run under tools/launch.py.

Each rank pins itself to a distinct core set BEFORE importing jax, so the
measured collective latency is communication + framework overhead — not
the core contention that pollutes the in-process virtual-mesh table
(MULTICHIP weak-scaling caveat). Prints one line per rank:

    PROC_SCALING {"rank", "n", "compute_ms", "allreduce": [...]}

``--loader-gate`` instead runs the proc-vs-thread DataLoader regression
fence (no distributed setup, no affinity pin): the spawn process pool
must deliver >= 0.8x the thread pool's throughput on the GIL-bound
python-transform dataset, or the PR that reintroduced per-epoch pool
spinup / shm churn fails CI. Prints one line and exits nonzero on
regression:

    LOADER_GATE {"ok", "ratio", "threshold", ...}

Reference anchor: tools/bandwidth/measure.py + tests/nightly/
dist_sync_kvstore.py launch scheme.
"""
import json
import os
import sys
import time

_LOADER_GATE = "--loader-gate" in sys.argv


def _loader_gate(workers=2, n=32, dim=2048, batch=16, threshold=0.8):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataloader import _PyBenchDataset

    ds = _PyBenchDataset(n, dim)

    def run(thread_pool, repeats=2):
        dl = DataLoader(ds, batch_size=batch, num_workers=workers,
                        thread_pool=thread_pool)
        # warm the pool first: the persistent spawn pool boots lazily and
        # its worker-import cost is a fixed startup fee, not loader
        # throughput (the thing the 0.8x fence guards)
        for _ in range(1 if thread_pool else 3):
            for _b in dl:
                pass
        best = 0.0
        for _ in range(repeats):  # best-of-N absorbs 1-core CI jitter
            t0 = time.perf_counter()
            cnt = 0
            for b in dl:
                cnt += b.shape[0]
            best = max(best, cnt / (time.perf_counter() - t0))
        if not thread_pool:
            dl._proc_pool.shutdown(wait=False, cancel_futures=True)
        return best

    thr = run(True)
    proc = run(False)
    ratio = proc / thr
    ok = ratio >= threshold
    print("LOADER_GATE " + json.dumps({
        "ok": ok, "ratio": round(ratio, 3), "threshold": threshold,
        "proc_items_per_s": round(proc, 1),
        "thread_items_per_s": round(thr, 1),
        "workers": workers, "n": n, "cpu_count": os.cpu_count()}),
        flush=True)
    return 0 if ok else 1


if _LOADER_GATE and __name__ == "__main__":
    sys.exit(_loader_gate())

if not _LOADER_GATE:
    # scaling-probe mode only: the loader gate must not pin cores or join
    # the coordinator, and neither may the spawn workers that re-execute
    # this module as __mp_main__.
    rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
    nproc = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    ncores = os.cpu_count() or 1
    per = max(1, ncores // max(nproc, 1))
    cores = {(rank * per + i) % ncores for i in range(per)}  # wraps when
    os.sched_setaffinity(0, cores)                           # ranks > cores

    import jax  # noqa: E402  (after affinity pinning)

    from mxnet_tpu._dist_init import ensure_distributed  # noqa: E402

    ensure_distributed()

    import jax.numpy as jnp  # noqa: E402

    from mxnet_tpu.parallel.collectives import (  # noqa: E402
        allreduce_across_processes)


def main():
    # local compute reference: jitted 512^2 matmul chain on this rank's core
    m = jnp.ones((512, 512), jnp.float32)
    f = jax.jit(lambda x: x @ x * 0.999)
    f(m).block_until_ready()
    t0 = time.perf_counter()
    out = m
    for _ in range(20):
        out = f(out)
    out.block_until_ready()
    compute_ms = (time.perf_counter() - t0) / 20 * 1e3

    rows = []
    for nfloat in (1 << 18, 1 << 22):          # 1 MiB, 16 MiB payloads
        v = jnp.ones((nfloat,), jnp.float32)
        allreduce_across_processes(v).block_until_ready()  # compile+connect
        t0 = time.perf_counter()
        iters = 8
        for _ in range(iters):
            out = allreduce_across_processes(v)
        out.block_until_ready()
        ms = (time.perf_counter() - t0) / iters * 1e3
        rows.append({"bytes": nfloat * 4, "allreduce_ms": round(ms, 3),
                     "gbps": round(nfloat * 4 * 8 / (ms / 1e3) / 1e9, 2)})

    print("PROC_SCALING " + json.dumps({
        "rank": rank, "n": nproc, "cores_per_rank": per,
        "compute_ms": round(compute_ms, 3), "allreduce": rows}),
        flush=True)


if __name__ == "__main__":
    main()
